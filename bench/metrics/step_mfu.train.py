"""Model operations of the window's training steps (6 x matmul parameters per
token plus causal attention, no recompute) over the traced window times the
chip's peak."""
from readers import train_mfu

LAYER = "model step (models/lm.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(run):
    return train_mfu(run)
