"""Percent of the traced window in which collective ops (the gradient
all-reduce, the batch's all-to-all, by HLO opcode, async start and done
included) ran on the first device."""
from readers import collective_share

LAYER = "collectives (distributed/, the trainer's gradient all-reduce)"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(run):
    return collective_share(run)
