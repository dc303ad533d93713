"""Model operations of the window's requests (2 x matmul parameters per true
prompt and output token, plus causal attention) over the traced window times
the chip's peak."""
from readers import serve_mfu

LAYER = "model step (models/lm.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy", "qwen2.5-3b.decode_heavy"]


def read(run):
    return serve_mfu(run)
