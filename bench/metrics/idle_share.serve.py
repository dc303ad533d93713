"""Percent of the traced window in which no op ran on the device."""
from readers import idle_share

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy", "qwen2.5-3b.decode_heavy"]


def read(run):
    return idle_share(run)
