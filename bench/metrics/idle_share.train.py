"""Percent of the traced window in which no op ran on the device."""
from readers import idle_share

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
WORKLOADS = ["qwen2-0.5b.train_8x1k"]


def read(run):
    return idle_share(run)
