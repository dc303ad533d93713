"""Percent of the traced window in which no op ran on the device."""
from readers import idle_share

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(run):
    return idle_share(run)
