"""Least time of the flash attention kernels' calls in the window (forward, dq
and dk/dv passes) over their device time."""
from readers import kernel_roofline

LAYER = "kernels (kernels/*.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(run):
    return kernel_roofline(run, "flash_attention")
