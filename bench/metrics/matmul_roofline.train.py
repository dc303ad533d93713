"""Least time of the matmul kernel's calls in the window (forward, backward and
recompute) over their device time."""
from readers import kernel_roofline

LAYER = "kernels (kernels/*.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
WORKLOADS = ["qwen2-0.5b.train_8x1k"]


def read(run):
    return kernel_roofline(run, "matmul")
