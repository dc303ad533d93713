"""Least time of the matmul kernel's calls in the window (prefill and decode)
over their device time."""
from readers import kernel_roofline

LAYER = "kernels (kernels/*.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy", "qwen2.5-3b.decode_heavy"]


def read(run):
    return kernel_roofline(run, "matmul")
