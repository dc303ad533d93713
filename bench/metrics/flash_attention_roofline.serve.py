"""Least time of the flash attention kernel's prefill calls in the window over
their device time."""
from readers import kernel_roofline

LAYER = "kernels (kernels/*.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy"]


def read(run):
    return kernel_roofline(run, "flash_attention")
