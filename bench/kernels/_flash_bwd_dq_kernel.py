"""The flash attention backward pass for dq (``kernels/attention.py``): do.v and
ds.k, counted as the forward's two products per attended pair."""
import os

import roofline

FAMILY = "flash_attention"
work = roofline.kernel("_flash_kernel", os.path.dirname(os.path.dirname(__file__))).work
