"""The flash attention backward pass for dk and dv (``kernels/attention.py``):
p.do and ds.q, counted as the forward's two products per attended pair."""
import os

import roofline

FAMILY = "flash_attention"
work = roofline.kernel("_flash_kernel", os.path.dirname(os.path.dirname(__file__))).work
