"""The tiled matmul of ``kernels/matmul.py``: x [m, k] @ w [k, n], reading
each operand once and writing the result once."""
from roofline import BYTES

FAMILY = "matmul"


def work(ins, outs):
    (dt, (m, k)), (_, (_, n)) = ins[0], ins[1]
    return 2.0 * m * k * n, (m * k + k * n + m * n) * BYTES[dt]
