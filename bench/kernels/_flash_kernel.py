"""The causal flash attention forward of ``kernels/attention.py``: q.k and p.v,
two products of 2 x d operations per attended (query, key) pair, on q
[b * heads, sq, d] and k, v [b * kv_heads, sk, d]."""
from roofline import attended_pairs, nbytes

FAMILY = "flash_attention"


def work(ins, outs):
    (_, (bh, sq, d)), (_, (_, sk, _)) = ins[0], ins[1]
    return 4.0 * bh * d * attended_pairs(sq, sk), nbytes(ins) + nbytes(outs)
