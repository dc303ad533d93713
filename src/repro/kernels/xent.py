"""Fused softmax-cross-entropy over large vocabularies — Pallas kernel.

The assigned archs have vocabs up to 262144; materializing fp32 softmax for
[tokens, vocab] is the single largest activation in training. This kernel
streams vocab blocks through VMEM with an online logsumexp (the same running
(m, l) trick as flash attention) and extracts the label logit on the fly, so
HBM traffic is one read of the logits — never a [tokens, vocab] write.

Tunables: block_rows × block_v VMEM tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import Constraint, DispatchSpec, ParamSpace, PowerOfTwoParam, tunable
from ..core.platform import SCOPED_VMEM_BYTES
from . import ref

_NEG_INF = -1e30


def _xent_kernel(
    logits_ref, labels_ref, loss_ref, lse_ref,
    m_scr, l_scr, ll_scr,
    *,
    block_v: int,
    v_steps: int,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        ll_scr[...] = jnp.zeros_like(ll_scr)

    x = logits_ref[...].astype(jnp.float32)        # [block_rows, block_v]
    m_prev = m_scr[...]                            # [block_rows, 1]
    m_new = jnp.maximum(m_prev, x.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.exp(x - m_new).sum(axis=-1, keepdims=True)
    m_scr[...] = m_new

    # Gather the label logit if it falls inside this vocab block.
    labels = labels_ref[...]                       # [block_rows, 1] int32
    v_lo = vi * block_v
    cols = v_lo + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    hit = cols == labels
    ll_scr[...] += jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)

    @pl.when(vi == v_steps - 1)
    def _done():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        loss_ref[...] = (lse - ll_scr[...]).astype(loss_ref.dtype)
        lse_ref[...] = lse


def softmax_xent_pallas(
    logits: jax.Array,  # [rows, vocab]
    labels: jax.Array,  # [rows] int32
    *,
    block_rows: int,
    block_v: int,
    interpret: bool = False,
    return_residuals: bool = False,
):
    """Online-lse cross entropy; ``return_residuals=True`` additionally
    yields the per-row logsumexp ([rows] fp32) the backward kernel consumes
    instead of re-streaming the logits (the dispatch residual contract).
    """
    rows, vocab = logits.shape
    block_rows = min(block_rows, rows)
    block_v = min(block_v, vocab)
    pad_r = (-rows) % block_rows
    pad_v = (-vocab) % block_v
    if pad_r or pad_v:
        # Pad logits with -inf-ish so padded columns don't perturb logsumexp;
        # padded rows get label 0 and are sliced away.
        logits = jnp.pad(logits, ((0, pad_r), (0, pad_v)), constant_values=_NEG_INF)
        labels = jnp.pad(labels, (0, pad_r))
    rp, vp = logits.shape
    v_steps = vp // block_v
    grid = (rp // block_rows, v_steps)

    loss, lse = pl.pallas_call(
        functools.partial(_xent_kernel, block_v=block_v, v_steps=v_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_v), lambda ri, vi: (ri, vi)),
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(logits, labels.astype(jnp.int32)[:, None])
    if return_residuals:
        return loss[:rows, 0], lse[:rows, 0]
    return loss[:rows, 0]


def _xent_vmem_bytes(cfg, dtype_bytes: int = 4) -> int:
    """Scoped VMEM of the larger of the two passes (the backward): the
    double-buffered logits and d_logits tiles, two f32 tile temporaries, and
    the [rows, 1] columns (labels, ct, lse, loss, scratch), each padded to
    a full 128-lane f32 row. ``dtype_bytes=4`` bounds f32 logits too."""
    br, bv = cfg["block_rows"], cfg["block_v"]
    return br * bv * (4 * dtype_bytes + 8) + 9 * br * 128 * 4


XENT_SPACE = ParamSpace(
    [
        PowerOfTwoParam("block_rows", 8, 1024),
        PowerOfTwoParam("block_v", 512, 32768),
    ],
    [
        Constraint(
            lambda c: _xent_vmem_bytes(c) <= SCOPED_VMEM_BYTES,
            "xent tile exceeds VMEM budget",
        )
    ],
)


def _xent_heuristic(logits, labels):
    """8192-wide vocab blocks, then the most rows (up to 256) the VMEM
    budget admits: 64 at 8192."""
    rows, vocab = logits.shape
    cfg = {"block_v": 512 if vocab < 512 else 8192,
           "block_rows": min(256, max(8, 1 << (int(rows) - 1).bit_length()))}
    while cfg["block_rows"] > 8 and not XENT_SPACE.is_valid(cfg):
        cfg["block_rows"] //= 2
    return cfg


def _xent_example():
    import numpy as np

    rs = np.random.RandomState(0)
    return (
        jnp.asarray(rs.randn(16, 640) * 2, jnp.float32),
        jnp.asarray(rs.randint(0, 640, 16), jnp.int32),
    ), {}


def _xent_bwd_plan(ct, logits, labels, loss, lse, **kwargs):
    """Backward plan: d_logits is one fused bwd dispatch site; labels carry
    no gradient (None → float0 cotangent).

    Residual contract: the forward's per-row logsumexp rides in as ``lse``,
    so the bwd kernel skips the online-lse re-streaming pass entirely.
    """
    from ..core.runtime import dispatch

    del loss  # d_logits needs the lse residual, not the loss values
    return dispatch("softmax_xent_bwd", ct, logits, labels, lse, **kwargs), None


@tunable(
    "softmax_xent",
    space=XENT_SPACE,
    reference=ref.softmax_xent_res,
    heuristic=_xent_heuristic,
    # logits AND labels lead with the token-row dim (both batch-sharded).
    dispatch=DispatchSpec(reference=ref.softmax_xent,
                          example=_xent_example, data_parallel_args=(0, 1),
                          vjp="dispatch", bwd=_xent_bwd_plan, residuals=1),
)
def softmax_xent(logits, labels, *, block_rows: int, block_v: int, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return softmax_xent_pallas(
        logits, labels, block_rows=block_rows, block_v=block_v,
        interpret=interpret, return_residuals=True,
    )


# ---------------------------------------------------------------------------
# Backward: d_logits = (softmax − onehot(label)) · ct, vocab-streamed.
# The forward's residual contract threads its per-row logsumexp in, so the
# old online-lse re-streaming pass is gone: ONE pallas_call, one read of the
# logits + one write of d_logits.
# ---------------------------------------------------------------------------


def _xent_bwd_kernel(logits_ref, labels_ref, ct_ref, lse_ref, dl_ref, *, block_v: int):
    vi = pl.program_id(1)
    x = logits_ref[...].astype(jnp.float32)        # [block_rows, block_v]
    p = jnp.exp(x - lse_ref[...])                  # softmax given the lse
    cols = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    hit = (cols == labels_ref[...]).astype(jnp.float32)
    dl_ref[...] = ((p - hit) * ct_ref[...]).astype(dl_ref.dtype)


def softmax_xent_bwd_pallas(
    ct: jax.Array,      # [rows] — per-row loss cotangent (fp32)
    logits: jax.Array,  # [rows, vocab]
    labels: jax.Array,  # [rows] int32
    lse: jax.Array,     # [rows] fp32 — the forward's saved logsumexp
    *,
    block_rows: int,
    block_v: int,
    interpret: bool = False,
) -> jax.Array:
    """One streamed pass over the logits given the residual-threaded lse —
    HBM traffic is one read + one write, never a [rows, vocab] fp32 softmax
    materialization and (post residual contract) never a second lse pass."""
    rows, vocab = logits.shape
    block_rows = min(block_rows, rows)
    block_v = min(block_v, vocab)
    pad_r = (-rows) % block_rows
    pad_v = (-vocab) % block_v
    if pad_r or pad_v:
        logits = jnp.pad(logits, ((0, pad_r), (0, pad_v)), constant_values=_NEG_INF)
        labels = jnp.pad(labels, (0, pad_r))
        ct = jnp.pad(ct, (0, pad_r))
        # Padded rows: lse = 0 with all-(-1e30) logits → p ≈ 0, ct = 0.
        lse = jnp.pad(lse, (0, pad_r))
    rp, vp = logits.shape
    v_steps = vp // block_v
    grid = (rp // block_rows, v_steps)
    labels2 = labels.astype(jnp.int32)[:, None]
    ct2 = ct.astype(jnp.float32)[:, None]
    lse2 = lse.astype(jnp.float32)[:, None]

    dl = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_v), lambda ri, vi: (ri, vi)),
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((block_rows, 1), lambda ri, vi: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_v), lambda ri, vi: (ri, vi)),
        out_shape=jax.ShapeDtypeStruct((rp, vp), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(logits, labels2, ct2, lse2)
    return dl[:rows, :vocab]


def _xent_bwd_heuristic(ct, logits, labels, lse):
    return _xent_heuristic(logits, labels)


def _xent_bwd_example():
    import numpy as np

    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(16, 640) * 2, jnp.float32)
    # The lse residual must be consistent with the logits — the oracle
    # recomputes it while the kernel trusts the handed-in rows.
    lse = jax.nn.logsumexp(logits, axis=-1)
    return (
        jnp.asarray(rs.randn(16), jnp.float32),                 # ct
        logits,                                                 # logits
        jnp.asarray(rs.randint(0, 640, 16), jnp.int32),         # labels
        lse,                                                    # lse residual
    ), {}


@tunable(
    "softmax_xent_bwd",
    space=XENT_SPACE,
    reference=ref.softmax_xent_bwd,
    heuristic=_xent_bwd_heuristic,
    # ct, logits, labels, lse all lead with the token-row dim.
    # vjp="reference" (not "none"): the oracle is differentiable jnp, so
    # grad-of-grad can differentiate through this gradient site.
    dispatch=DispatchSpec(example=_xent_bwd_example,
                          data_parallel_args=(0, 1, 2, 3), vjp="reference"),
)
def softmax_xent_bwd(ct, logits, labels, lse, *, block_rows: int, block_v: int,
                     interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return softmax_xent_bwd_pallas(
        ct, logits, labels, lse, block_rows=block_rows, block_v=block_v,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Abstract grid models (static legality; see core/gridmodel.py). The
# backward realizes ONE pallas_call now that the forward's residual contract
# threads the lse in (the old online-lse re-streaming pass is gone); the
# forward carries the (m, l) scratch on its v axis ("arbitrary") and emits
# the lse residual alongside the loss. Both tune over the shared XENT_SPACE.
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _xent_blocks(config, rows, vocab):
    br = min(config["block_rows"], rows)
    bv = min(config["block_v"], vocab)
    return br, bv, rows + (-rows) % br, vocab + (-vocab) % bv


def _xent_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((2048, 65536), (2048,))
    rows, vocab = shapes[0]
    br, bv, rp, vp = _xent_blocks(config, rows, vocab)
    grid = (rp // br, vp // bv)
    tile = lambda ri, vi: (ri, vi)
    row = lambda ri, vi: (ri, 0)
    return GridModel(
        "softmax_xent", grid, ("parallel", "arbitrary"),
        (
            RefModel("logits", (br, bv), tile, (rp, vp)),
            RefModel("labels", (br, 1), row, (rp, 1), dtype="int32"),
            RefModel("loss", (br, 1), row, (rp, 1), role="out"),
            RefModel("lse", (br, 1), row, (rp, 1), role="out"),
        ),
    )


def _xent_bwd_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((2048,), (2048, 65536), (2048,), (2048,))
    rows, vocab = shapes[1]
    br, bv, rp, vp = _xent_blocks(config, rows, vocab)
    grid = (rp // br, vp // bv)
    tile = lambda ri, vi: (ri, vi)
    row = lambda ri, vi: (ri, 0)
    return GridModel(
        "softmax_xent_bwd", grid, ("parallel", "parallel"),
        (
            RefModel("logits", (br, bv), tile, (rp, vp)),
            RefModel("labels", (br, 1), row, (rp, 1), dtype="int32"),
            RefModel("ct", (br, 1), row, (rp, 1)),
            RefModel("lse", (br, 1), row, (rp, 1)),
            RefModel("dl", (br, bv), tile, (rp, vp), role="out"),
        ),
    )


register_grid_model("softmax_xent", _xent_grid_model, space=XENT_SPACE)
register_grid_model("softmax_xent_bwd", _xent_bwd_grid_model,
                    space=XENT_SPACE)
