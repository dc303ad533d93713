"""Fused-epilogue tunables: gemm+bias+activation and rmsnorm+gemm.

The paper's loop-fusion pragma, applied to the two hottest producer→consumer
pairs in the model plane:

* ``matmul_bias_act`` — a blocked MXU gemm whose last k step adds the bias
  row and applies the activation in VMEM, so the [m, n] pre-activation
  never round-trips through HBM (dense-with-bias projections; the ffn
  up/gate matmuls with their gelu/silu epilogues).
* ``rmsnorm_matmul`` — normalizes each row block in VMEM and feeds it
  straight into the projection gemm, skipping the HBM-materialized
  normalized activation (final-norm → unembed).

Whether fusion *wins* is an empirical, platform-dependent question — the
epilogue lengthens the sequential k chain and the norm fusion re-normalizes
per n block — so model sites route through these kernels only where the
tuning database says so (``runtime.fusion_wins``): an exact tuned record is
the opt-in, everything else keeps the unfused dispatch path.

Backward plans decompose onto *other* kernels' dispatch sites (plain
``matmul`` / ``rmsnorm`` / ``rmsnorm_bwd`` records serve the gradients),
declared via ``DispatchSpec.bwd_via`` so the contracts pass can verify the
decomposition instead of expecting a ``*_bwd`` sibling.
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
from .matmul import _pad_to


def _apply_act(h: jax.Array, act: str) -> jax.Array:
    if act == "gelu":
        return jax.nn.gelu(h)
    if act == "silu":
        return jax.nn.silu(h)
    if act == "none":
        return h
    raise ValueError(f"unknown fused activation {act!r}")


# ---------------------------------------------------------------------------
# matmul_bias_act: blocked gemm with a bias+activation epilogue
# ---------------------------------------------------------------------------


def _mba_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int, act: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        h = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = _apply_act(h, act).astype(o_ref.dtype)


def matmul_bias_act_pallas(
    x: jax.Array,  # [m, k]
    w: jax.Array,  # [k, n]
    b: jax.Array,  # [n]
    *,
    bm: int,
    bn: int,
    bk: int,
    act: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """act(x @ w + b) with explicit (bm, bn, bk) VMEM tiles; the epilogue
    runs on the fp32 accumulator at the last k step. Padding follows
    matmul_pallas (zero rows/cols are sliced back off before the caller
    sees them, so the epilogue on padded lanes is harmless)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and b.shape == (n,), (x.shape, w.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp, wp = _pad_to(x, (bm, bk)), _pad_to(w, (bk, bn))
    bp = _pad_to(b.reshape(1, n), (1, bn))
    mp, kp = xp.shape
    np_ = wp.shape[1]
    k_steps = kp // bk
    grid = (mp // bm, np_ // bn, k_steps)

    out = pl.pallas_call(
        functools.partial(_mba_kernel, k_steps=k_steps, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp, bp)
    return out[:m, :n]


def _mba_vmem_bytes(cfg, dtype_bytes: int = 4) -> int:
    """Double-buffered x, w, bias-row and out tiles plus the f32
    accumulator, at f32 operands (as ``matmul``'s model)."""
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    return (
        2 * (bm * bk + bk * bn + 8 * bn + bm * bn) * dtype_bytes
        + bm * bn * 4
    )


FUSED_MATMUL_SPACE = ParamSpace(
    [
        PowerOfTwoParam("bm", 8, 1024),
        PowerOfTwoParam("bn", 128, 1024),
        PowerOfTwoParam("bk", 128, 2048),
    ],
    [
        Constraint(
            lambda c: _mba_vmem_bytes(c) <= SCOPED_VMEM_BYTES,
            "fused gemm tile working set exceeds VMEM budget",
        )
    ],
)


def _mba_heuristic(x, w, b):
    from .matmul import _matmul_heuristic

    return _matmul_heuristic(x, w)


def _mba_example():
    import numpy as np

    rs = np.random.RandomState(3)
    return (
        jnp.asarray(rs.randn(32, 64), jnp.float32),
        jnp.asarray(rs.randn(64, 16), jnp.float32),
        jnp.asarray(rs.randn(16) * 0.1, jnp.float32),
    ), {"act": "gelu"}


def _mba_canon(x, w, b):
    """Flatten leading (batch/seq) dims to rows, like matmul's canon."""
    if x.ndim == 2:
        return (x, w, b), lambda out: out
    lead = x.shape[:-1]
    xr = x.reshape(-1, x.shape[-1])
    return (xr, w, b), lambda out: out.reshape(*lead, out.shape[-1])


def _mba_bwd(ct, x, w, b, act: str = "none", **kwargs):
    """Backward plan: decompose onto plain matmul dispatch sites (bwd_via).

    The epilogue cotangent g = act'(h)·ct needs the pre-activation h, which
    the fused forward deliberately never materialized — recompute it as one
    matmul dispatch (itself a tuned site), then dx/dw are the transposed-
    operand gemms and db the row reduction of g.
    """
    from ..core.runtime import dispatch

    if act == "none":
        g = ct
    else:
        h = dispatch("matmul", x, w) + b
        _, evjp = jax.vjp(lambda hh: _apply_act(hh.astype(jnp.float32), act), h)
        g = evjp(ct.astype(jnp.float32))[0].astype(ct.dtype)
    dx = dispatch("matmul", g, w.T, **kwargs)
    dw = dispatch("matmul", x.T, g, dp_dims={0: 1, 1: 0}, **kwargs)
    db = g.sum(axis=0).astype(b.dtype)
    return dx, dw, db


@tunable(
    "matmul_bias_act",
    space=FUSED_MATMUL_SPACE,
    reference=ref.matmul_bias_act,
    heuristic=_mba_heuristic,
    dispatch=DispatchSpec(
        # Same shapes, different epilogue => distinct db records.
        key_extra=lambda kw: f"a{kw.get('act', 'none')}",
        canonicalize=_mba_canon,
        example=_mba_example,
        vjp="dispatch",
        bwd=_mba_bwd,
        bwd_via=("matmul",),
    ),
)
def matmul_bias_act(
    x, w, b, *, bm: int, bn: int, bk: int,
    act: str = "none", interpret: Optional[bool] = None,
):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return matmul_bias_act_pallas(
        x, w, b, bm=bm, bn=bn, bk=bk, act=act, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# rmsnorm_matmul: per-row-block normalize in VMEM, feed the gemm directly
# ---------------------------------------------------------------------------


def _rmm_kernel(x_ref, s_ref, w_ref, o_ref, *, eps: float, d: int):
    # Mirrors ref.rmsnorm_matmul's cast placement exactly: normalize in
    # fp32, cast back to the activation dtype, scale, then fp32-accumulate.
    xf = x_ref[...].astype(jnp.float32)               # [bm, d]
    var = jnp.sum(xf * xf, axis=-1, keepdims=True) / d
    xn = (xf * jax.lax.rsqrt(var + eps)).astype(x_ref.dtype) * s_ref[...]
    o_ref[...] = jnp.dot(
        xn, w_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def rmsnorm_matmul_pallas(
    x: jax.Array,      # [m, d]
    scale: jax.Array,  # [d]
    w: jax.Array,      # [d, n]
    *,
    bm: int,
    bn: int,
    eps: float = 1e-6,
    interpret: bool = False,
) -> jax.Array:
    """rmsnorm(x, scale) @ w with the full d axis resident per tile: each
    (bm, d) row block is normalized once per n block and multiplied into a
    (d, bn) weight tile. The norm is recomputed per n block — the tuner
    decides whether that trade beats the unfused HBM round-trip. Row
    padding is sliced back off; the mean uses the *true* d (padded rows are
    all-zero, so their garbage outputs are dropped by the slice)."""
    m, d = x.shape
    d2, n = w.shape
    assert d == d2 and scale.shape == (d,), (x.shape, scale.shape, w.shape)
    bm, bn = min(bm, m), min(bn, n)
    xp = _pad_to(x, (bm, 1))
    wp = _pad_to(w, (1, bn))
    mp, np_ = xp.shape[0], wp.shape[1]
    grid = (mp // bm, np_ // bn)

    out = pl.pallas_call(
        functools.partial(_rmm_kernel, eps=eps, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((d, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(xp, scale.reshape(1, d), wp)
    return out[:m, :n]


def _rmm_vmem_bytes(cfg, d: int = 4096, dtype_bytes: int = 2) -> int:
    """Double-buffered x, scale-row, w and out tiles, plus the f32
    normalized copy of x and the f32 product."""
    bm, bn = cfg["bm"], cfg["bn"]
    return (
        2 * (bm * d + 8 * d + d * bn + bm * bn) * dtype_bytes
        + bm * d * 4 + bm * bn * 4
    )


RMSNORM_MATMUL_SPACE = ParamSpace(
    [
        PowerOfTwoParam("bm", 8, 512),
        PowerOfTwoParam("bn", 128, 1024),
    ],
    [
        Constraint(
            lambda c: _rmm_vmem_bytes(c) <= SCOPED_VMEM_BYTES,
            "fused norm+gemm tile working set exceeds VMEM budget",
        )
    ],
)


def _rmm_heuristic(x, scale, w):
    m = 1
    for s in x.shape[:-1]:
        m *= int(s)
    n = w.shape[1]
    pick = lambda dim, cap: min(cap, max(8, 1 << (int(dim) - 1).bit_length()))
    return {"bm": min(pick(m, 128), 512), "bn": max(128, min(pick(n, 512), 1024))}


def _rmm_example():
    import numpy as np

    rs = np.random.RandomState(4)
    return (
        jnp.asarray(rs.randn(32, 64) * 0.5, jnp.float32),
        jnp.asarray(1.0 + rs.randn(64) * 0.1, jnp.float32),
        jnp.asarray(rs.randn(64, 16), jnp.float32),
    ), {}


def _rmm_canon(x, scale, w):
    """Flatten leading (batch/seq) dims to rows: [..., d] -> [rows, d]."""
    if x.ndim == 2:
        return (x, scale, w), lambda out: out
    lead = x.shape[:-1]
    xr = x.reshape(-1, x.shape[-1])
    return (xr, scale, w), lambda out: out.reshape(*lead, out.shape[-1])


def _rmm_bwd(ct, x, scale, w, eps: float = 1e-6, **kwargs):
    """Backward plan: decompose onto rmsnorm / matmul / rmsnorm_bwd sites.

    xn = rmsnorm(x, scale) is recomputed through its own dispatch site; the
    projection gradients are transposed-operand matmuls; the norm gradients
    route through the residual-threaded rmsnorm_bwd with inv-rms rebuilt
    from x (one cheap row reduction, not a kernel).
    """
    from ..core.runtime import dispatch

    xn = dispatch("rmsnorm", x, scale, eps=eps)
    d_xn = dispatch("matmul", ct, w.T, **kwargs)
    dw = dispatch("matmul", xn.T, ct, dp_dims={0: 1, 1: 0}, **kwargs)
    xf = x.astype(jnp.float32)
    invrms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)
    dx, dscale = dispatch("rmsnorm_bwd", d_xn, x, scale, invrms, **kwargs)
    return dx, dscale, dw


@tunable(
    "rmsnorm_matmul",
    space=RMSNORM_MATMUL_SPACE,
    reference=ref.rmsnorm_matmul,
    heuristic=_rmm_heuristic,
    dispatch=DispatchSpec(
        canonicalize=_rmm_canon,
        example=_rmm_example,
        vjp="dispatch",
        bwd=_rmm_bwd,
        bwd_via=("rmsnorm", "matmul", "rmsnorm_bwd"),
    ),
)
def rmsnorm_matmul(
    x, scale, w, *, bm: int, bn: int,
    eps: float = 1e-6, interpret: Optional[bool] = None,
):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return rmsnorm_matmul_pallas(
        x, scale, w, bm=bm, bn=bn, eps=eps, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Abstract grid models (static legality; see core/gridmodel.py)
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _mba_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((4096, 4096), (4096, 4096), (4096,))
    (m, k), n = shapes[0], shapes[1][1]
    bm = min(config["bm"], m)
    bn = min(config["bn"], n)
    bk = min(config["bk"], k)
    mp, kp, np_ = m + (-m) % bm, k + (-k) % bk, n + (-n) % bn
    grid = (mp // bm, np_ // bn, kp // bk)
    return GridModel(
        "matmul_bias_act", grid, ("parallel", "parallel", "arbitrary"),
        (
            RefModel("x", (bm, bk), lambda i, j, kk: (i, kk), (mp, kp)),
            RefModel("w", (bk, bn), lambda i, j, kk: (kk, j), (kp, np_)),
            RefModel("b", (1, bn), lambda i, j, kk: (0, j), (1, np_)),
            RefModel("out", (bm, bn), lambda i, j, kk: (i, j), (mp, np_),
                     role="out"),
        ),
    )


def _rmm_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((8192, 4096), (4096,), (4096, 4096))
    (m, d), n = shapes[0], shapes[2][1]
    bm = min(config["bm"], m)
    bn = min(config["bn"], n)
    mp, np_ = m + (-m) % bm, n + (-n) % bn
    grid = (mp // bm, np_ // bn)
    return GridModel(
        "rmsnorm_matmul", grid, ("parallel", "parallel"),
        (
            RefModel("x", (bm, d), lambda i, j: (i, 0), (mp, d)),
            RefModel("scale", (1, d), lambda i, j: (0, 0), (1, d)),
            RefModel("w", (d, bn), lambda i, j: (0, j), (d, np_)),
            RefModel("out", (bm, bn), lambda i, j: (i, j), (mp, np_),
                     role="out"),
        ),
    )


register_grid_model("matmul_bias_act", _mba_grid_model,
                    space=FUSED_MATMUL_SPACE)
register_grid_model("rmsnorm_matmul", _rmm_grid_model,
                    space=RMSNORM_MATMUL_SPACE)
