"""Fused RMSNorm — Pallas kernel with a tunable row-block.

Memory-bound: one read + one write of x. The knob is how many rows ride
through VMEM per grid step (block_rows); too small wastes grid overhead, too
large overflows VMEM for wide d_model. Fusing the reduction with the scale
multiply avoids the extra HBM round-trip XLA sometimes emits for the
mean-of-squares intermediate.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import DispatchSpec, ParamSpace, PowerOfTwoParam, tunable
from ..core.platform import SCOPED_VMEM_BYTES
from . import ref


def _rmsnorm_kernel(x_ref, w_ref, o_ref, r_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    o_ref[...] = ((x * r) * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    r_ref[...] = r


def rmsnorm_pallas(
    x: jax.Array,       # [rows, d]
    weight: jax.Array,  # [d]
    *,
    block_rows: int,
    eps: float = 1e-6,
    interpret: bool = False,
    return_residuals: bool = False,
):
    """Fused rmsnorm; ``return_residuals=True`` additionally yields the
    per-row inverse rms ([rows] fp32) — the residual the backward kernel
    consumes instead of re-deriving it (see the dispatch residual contract).
    """
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    grid = (xp.shape[0] // block_rows,)
    out, invrms = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xp, weight[None, :])
    if pad:
        out = out[:rows]
    if return_residuals:
        return out, invrms[:rows, 0]
    return out


# Whether a row block fits VMEM depends on d and the dtype, which a config
# does not name: the grid models below carry the budget, checked at the
# tuned shapes and dtype — the same budget the heuristic sizes by.
RMSNORM_SPACE = ParamSpace([PowerOfTwoParam("block_rows", 8, 4096)])


def _rmsnorm_vmem_bytes(block_rows: int, d: int, dtype_bytes: int = 4) -> int:
    """Scoped VMEM of the backward, the larger of the two passes: the
    double-buffered ct, x and dx row tiles, three f32 row temporaries, and
    the double-buffered inverse-rms column padded to 128 f32 lanes."""
    d_pad = -(-d // 128) * 128
    return block_rows * (d_pad * (6 * dtype_bytes + 3 * 4) + 2 * 128 * 4)


def _rmsnorm_heuristic(x, w):
    """The largest row block up to 1024 whose backward fits VMEM."""
    d, nbytes = x.shape[-1], jnp.dtype(x.dtype).itemsize
    p = 8
    while p < 1024 and (_rmsnorm_vmem_bytes(2 * p, d, nbytes)
                        <= SCOPED_VMEM_BYTES):
        p *= 2
    return {"block_rows": p}


def _rmsnorm_canon(x, weight):
    """Flatten [..., d] -> [rows, d] for the kernel; reshape the output back.

    Callers (the norm layer) hand over activations of any rank; the db key
    and the kernel both want the 2D row view. The reference path is rank-
    generic and never sees this.
    """
    shape = x.shape
    return (x.reshape(-1, shape[-1]), weight), lambda out: out.reshape(shape)


def _rmsnorm_example():
    import numpy as np

    rs = np.random.RandomState(0)
    # 3D on purpose: exercises the flatten/reshape canonicalization.
    return (
        jnp.asarray(rs.randn(2, 16, 32), jnp.float32),
        jnp.asarray(rs.randn(32), jnp.float32),
    ), {}


def _rmsnorm_bwd_plan(ct, x, weight, y, invrms, **kwargs):
    """Backward plan for the fwd tunable: one fused bwd dispatch site.

    Residual contract: called with the forward's canonical args, the primal
    output and the saved inverse-rms rows — the bwd kernel consumes invrms
    instead of re-deriving it (one fewer reduction over x).
    """
    from ..core.runtime import dispatch

    del y  # the rmsnorm gradient needs x and invrms, not the output
    return dispatch("rmsnorm_bwd", ct, x, weight, invrms, **kwargs)


@tunable(
    "rmsnorm",
    space=RMSNORM_SPACE,
    reference=ref.rmsnorm_res,
    heuristic=_rmsnorm_heuristic,
    dispatch=DispatchSpec(reference=ref.rmsnorm,
                          canonicalize=_rmsnorm_canon, example=_rmsnorm_example,
                          vjp="dispatch", bwd=_rmsnorm_bwd_plan, residuals=1),
)
def rmsnorm(x, weight, *, block_rows: int, eps: float = 1e-6, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return rmsnorm_pallas(x, weight, block_rows=block_rows, eps=eps,
                          interpret=interpret, return_residuals=True)


# ---------------------------------------------------------------------------
# Fused backward: d_x and d_weight in one row-streamed pass
# ---------------------------------------------------------------------------


def _rmsnorm_bwd_kernel(ct_ref, x_ref, w_ref, r_ref, dx_ref, dw_ref, dw_scr,
                        *, d: int, r_steps: int):
    ri = pl.program_id(0)

    @pl.when(ri == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)

    x = x_ref[...].astype(jnp.float32)             # [block_rows, d]
    ct = ct_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)             # [1, d]
    r = r_ref[...]                                 # [block_rows, 1] fp32
    g = ct * w                                     # upstream × scale
    # dx_j = g_j·r − x_j·r³·mean_i(g_i·x_i); padded rows carry r = 0 (the
    # residual pad value), so their g·r and dw contribution vanish.
    dot = jnp.sum(g * x, axis=-1, keepdims=True)
    dx_ref[...] = (g * r - x * (r ** 3) * (dot / d)).astype(dx_ref.dtype)
    dw_scr[...] += jnp.sum(ct * (x * r), axis=0, keepdims=True)

    @pl.when(ri == r_steps - 1)
    def _done():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)


def rmsnorm_bwd_pallas(
    ct: jax.Array,      # [rows, d] — cotangent of the rmsnorm output
    x: jax.Array,       # [rows, d]
    weight: jax.Array,  # [d]
    invrms: jax.Array,  # [rows] fp32 — the forward's saved inverse rms
    *,
    block_rows: int,
    eps: float = 1e-6,
    interpret: bool = False,
):
    """Fused (d_x, d_weight) given the residual-threaded inverse rms.

    Pre-residual-contract, this kernel re-derived ``rsqrt(mean(x²)+eps)``
    per row; the forward now hands it over, so the bwd pass is pure
    elementwise+reduction work on (ct, x, invrms). ``eps`` is accepted for
    key/reference symmetry but unused — the residual already encodes it.
    """
    del eps
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        ct = jnp.pad(ct, ((0, pad), (0, 0)))
        x = jnp.pad(x, ((0, pad), (0, 0)))
        invrms = jnp.pad(invrms, (0, pad))
    r_steps = x.shape[0] // block_rows
    dx, dw = pl.pallas_call(
        functools.partial(_rmsnorm_bwd_kernel, d=d, r_steps=r_steps),
        grid=(r_steps,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((1, d), weight.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        # the row grid carries the d_weight accumulator: sequential
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(ct, x, weight[None, :], invrms.astype(jnp.float32)[:, None])
    return (dx[:rows] if pad else dx), dw[0]


def _rmsnorm_bwd_heuristic(ct, x, weight, invrms):
    return _rmsnorm_heuristic(x, weight)


def _rmsnorm_bwd_example():
    import numpy as np

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(16, 32), jnp.float32)
    # The invrms residual must be consistent with x — the oracle recomputes
    # it from x while the kernel trusts the handed-in rows.
    invrms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1) + 1e-6)
    return (
        jnp.asarray(rs.randn(16, 32), jnp.float32),   # ct
        x,                                            # x
        jnp.asarray(rs.randn(32), jnp.float32),       # weight
        invrms,                                       # invrms residual
    ), {}


@tunable(
    "rmsnorm_bwd",
    space=RMSNORM_SPACE,
    reference=ref.rmsnorm_bwd,
    heuristic=_rmsnorm_bwd_heuristic,
    # ct, x and invrms are token-row-sharded. vjp="reference" (not "none"):
    # the oracle is plain differentiable jnp, so grad-of-grad can
    # differentiate *through* this gradient site.
    # d_weight sums over the rows.
    dispatch=DispatchSpec(example=_rmsnorm_bwd_example,
                          data_parallel_args=(0, 1, 3), reduced_outputs=(1,),
                          vjp="reference"),
)
def rmsnorm_bwd(ct, x, weight, invrms, *, block_rows: int, eps: float = 1e-6,
                interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return rmsnorm_bwd_pallas(ct, x, weight, invrms, block_rows=block_rows,
                              eps=eps, interpret=interpret)


# ---------------------------------------------------------------------------
# Abstract grid models (static legality; see core/gridmodel.py). Both kernels
# tune over RMSNORM_SPACE, so a config is legal only if legal under both —
# the bwd model is also the race detector's shipped ground truth: dw maps
# every grid point to block (0, 0), which is only safe because the row axis
# is declared "arbitrary" (sequential).
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _rmsnorm_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((8192, 4096), (4096,))
    rows, d = shapes[0]
    br = min(config["block_rows"], rows)
    rp = rows + (-rows) % br
    row = lambda i: (i, 0)
    w0 = lambda i: (0, 0)
    return GridModel(
        "rmsnorm", (rp // br,), ("parallel",),
        (
            RefModel("x", (br, d), row, (rp, d)),
            RefModel("w", (1, d), w0, (1, d)),
            RefModel("out", (br, d), row, (rp, d), role="out"),
            RefModel("invrms", (br, 1), row, (rp, 1), role="out"),
        ),
        vmem_need=functools.partial(_rmsnorm_vmem_bytes, br, d),
    )


def _rmsnorm_bwd_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((8192, 4096), (8192, 4096), (4096,), (8192,))
    rows, d = shapes[1]
    br = min(config["block_rows"], rows)
    rp = rows + (-rows) % br
    row = lambda i: (i, 0)
    w0 = lambda i: (0, 0)
    return GridModel(
        "rmsnorm_bwd", (rp // br,), ("arbitrary",),
        (
            RefModel("ct", (br, d), row, (rp, d)),
            RefModel("x", (br, d), row, (rp, d)),
            RefModel("w", (1, d), w0, (1, d)),
            RefModel("invrms", (br, 1), row, (rp, 1)),
            RefModel("dx", (br, d), row, (rp, d), role="out"),
            RefModel("dw", (1, d), w0, (1, d), role="out"),
        ),
        vmem_need=functools.partial(_rmsnorm_vmem_bytes, br, d),
    )


register_grid_model("rmsnorm", _rmsnorm_grid_model, space=RMSNORM_SPACE)
register_grid_model("rmsnorm_bwd", _rmsnorm_bwd_grid_model, space=RMSNORM_SPACE)
