"""Blocked MXU matmul — Pallas kernel with tunable VMEM tiling.

The paper's SIMD-pragma knob becomes the BlockSpec tile (bm, bn, bk): it
fixes the VMEM working set ``bm·bk + bk·bn + bm·bn(out) + bm·bn·4(acc)``
bytes and the MXU utilization (tiles should be multiples of 128 on the
contracting/lane dims). The k grid dim carries the fp32 accumulator and is
sequential ('arbitrary'); m/n are parallel.
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


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jax.Array, mults) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool = False,
) -> jax.Array:
    """[m, k] @ [k, n] -> [m, n] with explicit (bm, bn, bk) VMEM tiles.

    Non-divisible shapes are zero-padded up to tile multiples and the result
    sliced back (zero rows/cols contribute zero partial products, so padding
    is semantics-preserving for matmul).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp, wp = _pad_to(x, (bm, bk)), _pad_to(w, (bk, bn))
    mp, kp = xp.shape
    np_ = wp.shape[1]
    k_steps = kp // bk
    grid = (mp // bm, np_ // bn, k_steps)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n]


def _vmem_bytes(cfg, dtype_bytes: int = 4) -> int:
    """Scoped VMEM of one variant: the pipeline double-buffers the x, w and
    out tiles, plus the f32 accumulator. ``dtype_bytes=4`` bounds f32
    operands too, so an admitted tile compiles for every operand dtype."""
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    return 2 * (bm * bk + bk * bn + bm * bn) * dtype_bytes + bm * bn * 4


MATMUL_SPACE = ParamSpace(
    [
        PowerOfTwoParam("bm", 8, 1024),
        PowerOfTwoParam("bn", 128, 1024),
        PowerOfTwoParam("bk", 128, 2048),
    ],
    [
        Constraint(
            lambda c: _vmem_bytes(c) <= SCOPED_VMEM_BYTES,
            "tile working set exceeds VMEM budget",
        )
    ],
)


def _matmul_heuristic(x, w):
    """Shape-aware default ≈ what a hand-written library baseline would pick
    (always inside the VMEM budget: 256x256x512 needs 2.75 MiB at f32)."""
    m, k = x.shape
    n = w.shape[1]
    pick = lambda d, cap: min(cap, max(8, 1 << (int(d) - 1).bit_length()))
    return {
        "bm": pick(m, 256),
        "bn": max(128, pick(n, 256)),
        "bk": max(128, pick(k, 512)),
    }


def _matmul_example():
    import numpy as np

    rs = np.random.RandomState(0)
    return (
        jnp.asarray(rs.randn(32, 64), jnp.float32),
        jnp.asarray(rs.randn(64, 16), jnp.float32),
    ), {}


def _matmul_canon(x, w):
    """Flatten leading (batch/seq) dims to rows: [..., k] @ [k, n].

    Model call sites pass activations of any rank; the kernel and its
    database keys see the canonical [rows, k] layout (rows is the
    data-parallel dim, so sharded traces key on local rows).
    """
    if x.ndim == 2:
        return (x, w), lambda out: out
    lead = x.shape[:-1]
    xr = x.reshape(-1, x.shape[-1])
    return (xr, w), lambda out: out.reshape(*lead, out.shape[-1])


def _matmul_bwd(ct, x, w, **kwargs):
    """Backward plan: both gradients are matmul dispatch sites themselves.

    dL/dx = ct [m,n] @ wᵀ [n,k] and dL/dw = xᵀ [k,m] @ ct [m,n] — the
    auto-derived transposed-operand calls through the same registry, so a
    campaign record for the transposed bucket serves the gradient with zero
    extra machinery. dL/dw contracts over the token rows: under a sharded
    mesh its sharded dim is xᵀ's dim 1 / ct's dim 0, declared via
    ``dp_dims`` so the database key localizes the dims training actually
    shards (the planner emits the matching local-shape jobs).
    """
    from ..core.runtime import dispatch

    dx = dispatch("matmul", ct, w.T, **kwargs)
    dw = dispatch("matmul", x.T, ct, dp_dims={0: 1, 1: 0}, **kwargs)
    return dx, dw


@tunable(
    "matmul",
    space=MATMUL_SPACE,
    reference=ref.matmul,
    heuristic=_matmul_heuristic,
    dispatch=DispatchSpec(canonicalize=_matmul_canon, example=_matmul_example,
                          vjp="dispatch", bwd=_matmul_bwd),
)
def matmul(x, w, *, bm: int, bn: int, bk: int, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return matmul_pallas(x, w, bm=bm, bn=bn, bk=bk, interpret=interpret)


# ---------------------------------------------------------------------------
# Abstract grid model — the statically-checkable mirror of matmul_pallas's
# clamp/pad/grid arithmetic (see core/gridmodel.py). Nominal shapes are a
# production-scale gemm so the legality verdict reflects real tiled axes.
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _matmul_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((4096, 4096), (4096, 4096))
    (m, k), n = shapes[0], shapes[1][1]
    bm = min(config["bm"], m)
    bn = min(config["bn"], n)
    bk = min(config["bk"], k)
    mp, kp, np_ = m + (-m) % bm, k + (-k) % bk, n + (-n) % bn
    grid = (mp // bm, np_ // bn, kp // bk)
    return GridModel(
        "matmul", grid, ("parallel", "parallel", "arbitrary"),
        (
            RefModel("x", (bm, bk), lambda i, j, kk: (i, kk), (mp, kp)),
            RefModel("w", (bk, bn), lambda i, j, kk: (kk, j), (kp, np_)),
            RefModel("out", (bm, bn), lambda i, j, kk: (i, j), (mp, np_),
                     role="out"),
        ),
    )


register_grid_model("matmul", _matmul_grid_model, space=MATMUL_SPACE)
