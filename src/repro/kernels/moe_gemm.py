"""Grouped expert GEMM — the MoE dispatch site, keyed on (experts ×
capacity × hidden).

One [e, c, k] @ [e, k, n] batched contraction per expert-FFN matmul: the
expert dim is an outer grid axis (each expert's tile stream is independent),
c/n tile through VMEM like the dense matmul, and the k grid dim carries the
fp32 accumulator. Replaces the three ``ecd,edf`` einsums in
``moe._expert_ffn`` so Mixtral-style configs resolve through the tuned
runtime instead of XLA defaults.

The backward plan reuses this same tunable with transposed operands
(dL/dx = ct @ wᵀ, dL/dw = xᵀ @ ct per expert), so campaign records for the
transposed buckets serve the gradients — the matmul pattern, grouped.

Capacity derives from the *global* token count (``b·s`` of the traced,
unsharded shape) and the expert dim is a weight axis, so no argument is
batch-sharded: ``data_parallel_args=()``.
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


def _expert_gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def expert_gemm_pallas(
    x: jax.Array,   # [e, c, k]
    w: jax.Array,   # [e, k, n]
    *,
    bc: int,
    bn: int,
    bk: int,
    interpret: bool = False,
) -> jax.Array:
    e, c, k = x.shape
    e2, k2, n = w.shape
    assert e == e2 and k == k2, (x.shape, w.shape)
    bc, bn, bk = min(bc, c), min(bn, n), min(bk, k)
    pad3 = lambda t, mc, mk: jnp.pad(
        t, ((0, 0), (0, (-t.shape[1]) % mc), (0, (-t.shape[2]) % mk))
    )
    xp, wp = pad3(x, bc, bk), pad3(w, bk, bn)
    cp, kp = xp.shape[1], xp.shape[2]
    np_ = wp.shape[2]
    k_steps = kp // bk
    grid = (e, cp // bc, np_ // bn, k_steps)

    out = pl.pallas_call(
        functools.partial(_expert_gemm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda ie, i, j, kk: (ie, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda ie, i, j, kk: (ie, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bn), lambda ie, i, j, kk: (ie, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp)
    return out[:, :c, :n]


def _vmem_bytes(cfg, dtype_bytes: int = 4) -> int:
    """Scoped VMEM of one variant, as for ``matmul``: double-buffered x, w
    and out tiles plus the f32 accumulator, at f32 operands."""
    bc, bn, bk = cfg["bc"], cfg["bn"], cfg["bk"]
    return 2 * (bc * bk + bk * bn + bc * bn) * dtype_bytes + bc * bn * 4


EXPERT_GEMM_SPACE = ParamSpace(
    [
        PowerOfTwoParam("bc", 8, 1024),
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


def _expert_gemm_heuristic(x, w):
    e, c, k = x.shape
    n = w.shape[2]
    pick = lambda d, cap: min(cap, max(8, 1 << (int(d) - 1).bit_length()))
    return {
        "bc": min(pick(c, 256), 1024),
        "bn": max(128, min(pick(n, 256), 1024)),
        "bk": max(128, min(pick(k, 512), 2048)),
    }


def _expert_gemm_example():
    import numpy as np

    rs = np.random.RandomState(0)
    return (
        jnp.asarray(rs.randn(2, 12, 16), jnp.float32),
        jnp.asarray(rs.randn(2, 16, 8), jnp.float32),
    ), {}


def _expert_gemm_bwd(ct, x, w, **kwargs):
    """Backward plan: both grads are grouped-gemm dispatch sites themselves."""
    from ..core.runtime import dispatch

    dx = dispatch("expert_gemm", ct, jnp.swapaxes(w, 1, 2), **kwargs)
    dw = dispatch("expert_gemm", jnp.swapaxes(x, 1, 2), ct, **kwargs)
    return dx, dw


@tunable(
    "expert_gemm",
    space=EXPERT_GEMM_SPACE,
    reference=ref.expert_gemm,
    heuristic=_expert_gemm_heuristic,
    dispatch=DispatchSpec(example=_expert_gemm_example,
                          data_parallel_args=(),
                          vjp="dispatch", bwd=_expert_gemm_bwd),
)
def expert_gemm(x, w, *, bc: int, bn: int, bk: int,
                interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return expert_gemm_pallas(x, w, bc=bc, bn=bn, bk=bk, interpret=interpret)


# ---------------------------------------------------------------------------
# Abstract grid model (static legality; see core/gridmodel.py). Experts ride
# the outer grid axis; the k axis carries the accumulator scratch and is
# declared "arbitrary" — that is what makes the out ref (invariant along kk)
# race-free. Backward expert_gemm dispatches reuse this model with
# transposed operands, so one registration covers fwd and bwd keys.
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _expert_gemm_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((4, 4096, 4096), (4, 4096, 2048))
    e, c, k = shapes[0]
    n = shapes[1][2]
    bc = min(config["bc"], c)
    bn = min(config["bn"], n)
    bk = min(config["bk"], k)
    cp, kp, np_ = c + (-c) % bc, k + (-k) % bk, n + (-n) % bn
    grid = (e, cp // bc, np_ // bn, kp // bk)
    xmap = lambda ie, i, j, kk: (ie, i, kk)
    wmap = lambda ie, i, j, kk: (ie, kk, j)
    omap = lambda ie, i, j, kk: (ie, i, j)
    return GridModel(
        "expert_gemm", grid,
        ("parallel", "parallel", "parallel", "arbitrary"),
        (
            RefModel("x", (1, bc, bk), xmap, (e, cp, kp)),
            RefModel("w", (1, bk, bn), wmap, (e, kp, np_)),
            RefModel("out", (1, bc, bn), omap, (e, cp, np_), role="out"),
        ),
    )


register_grid_model("expert_gemm", _expert_gemm_grid_model,
                    space=EXPERT_GEMM_SPACE)
