"""Selective-scan (Mamba S6) tunables: chunked scan + fused decode update.

The recurrence is h_t = exp(dt_t·A)·h_{t-1} + (dt_t·xc_t)·B_t, y_t = h_t·C_t
with an fp32 carry. Two dispatch sites:

  * ``ssm_scan`` — training/prefill over [b, s, di]. The Pallas kernel
    streams length-``chunk`` time slices through VMEM per (batch, d_inner
    block) grid cell, carrying the [block_d, d_state] state in scratch; the
    reference is the chunked associative-scan form (the math previously
    inlined in ``models/ssm.py``), whose peak live tensor is
    [b, chunk, di, ds] — never the full [b, s, di, ds].
  * ``ssm_update`` — one fused decode step over [b, di].

Padding is identity-safe by construction: a zero-padded tail has dt = 0, so
dA = exp(0) = 1 and dBx = 0 — pad steps carry the state through unchanged.
(The old inline chunking padded *pre-coefficient* activations instead, so
``softplus(dt_bias) > 0`` kept stepping the recurrence across the pad and
corrupted the prefill→decode handoff state.)

Backwards are dispatch sites too (``ssm_scan_bwd`` / ``ssm_update_bwd``,
``vjp="dispatch"``): jnp variants whose chunk/block knob bounds the VJP's
rematerialization window, gated against the sequential ``ref.py`` oracles.
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


# ---------------------------------------------------------------------------
# Chunked associative-scan form — the reference plane of the ssm_scan
# tunable AND the remat-windowed body of the bwd variants.
# ---------------------------------------------------------------------------


def ssm_scan_chunked(xc: jax.Array, dt: jax.Array, B: jax.Array, C: jax.Array,
                     A: jax.Array, h0: jax.Array, *, chunk: int = 32):
    """Outer `lax.scan` over chunks, inner `associative_scan` within.

    Same signature/semantics as :func:`ref.ssm_scan`; peak live tensor is
    [b, chunk, di, ds].
    """
    b, s, di = xc.shape
    ds = A.shape[1]
    chunk = max(1, min(chunk, s))
    pad = (-s) % chunk
    xf = xc.astype(jnp.float32)
    if pad:
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        xf, dt, B, C = zpad(xf), zpad(dt), zpad(B), zpad(C)
    sp = s + pad
    nc = sp // chunk
    resh = lambda t: t.reshape(b, nc, chunk, *t.shape[2:]).swapaxes(0, 1)
    xcs, dts, Bs, Cs = resh(xf), resh(dt), resh(B), resh(C)

    def chunk_step(h, inp):
        xc_c, dt_c, B_c, C_c = inp              # [b,c,di], [b,c,di], [b,c,ds]x2
        dA = jnp.exp(dt_c[..., None] * A)       # [b,c,di,ds]
        dBx = (dt_c * xc_c)[..., None] * B_c[:, :, None, :]
        # prepend the carry as a pseudo-step: h_0's contribution
        a_all = jnp.concatenate([jnp.ones_like(dA[:, :1]), dA], axis=1)
        b_all = jnp.concatenate([h[:, None], dBx], axis=1)

        def combine(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a2 * a1, a2 * b1 + b2

        _, hs = jax.lax.associative_scan(combine, (a_all, b_all), axis=1)
        hs = hs[:, 1:]                          # [b,c,di,ds]
        y = jnp.einsum("bcds,bcs->bcd", hs, C_c)
        return hs[:, -1], y

    hN, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32), (xcs, dts, Bs, Cs))
    y = ys.swapaxes(0, 1).reshape(b, sp, di)[:, :s]
    return y, hN


# ---------------------------------------------------------------------------
# Pallas chunked scan
# ---------------------------------------------------------------------------


def _ssm_scan_kernel(xc_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                     y_ref, hn_ref, h_scr, *, chunk: int, s_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    xc = xc_ref[0].astype(jnp.float32)          # [chunk, bd]
    dt = dt_ref[0]                              # [chunk, bd] fp32
    bb = b_ref[0]                               # [chunk, ds] fp32
    cc = c_ref[0]
    a = a_ref[...]                              # [bd, ds]
    da = jnp.exp(dt[:, :, None] * a[None])      # [chunk, bd, ds]
    dbx = (dt * xc)[:, :, None] * bb[:, None, :]

    def step(t, carry):
        h, ys = carry
        h = da[t] * h + dbx[t]
        y_t = jnp.sum(h * cc[t][None, :], axis=-1)          # [bd]
        ys = jax.lax.dynamic_update_index_in_dim(ys, y_t, t, 0)
        return h, ys

    h, ys = jax.lax.fori_loop(0, chunk, step, (h_scr[...], jnp.zeros_like(dt)))
    h_scr[...] = h
    y_ref[0] = ys

    @pl.when(pl.program_id(2) == s_steps - 1)
    def _done():
        hn_ref[0] = h


def ssm_scan_pallas(
    xc: jax.Array,   # [b, s, di] model dtype
    dt: jax.Array,   # [b, s, di] fp32, post-softplus (>= 0)
    B: jax.Array,    # [b, s, ds] fp32
    C: jax.Array,    # [b, s, ds] fp32
    A: jax.Array,    # [di, ds] fp32 (negative)
    h0: jax.Array,   # [b, di, ds] fp32 carry-in
    *,
    chunk: int,
    block_d: int,
    interpret: bool = False,
):
    b, s, di = xc.shape
    ds = A.shape[1]
    chunk = min(chunk, s)
    block_d = min(block_d, di)
    sp = s + (-s) % chunk
    dip = di + (-di) % block_d
    # zero padding is identity-safe: dt = 0 => dA = 1, dBx = 0
    pad_sd = lambda t: jnp.pad(t, ((0, 0), (0, sp - s), (0, dip - di)))
    pad_s = lambda t: jnp.pad(t, ((0, 0), (0, sp - s), (0, 0)))
    xcp, dtp = pad_sd(xc), pad_sd(dt)
    Bp, Cp = pad_s(B), pad_s(C)
    Ap = jnp.pad(A, ((0, dip - di), (0, 0)))
    h0p = jnp.pad(h0, ((0, 0), (0, dip - di), (0, 0)))
    s_steps = sp // chunk
    grid = (b, dip // block_d, s_steps)

    y, hn = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, chunk=chunk, s_steps=s_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, chunk, block_d), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, chunk, ds), lambda ib, id_, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda ib, id_, ic: (ib, ic, 0)),
            pl.BlockSpec((block_d, ds), lambda ib, id_, ic: (id_, 0)),
            pl.BlockSpec((1, block_d, ds), lambda ib, id_, ic: (ib, id_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, block_d, ds), lambda ib, id_, ic: (ib, id_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, dip), jnp.float32),
            jax.ShapeDtypeStruct((b, dip, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, ds), jnp.float32)],
        # the time grid dim carries the state scratch: sequential
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xcp, dtp, Bp, Cp, Ap, h0p)
    return y[:, :s, :di], hn[:, :di]


def _scan_vmem_bytes(cfg, ds: int = 16) -> int:
    c, bd = cfg["chunk"], cfg["block_d"]
    # da + dbx intermediates dominate; xc/dt/y tiles + state scratch ride along
    return c * bd * ds * 8 + c * bd * 12 + bd * ds * 8


SSM_SCAN_SPACE = ParamSpace(
    [
        PowerOfTwoParam("chunk", 8, 512),
        PowerOfTwoParam("block_d", 8, 512),
    ],
    [
        Constraint(
            lambda c: _scan_vmem_bytes(c) <= SCOPED_VMEM_BYTES,
            "chunk x d_inner working set exceeds VMEM budget",
        )
    ],
)


def _pick_pow2(d: int, lo: int, cap: int) -> int:
    return min(cap, max(lo, 1 << (int(max(d, 1)) - 1).bit_length()))


def _ssm_scan_heuristic(xc, dt, B, C, A, h0):
    b, s, di = xc.shape
    return {"chunk": _pick_pow2(s, 8, 128), "block_d": _pick_pow2(di, 8, 256)}


def _ssm_scan_example():
    import numpy as np

    rs = np.random.RandomState(0)
    b, s, di, ds = 2, 12, 8, 4   # s not a chunk multiple: exercises padding
    return (
        jnp.asarray(rs.randn(b, s, di) * 0.5, jnp.float32),        # xc
        jnp.asarray(np.abs(rs.randn(b, s, di)) * 0.1 + 0.01, jnp.float32),
        jnp.asarray(rs.randn(b, s, ds) * 0.5, jnp.float32),        # B
        jnp.asarray(rs.randn(b, s, ds) * 0.5, jnp.float32),        # C
        jnp.asarray(-np.abs(rs.randn(di, ds)) - 0.1, jnp.float32),  # A
        jnp.asarray(rs.randn(b, di, ds) * 0.3, jnp.float32),       # h0
    ), {}


def _ssm_scan_bwd_plan(ct, xc, dt, B, C, A, h0, **kwargs):
    """Backward plan: one fused bwd dispatch site (its own tunable/records)."""
    from ..core.runtime import dispatch

    ct_y, ct_h = ct
    return dispatch(
        "ssm_scan_bwd", ct_y.astype(jnp.float32), ct_h.astype(jnp.float32),
        xc, dt, B, C, A, h0, **kwargs,
    )


@tunable(
    "ssm_scan",
    space=SSM_SCAN_SPACE,
    reference=ssm_scan_chunked,
    heuristic=_ssm_scan_heuristic,
    # A is the [di, ds] state matrix (a weight, never batch-sharded).
    dispatch=DispatchSpec(example=_ssm_scan_example,
                          data_parallel_args=(0, 1, 2, 3, 5),
                          vjp="dispatch", bwd=_ssm_scan_bwd_plan),
)
def ssm_scan(xc, dt, B, C, A, h0, *, chunk: int, block_d: int,
             interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return ssm_scan_pallas(xc, dt, B, C, A, h0, chunk=chunk, block_d=block_d,
                           interpret=interpret)


# ---------------------------------------------------------------------------
# Backward: chunk-windowed VJP of the chunked scan
# ---------------------------------------------------------------------------


SSM_SCAN_BWD_SPACE = ParamSpace([PowerOfTwoParam("chunk", 8, 512)])


def _ssm_scan_bwd_heuristic(ct_y, ct_h, xc, dt, B, C, A, h0):
    return {"chunk": _pick_pow2(xc.shape[1], 8, 64)}


def _ssm_scan_bwd_example():
    (xc, dt, B, C, A, h0), _ = _ssm_scan_example()
    import numpy as np

    rs = np.random.RandomState(1)
    ct_y = jnp.asarray(rs.randn(*xc.shape) * 0.5, jnp.float32)
    ct_h = jnp.asarray(rs.randn(*h0.shape) * 0.5, jnp.float32)
    return (ct_y, ct_h, xc, dt, B, C, A, h0), {}


@tunable(
    "ssm_scan_bwd",
    space=SSM_SCAN_BWD_SPACE,
    reference=ref.ssm_scan_bwd,
    heuristic=_ssm_scan_bwd_heuristic,
    dispatch=DispatchSpec(example=_ssm_scan_bwd_example,
                          data_parallel_args=(0, 1, 2, 3, 4, 5, 7),
                          reduced_outputs=(4,),     # dA sums over the batch
                          # Reference VJP: grad-of-grad differentiates through.
                          vjp="reference"),
)
def ssm_scan_bwd(ct_y, ct_h, xc, dt, B, C, A, h0, *, chunk: int):
    """VJP of the scan with the remat window as the knob: differentiates the
    chunked form, so only [b, chunk, di, ds] coefficient slabs go live."""
    _, vjp = jax.vjp(
        lambda *a: ssm_scan_chunked(*a, chunk=chunk), xc, dt, B, C, A, h0
    )
    return vjp((ct_y, ct_h))


# ---------------------------------------------------------------------------
# Fused single-step decode update
# ---------------------------------------------------------------------------


def _ssm_update_kernel(xc_ref, dt_ref, b_ref, c_ref, a_ref, h_ref,
                       y_ref, hn_ref):
    dt = dt_ref[...]                              # [bb, bd] fp32
    xc = xc_ref[...].astype(jnp.float32)
    da = jnp.exp(dt[:, :, None] * a_ref[...][None])
    hn = da * h_ref[...] + (dt * xc)[:, :, None] * b_ref[...][:, None, :]
    y_ref[...] = jnp.sum(hn * c_ref[...][:, None, :], axis=-1)
    hn_ref[...] = hn


def ssm_update_pallas(
    xc: jax.Array,   # [b, di] model dtype
    dt: jax.Array,   # [b, di] fp32
    B: jax.Array,    # [b, ds] fp32
    C: jax.Array,    # [b, ds] fp32
    A: jax.Array,    # [di, ds] fp32
    h: jax.Array,    # [b, di, ds] fp32
    *,
    block_b: int,
    block_d: int,
    interpret: bool = False,
):
    b, di = xc.shape
    ds = A.shape[1]
    block_b = min(block_b, b)
    block_d = min(block_d, di)
    bp = b + (-b) % block_b
    dip = di + (-di) % block_d
    pad2 = lambda t: jnp.pad(t, ((0, bp - b), (0, dip - di)))
    xcp, dtp = pad2(xc), pad2(dt)
    Bp = jnp.pad(B, ((0, bp - b), (0, 0)))
    Cp = jnp.pad(C, ((0, bp - b), (0, 0)))
    Ap = jnp.pad(A, ((0, dip - di), (0, 0)))
    hp = jnp.pad(h, ((0, bp - b), (0, dip - di), (0, 0)))
    grid = (bp // block_b, dip // block_d)

    y, hn = pl.pallas_call(
        _ssm_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, ds), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, ds), lambda i, j: (i, 0)),
            pl.BlockSpec((block_d, ds), lambda i, j: (j, 0)),
            pl.BlockSpec((block_b, block_d, ds), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, block_d, ds), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, dip), jnp.float32),
            jax.ShapeDtypeStruct((bp, dip, ds), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(xcp, dtp, Bp, Cp, Ap, hp)
    return y[:b, :di], hn[:b, :di]


SSM_UPDATE_SPACE = ParamSpace(
    [
        PowerOfTwoParam("block_b", 8, 512),
        PowerOfTwoParam("block_d", 8, 512),
    ],
    [
        Constraint(
            lambda c: c["block_b"] * c["block_d"] * 16 * 8 + c["block_b"]
            * c["block_d"] * 12 <= SCOPED_VMEM_BYTES,
            "decode-state tile exceeds VMEM budget",
        )
    ],
)


def _ssm_update_heuristic(xc, dt, B, C, A, h):
    b, di = xc.shape
    return {"block_b": _pick_pow2(b, 8, 256), "block_d": _pick_pow2(di, 8, 256)}


def _ssm_update_example():
    import numpy as np

    rs = np.random.RandomState(2)
    b, di, ds = 3, 8, 4
    return (
        jnp.asarray(rs.randn(b, di) * 0.5, jnp.float32),
        jnp.asarray(np.abs(rs.randn(b, di)) * 0.1 + 0.01, jnp.float32),
        jnp.asarray(rs.randn(b, ds) * 0.5, jnp.float32),
        jnp.asarray(rs.randn(b, ds) * 0.5, jnp.float32),
        jnp.asarray(-np.abs(rs.randn(di, ds)) - 0.1, jnp.float32),
        jnp.asarray(rs.randn(b, di, ds) * 0.3, jnp.float32),
    ), {}


def _ssm_update_bwd_plan(ct, xc, dt, B, C, A, h, **kwargs):
    from ..core.runtime import dispatch

    ct_y, ct_h = ct
    return dispatch(
        "ssm_update_bwd", ct_y.astype(jnp.float32), ct_h.astype(jnp.float32),
        xc, dt, B, C, A, h, **kwargs,
    )


@tunable(
    "ssm_update",
    space=SSM_UPDATE_SPACE,
    reference=ref.ssm_update,
    heuristic=_ssm_update_heuristic,
    dispatch=DispatchSpec(example=_ssm_update_example,
                          data_parallel_args=(0, 1, 2, 3, 5),
                          vjp="dispatch", bwd=_ssm_update_bwd_plan),
)
def ssm_update(xc, dt, B, C, A, h, *, block_b: int, block_d: int,
               interpret: Optional[bool] = None):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return ssm_update_pallas(xc, dt, B, C, A, h, block_b=block_b,
                             block_d=block_d, interpret=interpret)


SSM_UPDATE_BWD_SPACE = ParamSpace([PowerOfTwoParam("block_d", 8, 512)])


def _ssm_update_bwd_heuristic(ct_y, ct_h, xc, dt, B, C, A, h):
    return {"block_d": _pick_pow2(xc.shape[1], 8, 256)}


def _ssm_update_bwd_example():
    (xc, dt, B, C, A, h), _ = _ssm_update_example()
    import numpy as np

    rs = np.random.RandomState(3)
    ct_y = jnp.asarray(rs.randn(*xc.shape) * 0.5, jnp.float32)
    ct_h = jnp.asarray(rs.randn(*h.shape) * 0.5, jnp.float32)
    return (ct_y, ct_h, xc, dt, B, C, A, h), {}


@tunable(
    "ssm_update_bwd",
    space=SSM_UPDATE_BWD_SPACE,
    reference=ref.ssm_update_bwd,
    heuristic=_ssm_update_bwd_heuristic,
    dispatch=DispatchSpec(example=_ssm_update_bwd_example,
                          data_parallel_args=(0, 1, 2, 3, 4, 5, 7),
                          reduced_outputs=(4,),     # dA sums over the batch
                          # Reference VJP: grad-of-grad differentiates through.
                          vjp="reference"),
)
def ssm_update_bwd(ct_y, ct_h, xc, dt, B, C, A, h, *, block_d: int):
    """Blocked VJP of the decode update: d_inner is sliced into block_d
    strips (the working-set knob), B/C/state grads summed across strips."""
    di = xc.shape[1]
    bd = max(1, min(block_d, di))
    gx, gdt, gA, gh = [], [], [], []
    gB = gC = None
    for lo in range(0, di, bd):
        hi = min(lo + bd, di)
        _, vjp = jax.vjp(
            ref.ssm_update,
            xc[:, lo:hi], dt[:, lo:hi], B, C, A[lo:hi], h[:, lo:hi],
        )
        dxi, ddti, dBi, dCi, dAi, dhi = vjp((ct_y[:, lo:hi], ct_h[:, lo:hi]))
        gx.append(dxi)
        gdt.append(ddti)
        gA.append(dAi)
        gh.append(dhi)
        gB = dBi if gB is None else gB + dBi
        gC = dCi if gC is None else gC + dCi
    return (
        jnp.concatenate(gx, axis=1),
        jnp.concatenate(gdt, axis=1),
        gB,
        gC,
        jnp.concatenate(gA, axis=0),
        jnp.concatenate(gh, axis=1),
    )


# ---------------------------------------------------------------------------
# Abstract grid models (static legality; see core/gridmodel.py). The scan's
# sequential chunk axis carries the state scratch — the model declares it
# "arbitrary", which is what keeps the hn carry race-free. The *_bwd spaces
# are jnp-only (no pallas_call), so they register no model and
# legal_configs() returns their full enumeration. Nominal shapes use a
# production d_inner (2048), where the d-strip axis is genuinely tiled —
# that is where TPU lane alignment prunes block_d below 128 (ROADMAP item
# 1's "chosen for CPU interpret correctness, not lane alignment").
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _ssm_scan_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((4, 2048, 2048), (4, 2048, 2048), (4, 2048, 16),
                  (4, 2048, 16), (2048, 16), (4, 2048, 16))
    b, s, di = shapes[0]
    ds = shapes[4][1]
    chunk = min(config["chunk"], s)
    block_d = min(config["block_d"], di)
    sp = s + (-s) % chunk
    dip = di + (-di) % block_d
    grid = (b, dip // block_d, sp // chunk)
    xmap = lambda ib, id_, ic: (ib, ic, id_)
    bmap = lambda ib, id_, ic: (ib, ic, 0)
    amap = lambda ib, id_, ic: (id_, 0)
    hmap = lambda ib, id_, ic: (ib, id_, 0)
    return GridModel(
        "ssm_scan", grid, ("parallel", "parallel", "arbitrary"),
        (
            RefModel("xc", (1, chunk, block_d), xmap, (b, sp, dip)),
            RefModel("dt", (1, chunk, block_d), xmap, (b, sp, dip)),
            RefModel("B", (1, chunk, ds), bmap, (b, sp, ds)),
            RefModel("C", (1, chunk, ds), bmap, (b, sp, ds)),
            RefModel("A", (block_d, ds), amap, (dip, ds)),
            RefModel("h0", (1, block_d, ds), hmap, (b, dip, ds)),
            RefModel("y", (1, chunk, block_d), xmap, (b, sp, dip),
                     role="out"),
            RefModel("hn", (1, block_d, ds), hmap, (b, dip, ds),
                     role="out"),
        ),
    )


def _ssm_update_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((1024, 2048), (1024, 2048), (1024, 16), (1024, 16),
                  (2048, 16), (1024, 2048, 16))
    b, di = shapes[0]
    ds = shapes[4][1]
    block_b = min(config["block_b"], b)
    block_d = min(config["block_d"], di)
    bp = b + (-b) % block_b
    dip = di + (-di) % block_d
    grid = (bp // block_b, dip // block_d)
    xy = lambda i, j: (i, j)
    bmap = lambda i, j: (i, 0)
    amap = lambda i, j: (j, 0)
    hmap = lambda i, j: (i, j, 0)
    return GridModel(
        "ssm_update", grid, ("parallel", "parallel"),
        (
            RefModel("xc", (block_b, block_d), xy, (bp, dip)),
            RefModel("dt", (block_b, block_d), xy, (bp, dip)),
            RefModel("B", (block_b, ds), bmap, (bp, ds)),
            RefModel("C", (block_b, ds), bmap, (bp, ds)),
            RefModel("A", (block_d, ds), amap, (dip, ds)),
            RefModel("h", (block_b, block_d, ds), hmap, (bp, dip, ds)),
            RefModel("y", (block_b, block_d), xy, (bp, dip), role="out"),
            RefModel("hn", (block_b, block_d, ds), hmap, (bp, dip, ds),
                     role="out"),
        ),
    )


register_grid_model("ssm_scan", _ssm_scan_grid_model, space=SSM_SCAN_SPACE)
register_grid_model("ssm_update", _ssm_update_grid_model,
                    space=SSM_UPDATE_SPACE)
