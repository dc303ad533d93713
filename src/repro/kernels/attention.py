"""Flash attention (causal / sliding-window, GQA) — Pallas kernel.

TPU adaptation of the memory-hierarchy insight: never materialize the
[s_q, s_k] score matrix in HBM; stream K/V blocks through VMEM with an
online-softmax accumulator. Tunables are the (block_q, block_k) VMEM tiles —
the direct analogue of the paper's per-platform tile/pragma knobs (the best
blocks depend on seq_len and head_dim exactly as Figure 1's best variant
depends on input size).

Grid: (batch·heads, s_q/block_q, s_k/block_k); k-dim sequential (carries the
running max / denominator / output accumulator in VMEM scratch). Causal and
sliding-window masking prune fully-masked K/V blocks via `pl.when`, so SWA
cost scales with window, not seq_len.
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

_NEG_INF = -1e30  # avoid nan from (-inf) - (-inf) in fully-masked rows


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    k_steps: int,
    q_offset: int,
):
    """Forward flash kernel; emits per-row logsumexp alongside the output —
    the residual contract hands it to the backward plan, which no longer
    re-runs this schedule to recover it."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level pruning: with causality, K blocks strictly in the future of
    # the whole Q block contribute nothing; with SWA, K blocks entirely
    # before the window do not either.
    q_hi = (qi + 1) * block_q - 1 + q_offset    # last absolute q position
    q_lo = qi * block_q + q_offset              # first absolute q position
    k_lo = ki * block_k
    k_hi = (ki + 1) * block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi
    if window > 0:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [block_q, d]
        k = k_ref[0].astype(jnp.float32)          # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # [block_q, block_k]

        if causal or window > 0:
            q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = jnp.bool_(True)
            if causal:
                mask &= q_ids >= k_ids
            if window > 0:
                mask &= (q_ids - k_ids) < window
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                        # [block_q, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # [block_q, block_k]
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(denom)


def flash_attention_pallas(
    q: jax.Array,  # [b, h, s_q, d]
    k: jax.Array,  # [b, kv, s_k, d]
    v: jax.Array,  # [b, kv, s_k, d]
    *,
    block_q: int,
    block_k: int,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    interpret: bool = False,
    return_residuals: bool = False,
):
    b, h, s_q, d = q.shape
    _, kv, s_k, _ = k.shape
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    assert s_q % block_q == 0 and s_k % block_k == 0, (s_q, s_k, block_q, block_k)
    k_steps = s_k // block_k
    grid = (b * h, s_q // block_q, k_steps)
    # Decode/suffix alignment: q positions occupy the *end* of the k axis.
    q_offset = s_k - s_q

    qr = q.reshape(b * h, s_q, d)
    # GQA: map flattened (b*h) program index to its kv head.
    def kv_index(bh, qi, ki):
        bb = bh // h
        hh = bh % h
        return (bb * kv + hh // group, ki, 0)

    kr = k.reshape(b * kv, s_k, d)
    vr = v.reshape(b * kv, s_k, d)

    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            window=window,
            block_q=block_q,
            block_k=block_k,
            k_steps=k_steps,
            q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)
    if return_residuals:
        return out.reshape(b, h, s_q, d), lse.reshape(b, h, s_q)
    return out.reshape(b, h, s_q, d)


def _attn_vmem_bytes(cfg, d: int = 128, dtype_bytes: int = 4) -> int:
    """Scoped VMEM of the largest of the three passes. Each double-buffers
    its q/do/o and k/v tiles and holds f32 [bq, bk] score temporaries (two
    forward, four backward); [bq] columns (lse, delta, m, l) pad to 128 f32
    lanes, [bq] rows to 8 sublanes. d=128 and f32 operands bound every head
    width and dtype the models use."""
    bq, bk = cfg["block_q"], cfg["block_k"]
    tiles = 2 * (2 * bq + 2 * bk) * d * dtype_bytes
    col = bq * 128 * 4
    fwd = tiles + 4 * col + bq * d * 4 + 2 * bq * bk * 4
    dq = tiles + 4 * col + 2 * bq * d * dtype_bytes + bq * d * 4 + 4 * bq * bk * 4
    dkv = tiles + 4 * 8 * bq * 4 + 6 * bk * d * 4 + 4 * bq * bk * 4
    return max(fwd, dq, dkv)


ATTENTION_SPACE = ParamSpace(
    [
        PowerOfTwoParam("block_q", 128, 2048),
        PowerOfTwoParam("block_k", 128, 2048),
    ],
    [
        Constraint(
            lambda c: _attn_vmem_bytes(c) <= SCOPED_VMEM_BYTES,
            "attention tile working set exceeds VMEM budget",
        )
    ],
)


def _attn_heuristic(q, k, v):
    s_q, s_k = q.shape[2], k.shape[2]
    return {"block_q": min(512, max(128, min(s_q, 512))) if s_q >= 128 else 128,
            "block_k": 512 if s_k >= 512 else 128}


def _attn_example():
    import numpy as np

    rs = np.random.RandomState(0)
    mk = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    return (mk(1, 4, 128, 16), mk(1, 2, 128, 16), mk(1, 2, 128, 16)), {"causal": True}


def _flash_bwd_plan(ct, q, k, v, o, lse, **kwargs):
    """Backward plan for the fwd tunable: one fused bwd dispatch site
    (dq/dk/dv together). The residual contract hands it the forward output
    and per-query logsumexp, so no recompute pass is needed."""
    from ..core.runtime import dispatch

    return dispatch("flash_attention_bwd", ct, q, k, v, o, lse, **kwargs)


@tunable(
    "flash_attention",
    space=ATTENTION_SPACE,
    # Tuning reference emits the same (out, lse) structure as the variant.
    reference=functools.partial(ref.attention_res, causal=True),
    heuristic=_attn_heuristic,
    dispatch=DispatchSpec(
        # Deployment reference is primal-only (same call kwargs).
        reference=ref.attention,
        # Same shapes, different masking semantics => distinct db records.
        key_extra=lambda kw: f"c{kw.get('causal', True)}w{kw.get('window', 0)}",
        example=_attn_example,
        # q, k, v all lead with the (data-parallel) batch dim.
        data_parallel_args=(0, 1, 2),
        vjp="dispatch",
        bwd=_flash_bwd_plan,
        residuals=1,  # per-query logsumexp, threaded to the bwd plan
    ),
)
def flash_attention(
    q, k, v, *, block_q: int, block_k: int,
    causal: bool = True, window: int = 0,
    scale: Optional[float] = None, interpret: Optional[bool] = None,
):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return flash_attention_pallas(
        q, k, v, block_q=block_q, block_k=block_k,
        causal=causal, window=window, scale=scale, interpret=interpret,
        return_residuals=True,
    )


# ---------------------------------------------------------------------------
# Flash attention backward: residual-threaded (o, lse) from the forward,
# then blocked dq and dk/dv — two Pallas passes, no recompute pass.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    dq_scr,
    *,
    scale: float,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    k_steps: int,
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_hi = (qi + 1) * block_q - 1 + q_offset
    q_lo = qi * block_q + q_offset
    k_lo = ki * block_k
    k_hi = (ki + 1) * block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi
    if window > 0:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)         # [bq, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal or window > 0:
            q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = jnp.bool_(True)
            if causal:
                mask &= q_ids >= k_ids
            if window > 0:
                mask &= (q_ids - k_ids) < window
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])                # exact softmax via lse
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                           # [bq, bk]
        ds = p * (dp - delta_ref[0])
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    @pl.when(ki == k_steps - 1)
    def _done():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *,
    scale: float,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    q_steps: int,
    q_offset: int,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_hi = (qi + 1) * block_q - 1 + q_offset
    q_lo = qi * block_q + q_offset
    k_lo = ki * block_k
    k_hi = (ki + 1) * block_k - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi
    if window > 0:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)         # [bq, d]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # [bk, bq]
        if causal or window > 0:
            k_ids = k_lo + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            mask = jnp.bool_(True)
            if causal:
                mask &= q_ids >= k_ids
            if window > 0:
                mask &= (q_ids - k_ids) < window
            st = jnp.where(mask, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])              # [bk, bq]
        dv_scr[...] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                           # [bk, bq]
        dst = pt * (dpt - delta_ref[0])
        dk_scr[...] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale

    @pl.when(qi == q_steps - 1)
    def _done():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(
    ct: jax.Array,   # [b, h, s_q, d] — cotangent of the attention output
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,    # [b, h, s_q, d] — residual: the forward output
    lse: jax.Array,  # [b, h, s_q]    — residual: per-query logsumexp
    *,
    block_q: int,
    block_k: int,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    interpret: bool = False,
):
    """(dq, dk, dv) via the residual-threaded flash backward: (o, lse) come
    from the forward pass (no recompute), delta = rowsum(do·o) is one cheap
    elementwise reduction, then one k-streaming pass for dq and one
    q-streaming pass for dk/dv — exactly two Pallas calls. Nothing
    [s_q, s_k]-sized ever touches HBM. GQA: dk/dv are computed per q-head
    and group-summed into the kv heads afterwards.
    """
    b, h, s_q, d = q.shape
    _, kv, s_k, _ = k.shape
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    assert s_q % block_q == 0 and s_k % block_k == 0, (s_q, s_k, block_q, block_k)
    k_steps = s_k // block_k
    q_steps = s_q // block_q
    q_offset = s_k - s_q

    qr = q.reshape(b * h, s_q, d)
    dor = ct.reshape(b * h, s_q, d)
    kr = k.reshape(b * kv, s_k, d)
    vr = v.reshape(b * kv, s_k, d)

    def kv_index_q(bh, qi, ki):
        bb = bh // h
        hh = bh % h
        return (bb * kv + hh // group, ki, 0)

    def kv_index_k(bh, ki, qi):
        bb = bh // h
        hh = bh % h
        return (bb * kv + hh // group, ki, 0)

    common = dict(
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_offset=q_offset,
    )

    # delta = rowsum(do·o) from the residual-threaded forward output. The
    # dq pass reads lse/delta as [bq, 1] columns (rows of its score block),
    # the dk/dv pass as [1, bq] rows (columns of its transposed block): each
    # layout keeps its block's last two dims tileable.
    o_r = o.reshape(b * h, s_q, d)
    lse_r = lse.astype(jnp.float32).reshape(b * h, s_q)
    delta = jnp.sum(dor.astype(jnp.float32) * o_r.astype(jnp.float32), axis=-1)
    lse_col, delta_col = lse_r[:, :, None], delta[:, :, None]
    lse_row, delta_row = lse_r[:, None, :], delta[:, None, :]

    # 1. dq: stream K/V blocks per Q block (k grid dim carries the acc)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, k_steps=k_steps, **common),
        grid=(b * h, q_steps, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index_q),
            pl.BlockSpec((1, block_k, d), kv_index_q),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr, dor, lse_col, delta_col)

    # 2. dk/dv: stream Q blocks per K block (q grid dim carries the accs),
    # per q-head; group-sum into kv heads below.
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, q_steps=q_steps, **common),
        grid=(b * h, k_steps, q_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index_k),
            pl.BlockSpec((1, block_k, d), kv_index_k),
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_k, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCOPED_VMEM_BYTES,
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr, dor, lse_row, delta_row)
    dk = dk_h.reshape(b, kv, group, s_k, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, kv, group, s_k, d).sum(axis=2).astype(v.dtype)
    return dq.reshape(b, h, s_q, d), dk, dv


def _attn_bwd_heuristic(ct, q, k, v, o, lse):
    return _attn_heuristic(q, k, v)


def _attn_bwd_example():
    import numpy as np

    rs = np.random.RandomState(1)
    mk = lambda *s: jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
    q, k, v = mk(1, 4, 128, 16), mk(1, 2, 128, 16), mk(1, 2, 128, 16)
    o, lse = ref.attention_res(q, k, v, causal=True)
    return (mk(1, 4, 128, 16), q, k, v, o, lse), {"causal": True}


@tunable(
    "flash_attention_bwd",
    space=ATTENTION_SPACE,
    reference=ref.attention_bwd,
    heuristic=_attn_bwd_heuristic,
    dispatch=DispatchSpec(
        key_extra=lambda kw: f"c{kw.get('causal', True)}w{kw.get('window', 0)}",
        example=_attn_bwd_example,
        # ct, q, k, v, o, lse all lead with the batch dim.
        data_parallel_args=(0, 1, 2, 3, 4, 5),
        # Reference VJP so grad-of-grad differentiates through this site.
        vjp="reference",
    ),
)
def flash_attention_bwd(
    ct, q, k, v, o, lse, *, block_q: int, block_k: int,
    causal: bool = True, window: int = 0,
    scale: Optional[float] = None, interpret: Optional[bool] = None,
):
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return flash_attention_bwd_pallas(
        ct, q, k, v, o, lse, block_q=block_q, block_k=block_k,
        causal=causal, window=window, scale=scale, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Abstract grid models (static legality; see core/gridmodel.py). The
# forward asserts block divisibility instead of padding, so the builders
# return None (= kernel rejects the shapes) when s_q/s_k don't divide. The
# backward realizes TWO pallas_calls — dq and dk/dv; (o, lse) arrive as
# residuals from the forward — one model each; both tunables share
# ATTENTION_SPACE, so a config must be legal under all three models.
# ---------------------------------------------------------------------------
from ..core.gridmodel import GridModel, RefModel, register_grid_model


def _flash_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((2, 4, 4096, 128), (2, 2, 4096, 128), (2, 2, 4096, 128))
    b, h, s_q, d = shapes[0]
    kv, s_k = shapes[1][1], shapes[1][2]
    if h % kv:
        return None
    group = h // kv
    bq = min(config["block_q"], s_q)
    bk = min(config["block_k"], s_k)
    if s_q % bq or s_k % bk:
        return None
    grid = (b * h, s_q // bq, s_k // bk)
    qmap = lambda bh, qi, ki: (bh, qi, 0)
    kvmap = lambda bh, qi, ki: ((bh // h) * kv + (bh % h) // group, ki, 0)
    return GridModel(
        "flash_attention", grid, ("parallel", "parallel", "arbitrary"),
        (
            RefModel("q", (1, bq, d), qmap, (b * h, s_q, d)),
            RefModel("k", (1, bk, d), kvmap, (b * kv, s_k, d)),
            RefModel("v", (1, bk, d), kvmap, (b * kv, s_k, d)),
            RefModel("out", (1, bq, d), qmap, (b * h, s_q, d), role="out"),
            RefModel("lse", (1, bq, 1), qmap, (b * h, s_q, 1), role="out"),
        ),
    )


def _flash_bwd_grid_model(config, shapes=None):
    if shapes is None:
        shapes = ((2, 4, 4096, 128), (2, 4, 4096, 128),
                  (2, 2, 4096, 128), (2, 2, 4096, 128),
                  (2, 4, 4096, 128), (2, 4, 4096))
    b, h, s_q, d = shapes[1]
    kv, s_k = shapes[2][1], shapes[2][2]
    if h % kv:
        return None
    group = h // kv
    bq = min(config["block_q"], s_q)
    bk = min(config["block_k"], s_k)
    if s_q % bq or s_k % bk:
        return None
    q_steps, k_steps = s_q // bq, s_k // bk
    qmap = lambda bh, qi, ki: (bh, qi, 0)
    kvmap = lambda bh, qi, ki: ((bh // h) * kv + (bh % h) // group, ki, 0)
    q_dims, kv_dims = (b * h, s_q, d), (b * kv, s_k, d)
    dq_pass = GridModel(
        "flash_attention_bwd", (b * h, q_steps, k_steps),
        ("parallel", "parallel", "arbitrary"),
        (
            RefModel("q", (1, bq, d), qmap, q_dims),
            RefModel("k", (1, bk, d), kvmap, kv_dims),
            RefModel("v", (1, bk, d), kvmap, kv_dims),
            RefModel("do", (1, bq, d), qmap, q_dims),
            RefModel("lse", (1, bq, 1), qmap, (b * h, s_q, 1)),
            RefModel("delta", (1, bq, 1), qmap, (b * h, s_q, 1)),
            RefModel("dq", (1, bq, d), qmap, q_dims, role="out"),
        ),
    )
    # dk/dv stream Q per K block: grid axes are (bh, ki, qi).
    qmap_k = lambda bh, ki, qi: (bh, qi, 0)
    lmap_k = lambda bh, ki, qi: (bh, 0, qi)
    kvmap_k = lambda bh, ki, qi: ((bh // h) * kv + (bh % h) // group, ki, 0)
    dkv_map = lambda bh, ki, qi: (bh, ki, 0)
    dkv_pass = GridModel(
        "flash_attention_bwd", (b * h, k_steps, q_steps),
        ("parallel", "parallel", "arbitrary"),
        (
            RefModel("q", (1, bq, d), qmap_k, q_dims),
            RefModel("k", (1, bk, d), kvmap_k, kv_dims),
            RefModel("v", (1, bk, d), kvmap_k, kv_dims),
            RefModel("do", (1, bq, d), qmap_k, q_dims),
            RefModel("lse", (1, 1, bq), lmap_k, (b * h, 1, s_q)),
            RefModel("delta", (1, 1, bq), lmap_k, (b * h, 1, s_q)),
            RefModel("dk", (1, bk, d), dkv_map, (b * h, s_k, d), role="out"),
            RefModel("dv", (1, bk, d), dkv_map, (b * h, s_k, d), role="out"),
        ),
    )
    return (dq_pass, dkv_pass)


register_grid_model("flash_attention", _flash_grid_model,
                    space=ATTENTION_SPACE)
register_grid_model("flash_attention_bwd", _flash_bwd_grid_model,
                    space=ATTENTION_SPACE)
