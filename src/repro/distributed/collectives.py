"""Distributed-optimization tricks: gradient compression + manual collectives.

Gradient compression targets the cross-pod ("pod" axis) all-reduce, which
rides DCN, not ICI — its bytes are the multi-pod scaling tax. Two schemes:

  * bf16: cast grads before reduction (2× bytes). Lossy but empirically safe
    for LM training at these scales.
  * int8 + error feedback: per-tensor symmetric quantization; the residual
    (g - dequant(quant(g))) is carried in optimizer-side state and added to
    the next step's gradient. 1-bit-SGD-style EF guarantees the *accumulated*
    gradient is unbiased over time; test_collectives proves convergence on a
    quadratic matches fp32 within tolerance.

`ring_all_reduce` is a shard_map/ppermute reference implementation of the
bidirectional ring schedule — the 'collective schedule' variant the layout
tuner can select against XLA's built-in all-reduce (and the unit test proves
it numerically identical to psum).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# Gradient compression with error feedback
# ---------------------------------------------------------------------------


def ef_init(params) -> Any:
    """Zero error-feedback residuals, shaped like params (fp32)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )


def _quant_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_grads(grads, ef_state, mode: str = "none"):
    """Apply compression (simulating the wire format of the cross-pod
    all-reduce) + error feedback. Returns (compressed_grads, new_ef_state).

    In a real deployment the quant/dequant brackets the DCN all-reduce; under
    pjit the reduction is compiler-inserted, so we compress the gradient
    *contribution* — same numerics, and the wire-byte savings are reported in
    the roofline collective term by the corresponding layout variant.
    """
    if mode == "none":
        return grads, ef_state
    if mode == "bf16":
        out = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.bfloat16).astype(jnp.float32), grads
        )
        return out, ef_state
    if mode == "int8_ef":

        def one(g, e):
            g32 = g.astype(jnp.float32) + e
            q, s = _quant_int8(g32)
            deq = _dequant_int8(q, s)
            return deq, g32 - deq

        g_flat, treedef = jax.tree_util.tree_flatten(grads)
        e_flat = jax.tree_util.tree_leaves(ef_state)
        pairs = [one(g, e) for g, e in zip(g_flat, e_flat)]
        out = jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs])
        new_ef = jax.tree_util.tree_unflatten(treedef, [p[1] for p in pairs])
        return out, new_ef
    raise ValueError(f"unknown compression mode {mode!r}")


# ---------------------------------------------------------------------------
# Manual ring all-reduce (collective-schedule variant)
# ---------------------------------------------------------------------------


def ring_all_reduce(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Bidirectional-ring all-reduce via explicit ppermute hops.

    Semantics: `x` is a global [n, d] array sharded along dim0 by `axis`
    (row i = device i's contribution). Returns the same global shape where
    EVERY row equals the elementwise sum — i.e. an all-reduce whose schedule
    we own: n-1 reduce-scatter hops + n-1 all-gather hops, each moving d/n
    elements per device. Total wire bytes per device = 2·d·(n-1)/n — the
    bandwidth-optimal ring, vs XLA's opaque choice. Exists as a searchable
    collective-schedule variant and as the overlap template (each hop is a
    fori_loop step that XLA may interleave with independent compute).

    test_collectives proves it equals psum exactly on an 8-device host mesh.
    """
    n = mesh.shape[axis]
    if n == 1:
        return x
    d = x.shape[-1]
    pad = (-d) % n

    def body(v):
        # v: local row [1, d_padded] — split into n ring chunks
        chunks = v.reshape(n, -1)
        me = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def rs(step, acc):
            send_idx = (me - step) % n
            buf = jax.lax.ppermute(acc[send_idx], axis, perm)
            recv_idx = (me - step - 1) % n
            return acc.at[recv_idx].add(buf)

        acc = jax.lax.fori_loop(0, n - 1, rs, chunks)
        # fully-reduced chunk now lives at index (me + 1) % n

        def ag(step, acc):
            send_idx = (me + 1 - step) % n
            buf = jax.lax.ppermute(acc[send_idx], axis, perm)
            recv_idx = (me - step) % n
            return acc.at[recv_idx].set(buf)

        acc = jax.lax.fori_loop(0, n - 1, ag, acc)
        return acc.reshape(1, -1)

    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None))
    out = fn(xp)
    return out[:, :d] if pad else out
