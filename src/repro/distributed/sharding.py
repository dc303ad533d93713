"""Divisibility-aware sharding solver: logical axes → mesh axes.

Model code annotates every parameter dim with a *logical* name ("vocab",
"ff", "heads", ...). This module decides which *mesh* axis shards which dim,
given a :class:`Layout`. The assignment is greedy by rule priority with two
hard checks: (a) the dim size must divide the mesh-axis size, (b) a mesh
axis may shard at most one dim per tensor.

Why a solver instead of fixed Megatron rules: the assigned archs have head
counts (24, 14, 56, 8) that do NOT divide a 16-way tensor axis, expert
counts (8) smaller than it, and vocab/ff dims that always divide. Fixed
rules would simply fail; the solver downgrades gracefully (shard ff instead
of experts, replicate heads and lean on batch sharding, ...) and the
*choice set* is exposed to the autotuner as the layout search space — the
paper's "performance directive" applied to distribution.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Layout:
    """One point in the distribution-layout search space."""

    tensor_axis: str = "model"
    data_axes: Tuple[str, ...] = ("data",)       # batch axes (pod prepended if present)
    fsdp: bool = False            # additionally shard params' d_model over data
    shard_experts: bool = True    # prefer expert-parallel over expert-ff TP
    scan_layers: bool = True      # (informational; model always scans)
    # Logical-unit counts: ("heads", 24) means the "heads" dim is 24 physical
    # units (the fused dim is heads·head_dim) — sharding must not split a
    # unit, so divisibility is checked against the COUNT, not the dim size.
    # Splitting mid-head forces an activation reshard at every [b,s,h,hd]
    # reshape, which the baseline dry-run showed costs ~100× the step's
    # useful collective traffic.
    counts: Tuple[Tuple[str, int], ...] = ()
    head_aware: bool = True       # False reproduces the naive baseline
    name: str = "default"

    def count_of(self, logical: str) -> Optional[int]:
        for k, v in self.counts:
            if k == logical:
                return v
        return None


# priority: lower = assigned first. Only these names are ever sharded.
_TENSOR_RULES: Dict[str, int] = {
    "vocab": 0,
    "experts": 1,
    "ff": 2,
    "ff2": 3,
    "heads": 4,
    "kv_heads": 5,
}
_FSDP_NAME = "d_model"


def axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def spec_for_dims(
    dims: Sequence[str],
    shape: Sequence[int],
    mesh: Mesh,
    layout: Layout,
) -> P:
    """PartitionSpec for one tensor given its logical dim names."""
    t_axis = layout.tensor_axis
    t_size = axis_size(mesh, t_axis) if t_axis in mesh.axis_names else 1
    d_axes = tuple(a for a in layout.data_axes if a in mesh.axis_names)
    d_size = 1
    for a in d_axes:
        d_size *= axis_size(mesh, a)

    assignment: Dict[int, Any] = {}
    used_tensor = False

    def unit_ok(name: str, size: int) -> bool:
        if size % t_size:
            return False
        if layout.head_aware:
            c = layout.count_of(name)
            if c is not None and c % t_size:
                return False
        return True

    # 1. tensor-parallel dim: best-priority shardable logical name
    candidates = [
        (prio, i)
        for i, name in enumerate(dims)
        for prio in [_TENSOR_RULES.get(name)]
        if prio is not None and t_size > 1 and unit_ok(name, shape[i])
    ]
    if not layout.shard_experts:
        candidates = [(p, i) for (p, i) in candidates if dims[i] != "experts"]
    if candidates:
        _, idx = min(candidates)
        assignment[idx] = t_axis
        used_tensor = True

    # 2. FSDP dim: shard d_model over the data axes (XLA all-gathers on use)
    if layout.fsdp and d_size > 1:
        for i, name in enumerate(dims):
            if i in assignment or name != _FSDP_NAME:
                continue
            if shape[i] % d_size == 0:
                assignment[i] = d_axes if len(d_axes) > 1 else d_axes[0]
                break

    if not assignment:
        return P()
    parts = [assignment.get(i) for i in range(len(dims))]
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def param_shardings(axes_tree, shapes_tree, mesh: Mesh, layout: Layout):
    """NamedSharding tree for a params pytree (axes_tree gives dim names)."""
    is_names = lambda x: isinstance(x, tuple) and all(isinstance(s, str) for s in x)

    def one(ax, leaf):
        return NamedSharding(mesh, spec_for_dims(ax, leaf.shape, mesh, layout))

    return jax.tree_util.tree_map(one, axes_tree, shapes_tree, is_leaf=is_names)


# ---------------------------------------------------------------------------
# Activations / batches / caches
# ---------------------------------------------------------------------------


def _divisible_data_axes(
    sizes: Dict[str, int], layout: Layout, batch_size: int
) -> Tuple[Tuple[str, ...], int]:
    """Greedy data-axis selection for a batch-like dim: which of the
    data-parallel axes (pod first, then the layout's data axes) shard a dim
    of `batch_size`, and their combined degree.

    This single rule backs both :func:`batch_spec` (the sharding the trainer
    actually requests) and :func:`local_shard_shape` (the per-device shape
    the tuning database keys on) — keeping them one function is what makes
    campaign records match training dispatch.
    """
    seen, use = set(), []
    prod = 1
    for a in ("pod",) + tuple(layout.data_axes):
        if a in seen or a not in sizes:
            continue
        seen.add(a)
        s = int(sizes[a])
        if s > 0 and batch_size % (prod * s) == 0:
            use.append(a)
            prod *= s
    return tuple(use), prod


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_parallel_degree(
    sizes: Dict[str, int], layout: Layout, batch_size: int
) -> int:
    """How many ways a batch-like dim of `batch_size` is split on this mesh."""
    return _divisible_data_axes(sizes, layout, batch_size)[1]


def local_shard_shape(
    shape: Sequence[int], sizes: Dict[str, int], layout: Layout
) -> Tuple[int, ...]:
    """The per-device shape of a batch-leading global array under `layout`.

    Only the leading (batch/token) dim is divided — mirror of
    :func:`batch_spec`: activations inside a jit-sharded trace carry global
    shapes, but each device executes the local shard, and that is the shape
    a tuning campaign measures. Dims the mesh cannot divide stay global.
    """
    shape = tuple(int(d) for d in shape)
    if not shape:
        return shape
    dp = data_parallel_degree(sizes, layout, shape[0])
    if dp <= 1:
        return shape
    return (shape[0] // dp,) + shape[1:]


def localize_shapes(
    shapes: Sequence[Sequence[int]],
    batch_arg_indices: Optional[Sequence[int]] = None,
    batch_arg_dims: Optional[Dict[int, int]] = None,
) -> Tuple[Tuple[int, ...], ...]:
    """Localize batch-sharded shapes by the *ambient* data-parallel degree.

    This is the runtime's local-shape keying hook (see
    ``repro.core.tuner._args_key``). The degree comes from the enclosing
    :func:`mesh_context`'s explicit ``dp_degree`` — computed ONCE by whoever
    owns the step's input sharding (the Trainer: from its batch dim), never
    re-derived from an individual argument's leading dim. Per-arg derivation
    would silently diverge from both the real sharding and the campaign
    planner whenever a data axis happens to divide a *flattened* activation
    dim (batch·seq) but not the batch itself. Outside a mesh context, or
    when the context carries no degree, this is the identity — unsharded
    database keys are unchanged.

    ``batch_arg_indices`` localizes the *leading* dim of those shapes — the
    forward convention. ``batch_arg_dims`` (``{shape index: dim index}``)
    localizes an arbitrary dim instead: backward dispatch sites need this
    because transposed operands carry the token dim elsewhere (matmul's
    dL/dw is ``x.T [d, T] @ ct [T, n]`` — the sharded dim of arg 0 is dim
    1). A dim the degree does not divide is left global (those rows are
    replicated, not sharded).
    """
    dp = _DP_CTX.get()
    if not dp or dp <= 1:
        return tuple(tuple(int(d) for d in s) for s in shapes)
    if batch_arg_dims is not None:
        dims = dict(batch_arg_dims)
    elif batch_arg_indices is not None:
        dims = {i: 0 for i in batch_arg_indices}
    else:
        dims = {i: 0 for i in range(len(shapes))}

    def one(i, s):
        s = tuple(int(d) for d in s)
        dim = dims.get(i)
        if dim is not None and len(s) > dim and s[dim] % dp == 0:
            return s[:dim] + (s[dim] // dp,) + s[dim + 1:]
        return s

    return tuple(one(i, s) for i, s in enumerate(shapes))


def data_axes_for(mesh: Mesh, layout: Layout, degree: int) -> Tuple[str, ...]:
    """The mesh axes a batch dim split ``degree`` ways is sharded over —
    the greedy prefix of the data axes that :func:`batch_spec` takes."""
    sizes = mesh_axis_sizes(mesh)
    use, prod = [], 1
    for a in dict.fromkeys(("pod",) + tuple(layout.data_axes)):
        if a in sizes and degree % (prod * sizes[a]) == 0:
            use.append(a)
            prod *= sizes[a]
    return tuple(use) if prod == degree else ()


def batch_spec(mesh: Mesh, layout: Layout, batch_size: int) -> P:
    """Shard the batch dim over every data-ish axis that divides it."""
    use, _ = _divisible_data_axes(mesh_axis_sizes(mesh), layout, batch_size)
    if not use:
        return P()
    return P(use if len(use) > 1 else use[0])


def data_specs(batch_tree, mesh: Mesh, layout: Layout):
    """Shardings for a training/serving batch: dim0 = batch."""

    def one(leaf):
        if not hasattr(leaf, "shape") or leaf.ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, batch_spec(mesh, layout, leaf.shape[0]))

    return jax.tree_util.tree_map(one, batch_tree)


def cache_shardings(cache_tree, mesh: Mesh, layout: Layout):
    """Shardings for decode caches.

    Leaves are stacked (layers, batch, ...). Strategy:
      dim0 (layers) replicated; dim1 (batch) over data axes if divisible;
      then the largest remaining dim divisible by the tensor axis gets it
      (kv-heads when divisible, else cache-length / feature dims — for B=1
      long-context cells this lands on the sequence dim, i.e. sequence
      parallelism of the KV cache).
    """
    t_axis = layout.tensor_axis
    t_size = axis_size(mesh, t_axis) if t_axis in mesh.axis_names else 1
    d_axes = tuple(a for a in ("pod",) + tuple(layout.data_axes) if a in mesh.axis_names)

    def one(leaf):
        if not hasattr(leaf, "shape") or leaf.ndim < 2:
            return NamedSharding(mesh, P())
        parts: list = [None] * leaf.ndim
        # batch over data axes
        bs = leaf.shape[1]
        use, prod, seen = [], 1, set()
        for a in d_axes:
            if a in seen:
                continue
            seen.add(a)
            s = axis_size(mesh, a)
            if bs % (prod * s) == 0:
                use.append(a)
                prod *= s
        if use:
            parts[1] = tuple(use) if len(use) > 1 else use[0]
        leftover_data = [a for a in d_axes if a not in use]
        # tensor axis on the largest divisible remaining dim (prefer last dims)
        if t_size > 1:
            best = None
            for i in range(leaf.ndim - 1, 1, -1):
                if leaf.shape[i] % t_size == 0 and leaf.shape[i] >= t_size:
                    if best is None or leaf.shape[i] > leaf.shape[best]:
                        best = i
            if best is not None:
                parts[best] = t_axis
        # unsharded batch (B=1): put leftover data axes on the longest dim
        if leftover_data and parts[1] is None and leaf.ndim >= 3:
            d_size = 1
            for a in leftover_data:
                d_size *= axis_size(mesh, a)
            cand = [
                i for i in range(2, leaf.ndim)
                if parts[i] is None and leaf.shape[i] % d_size == 0
                and leaf.shape[i] >= d_size
            ]
            if cand:
                i = max(cand, key=lambda j: leaf.shape[j])
                parts[i] = tuple(leftover_data) if len(leftover_data) > 1 else leftover_data[0]
        while parts and parts[-1] is None:
            parts.pop()
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map(one, cache_tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Ambient mesh/layout context — lets deep model code (e.g. MoE dispatch)
# place with_sharding_constraint hints without threading mesh objects
# through every layer signature. Set by build_cell / Trainer.
# ---------------------------------------------------------------------------

import contextlib
import contextvars

_MESH_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mesh_layout", default=None
)
# The step's data-parallel degree, for local-shape database keying. Kept in
# its own contextvar (not the mesh/layout tuple) so current_mesh_layout()
# keeps its two-tuple contract for constrain()/model code.
_DP_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_dp_degree", default=None
)

# Whether the ambient dp_degree is an *approximation*: the scope owner
# computed it from a dim (the microbatch) whose divisibility differs from
# the full input batch XLA actually sharded, so local-shape keys may not
# match the true per-device shard. Carried separately so the keying layer
# (tuner._args_key) can emit a structured one-time warning naming the key.
_DP_APPROX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_dp_approx", default=False
)


@contextlib.contextmanager
def mesh_context(mesh: Mesh, layout: Layout, dp_degree: Optional[int] = None,
                 dp_approx: bool = False):
    """Ambient mesh/layout scope.

    `dp_degree` opts the scope into local-shape database keying (see
    :func:`localize_shapes`): it is the degree the step's *batch dim* is
    actually sharded at — the owner of the input shardings computes it via
    :func:`data_parallel_degree` on that batch dim (as the Trainer does).
    Left at None (the dry-run / lower_cell scopes), dispatch keys stay
    global.

    `dp_approx` flags that degree as approximate (see :data:`_DP_APPROX`):
    the Trainer sets it when the per-microbatch batch dim divides the mesh
    differently from the full input batch, so keys computed under this scope
    trigger the one-time ``dispatch.local_key_approx`` obs warning.
    """
    tok = _MESH_CTX.set((mesh, layout))
    tok_dp = _DP_CTX.set(dp_degree)
    tok_ap = _DP_APPROX.set(bool(dp_approx))
    try:
        yield
    finally:
        _MESH_CTX.reset(tok)
        _DP_CTX.reset(tok_dp)
        _DP_APPROX.reset(tok_ap)


def current_mesh_layout():
    return _MESH_CTX.get()


def current_dp_degree() -> Optional[int]:
    return _DP_CTX.get()


def current_dp_approx() -> bool:
    """Is the ambient local-shape keying degree an approximation?"""
    return bool(_DP_APPROX.get())


def constrain(x, *dims):
    """Best-effort sharding hint: dims are mesh-axis names or None.

    No-op outside a mesh_context, so model code stays runnable on the bare
    1-device host without ceremony.
    """
    ctx = _MESH_CTX.get()
    if ctx is None:
        return x
    mesh, _ = ctx
    parts = [d if (d is None or d in mesh.axis_names) else None for d in dims]
    while parts and parts[-1] is None:
        parts.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts))
    )


def constrain_heads(x, n_units: int, unit_dim: int):
    """Sharding annotation for attention activations around the head
    split/merge reshapes: batch (dim 0) over the data axes that divide it,
    the head dim (`unit_dim`, carrying `n_units` head units) over the
    tensor axis when it divides the *unit count* (never mid-head), all
    other dims replicated.

    These anchors are what lets the SPMD partitioner walk the
    ``[b, s, h·hd] ⇄ [b, h, s, hd] ⇄ [b·h, s, hd]`` reshape chain around a
    flash-attention dispatch without an "involuntary full
    rematerialization" (an all-gather + reshard of the whole activation —
    the warning the sharded smoke step used to print). No-op outside a
    mesh_context.
    """
    ctx = _MESH_CTX.get()
    if ctx is None:
        return x
    mesh, layout = ctx
    sizes = mesh_axis_sizes(mesh)
    parts: list = [None] * x.ndim
    use, _ = _divisible_data_axes(sizes, layout, int(x.shape[0]))
    if use:
        parts[0] = tuple(use) if len(use) > 1 else use[0]
    t = layout.tensor_axis
    t_size = int(sizes.get(t, 1))
    if (t_size > 1 and n_units % t_size == 0
            and int(x.shape[unit_dim]) % t_size == 0):
        parts[unit_dim] = t
    while parts and parts[-1] is None:
        parts.pop()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))
