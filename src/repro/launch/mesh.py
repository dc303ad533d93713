"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS for 512 host devices before any jax
import; everything else sees the real 1-device CPU).

Topology (TPU v5e): single pod = 16×16 = 256 chips, axes ("data", "model");
multi-pod = 2 pods = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is pure data parallelism over DCN, "model" stays intra-pod ICI.
"""
from __future__ import annotations

import math

import jax


def _mk_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, devices=devices, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — the "
            "dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax"
        )
    return _mk_mesh(shape, axes, devs[:n])


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1×1 mesh for CPU smoke tests and examples."""
    return _mk_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def parse_mesh_spec(spec: str):
    """"DATAxMODEL" (or "PODxDATAxMODEL") -> (shape tuple, axis names).

    The shared notation for ``--mesh`` launcher flags and the campaign
    planner's ``--train-mesh``: "2x4" is a (data=2, model=4) mesh, "2x16x16"
    prepends a pod axis.
    """
    try:
        dims = tuple(int(d) for d in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh spec {spec!r}: expected e.g. '2x4' or '2x16x16'")
    if len(dims) == 2:
        return dims, ("data", "model")
    if len(dims) == 3:
        return dims, ("pod", "data", "model")
    raise ValueError(f"mesh spec {spec!r}: expected 2 or 3 dims, got {len(dims)}")


def make_mesh_from_spec(spec: str) -> jax.sharding.Mesh:
    """Build a mesh from a "DATAxMODEL" spec over the available devices."""
    shape, axes = parse_mesh_spec(spec)
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "importing jax for a fake-device host mesh"
        )
    return _mk_mesh(shape, axes, devs[:n])
