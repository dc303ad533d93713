"""Hardware profiles, platform fingerprinting, and the override escape hatch.

Performance portability (the paper's C4) requires tuning results to be keyed
by *platform*: the same generic code specializes differently per machine.
A :class:`HardwareProfile` carries the peaks the analytic evaluator needs
(roofline terms) plus the capacity constraints (VMEM) that prune kernel tile
spaces.

:func:`detect_platform` fingerprints ``jax.devices()`` into one of the known
profiles (tpu-v4 / tpu-v5e / cpu-host), so the dispatch runtime and the
campaign tools namespace their databases automatically — no caller wires a
platform string. When automatic detection is wrong or too coarse (a new TPU
generation, an A/B experiment that must not share records with production),
the escape hatch overrides it, in precedence order:

1. an explicit ``detect_platform(override=...)`` argument;
2. :func:`set_platform_override` (process-wide, e.g. from a launcher flag);
3. the ``REPRO_PLATFORM`` environment variable.

An override naming a known profile selects it; an unknown name clones the
fingerprinted profile under the new name, so roofline peaks stay sensible
while the database namespace is fully isolated.

Peaks come from Google Cloud's TPU documentation ("TPU v5e": 197 TFLOP/s
bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip; "TPU v4": 275
TFLOP/s bf16, 32 GiB HBM at 1,200 GB/s). Fingerprinting maps
``device_kind`` (what ``jax.devices()[0].device_kind`` reports) onto a
profile through :data:`DEVICE_KINDS`; a TPU kind missing from that table is
an error, never a guess.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Union

import jax

# Scoped VMEM one Pallas kernel is compiled under (the Mosaic default on
# v4/v5e; every kernel passes it explicitly). Tile working-set budgets and
# the legality pass check against this, not against the VMEM capacity.
SCOPED_VMEM_BYTES = 16 * 1024**2


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    name: str                      # platform key for the tuning database
    peak_flops_bf16: float         # FLOP/s per chip
    hbm_bandwidth: float           # bytes/s per chip
    ici_bandwidth: float           # bytes/s per link
    hbm_bytes: int                 # per-chip HBM capacity
    vmem_bytes: int                # per-core VMEM capacity
    mxu_dim: int = 128            # systolic array native tile edge
    lanes: int = 128               # VPU lane count (last-dim alignment)
    sublanes: int = 8              # second-to-last-dim alignment (fp32)


TPU_V5E = HardwareProfile(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,            # 1,600 Gbit/s per chip over 4 links
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
)

TPU_V4 = HardwareProfile(
    name="tpu-v4",
    peak_flops_bf16=275e12,
    hbm_bandwidth=1200e9,
    ici_bandwidth=100e9,
    hbm_bytes=32 * 1024**3,
    vmem_bytes=128 * 1024**2,
)

# The host CPU is a legitimate tuning platform (the paper's own Figure 1 is a
# CPU result): wall-clock evaluation happens here. Peaks are rough single-core
# numbers; they only matter for cost-model scoring, which on CPU we do not use.
CPU_HOST = HardwareProfile(
    name="cpu-host",
    peak_flops_bf16=100e9,
    hbm_bandwidth=20e9,
    ici_bandwidth=10e9,
    hbm_bytes=32 * 1024**3,
    vmem_bytes=32 * 1024**2,   # ~L2/L3 budget analogue for tile pruning
)

PROFILES = {p.name: p for p in (TPU_V5E, TPU_V4, CPU_HOST)}

# jax ``device_kind`` -> profile. The source of each row's peaks is in the
# module docstring.
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E, "TPU v4": TPU_V4}

# Process-wide explicit override (set_platform_override / REPRO_PLATFORM).
_override: Optional[str] = None


def set_platform_override(name: Union[str, HardwareProfile, None]) -> None:
    """Pin the platform key for this process (None restores auto-detection).

    This is the escape hatch for hosts where fingerprinting is wrong or too
    coarse: launchers expose it as ``--platform``. It takes effect for every
    subsequent :func:`detect_platform` call and for runtimes constructed
    without an explicit ``platform=``.
    """
    global _override
    _override = name.name if isinstance(name, HardwareProfile) else name


def platform_override() -> Optional[str]:
    """The active override name (explicit call wins over $REPRO_PLATFORM)."""
    return _override or os.environ.get("REPRO_PLATFORM") or None


def _fingerprint() -> HardwareProfile:
    """Map ``jax.devices()`` onto a known profile.

    On a TPU ``device_kind`` names the generation ("TPU v4", "TPU v5 lite");
    on a CPU host the platform is 'cpu'. Tuning records are stored under the
    detected key, so a database produced on one never shadows another's —
    that isolation is what makes shipping per-platform DBs safe. A device
    without a row in :data:`DEVICE_KINDS` raises: roofline shares and VMEM
    budgets computed from another chip's peaks would be wrong silently.
    """
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_HOST
    prof = DEVICE_KINDS.get(dev.device_kind)
    if prof is None:
        raise ValueError(
            f"no hardware profile for {dev.platform} device_kind "
            f"{dev.device_kind!r}; known kinds: {sorted(DEVICE_KINDS)}"
        )
    return prof


def detect_platform(override: Optional[str] = None) -> HardwareProfile:
    """The :class:`HardwareProfile` this process tunes and dispatches under.

    ``override`` (or the process override, see :func:`set_platform_override`)
    short-circuits fingerprinting: a known profile name selects it; an
    unknown name clones the fingerprinted profile under that name — the
    database namespace is isolated while roofline peaks stay sensible.
    """
    name = override or platform_override()
    if name:
        prof = PROFILES.get(name)
        if prof is None:
            prof = dataclasses.replace(_fingerprint(), name=name)
        return prof
    return _fingerprint()
