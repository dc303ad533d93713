"""Abstract grid/BlockSpec models — the statically-checkable half of a kernel.

Every Pallas tunable in this repo is a *family* of kernels indexed by a
config: the config picks block shapes, the kernel derives a grid, index
maps, and ``dimension_semantics`` from them. Whether a config is *legal* on
a platform is a function of exactly those derived objects — not of the
kernel body — so legality can be decided without compiling or running
anything (Petrovič et al. 2019 filter infeasible configs the same way,
before measurement).

A kernel module registers a **grid builder**: a pure function
``build(config, shapes=None) -> GridModel | tuple[GridModel, ...] | None``
that mirrors the exact clamp/pad/grid arithmetic of the kernel entry point
(``None`` means the kernel itself would reject the shapes, e.g. flash
attention's divisibility asserts). Multi-pass kernels (xent backward's
lse+dl passes, flash backward's three passes) return one model per
``pallas_call``. The checks here then decide, per config × platform:

* **write-write races** — an output ref whose index map is *invariant*
  along a grid axis declared "parallel": two grid points would write the
  same block concurrently. This is the exact hazard class that forces
  ``rmsnorm_bwd``'s dw accumulator and ``ssm_scan``'s chunk carry onto
  sequential ("arbitrary") axes. Platform-independent → always an error.
* **index-map out-of-bounds** — a block index at any grid corner that maps
  outside the padded array dims. Platform-independent → always an error.
* **TPU tiling alignment** — when a block actually *tiles* an axis
  (block < padded dim), the last block dim must be a multiple of the lane
  count (128) and the second-to-last a multiple of the dtype sublane count
  (8 for f32, 16 for bf16). A block spanning the full dim is exempt (Mosaic
  pads whole arrays). Platform-dependent → these are *pruned configs*, not
  bugs.
* **VMEM** — a model that states its scoped-VMEM need at these shapes
  (``GridModel.vmem_need``, a function of the element size, for kernels
  whose need depends on shapes a config does not name) must fit the
  per-kernel limit at the dtype the kernel runs at. TPU-only → a pruned
  config.

``config_verdict`` / ``space_illegal`` / ``space_report`` are the low-level
API; ``ParamSpace.legal_configs(platform)`` (see ``params.py``) and the
``repro.analysis`` pass-2 checker are the two consumers. This module must
not import ``params`` — spaces link back to kernels via the
``_grid_kernels`` attribute that :func:`register_grid_model` attaches.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from .platform import PROFILES, SCOPED_VMEM_BYTES, HardwareProfile, detect_platform

# ---------------------------------------------------------------------------
# Model structures
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RefModel:
    """One BlockSpec'd ref of a pallas_call: block shape + index map + dims.

    ``dims`` are the *padded* array dims the index map addresses (in
    block-index units: ``index_map(*grid_coord)[d]`` selects block
    ``idx[d]`` of size ``block[d]`` along an axis of extent ``dims[d]``).
    ``role`` distinguishes outputs (race-checked) from inputs. ``dtype``
    overrides the model dtype for refs that differ (e.g. int32 labels).
    """

    name: str
    block: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]
    dims: Tuple[int, ...]
    role: str = "in"                 # "in" | "out"
    dtype: Optional[str] = None

    def __post_init__(self):
        if len(self.block) != len(self.dims):
            raise ValueError(
                f"ref {self.name!r}: block rank {len(self.block)} != "
                f"dims rank {len(self.dims)}"
            )


@dataclasses.dataclass(frozen=True)
class GridModel:
    """The abstract (grid, semantics, refs) triple of one pallas_call."""

    kernel: str
    grid: Tuple[int, ...]
    semantics: Tuple[str, ...]       # "parallel" | "arbitrary" per axis
    refs: Tuple[RefModel, ...]
    # Scoped VMEM need in bytes given the element size, where shape-bound.
    vmem_need: Optional[Callable[[int], int]] = None

    def __post_init__(self):
        if len(self.grid) != len(self.semantics):
            raise ValueError(
                f"{self.kernel}: grid rank {len(self.grid)} != "
                f"semantics rank {len(self.semantics)}"
            )

    def signature(self) -> Tuple:
        """Hashable identity of the *realized* kernel: configs with equal
        signatures compile to indistinguishable kernels at these shapes
        (the redundancy relation ``space_report`` counts)."""
        return (
            self.grid,
            tuple((r.name, r.block, r.dims) for r in self.refs),
        )


# ---------------------------------------------------------------------------
# Builder registry
# ---------------------------------------------------------------------------

BuildFn = Callable[..., Union[GridModel, Tuple[GridModel, ...], None]]


@dataclasses.dataclass(frozen=True)
class GridBuilder:
    kernel: str
    build: BuildFn
    space: Any = None                # the ParamSpace the kernel tunes over
    dtype: str = "float32"           # dtype the nominal shapes run at


_GRID_MODELS: Dict[str, GridBuilder] = {}


def register_grid_model(
    kernel: str,
    build: BuildFn,
    space: Any = None,
    dtype: str = "float32",
) -> None:
    """Declare the abstract grid model for a kernel tunable.

    Also links the kernel back onto ``space`` (via ``space._grid_kernels``)
    so ``ParamSpace.legal_configs`` can check a shared space against *every*
    kernel that tunes over it (e.g. RMSNORM_SPACE serves both ``rmsnorm``
    and ``rmsnorm_bwd`` — a config is legal iff legal under both).
    """
    _GRID_MODELS[kernel] = GridBuilder(kernel, build, space, dtype)
    if space is not None:
        kernels = getattr(space, "_grid_kernels", None)
        if kernels is None:
            kernels = []
            space._grid_kernels = kernels
        if kernel not in kernels:
            kernels.append(kernel)


def registered_models() -> Dict[str, GridBuilder]:
    return dict(_GRID_MODELS)


def build_models(
    kernel: str,
    config: Dict[str, Any],
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
) -> Optional[Tuple[GridModel, ...]]:
    """All pallas_call models the kernel realizes for this config (None if
    the kernel would reject the shapes outright)."""
    builder = _GRID_MODELS.get(kernel)
    if builder is None:
        return None
    try:
        out = builder.build(config, shapes)
    except Exception:
        return None
    if out is None:
        return None
    return out if isinstance(out, tuple) else (out,)


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "float64": 8,
    "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
    "int64": 8, "int32": 4, "int16": 2, "int8": 1, "uint8": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "bool": 1,
}


def sublanes_for(profile: HardwareProfile, dtype: str) -> int:
    """Second-to-last-dim alignment for ``dtype`` on ``profile``.

    ``profile.sublanes`` is the fp32 (4-byte) figure; narrower dtypes pack
    more rows per physical sublane tile: 8×128 f32 → 16×128 bf16 → 32×128
    int8.
    """
    bytes_ = _DTYPE_BYTES.get(str(dtype), 4)
    return max(1, (profile.sublanes * 4) // bytes_)


def _grid_corners(grid: Tuple[int, ...]) -> List[Tuple[int, ...]]:
    return list(itertools.product(*({0, g - 1} for g in grid)))


def check_races(model: GridModel) -> Optional[str]:
    """Flag output refs invariant along a non-sequential grid axis.

    For each "parallel" axis, probe the index map at consecutive coordinates
    along that axis (holding others at a corner): if two *distinct* grid
    points map an output to the same block index, they write the same memory
    concurrently — a write-write race. Index-map coincidence along a
    parallel axis is the race, so this probe has no false positives;
    "arbitrary" axes execute sequentially and are exempt (that is exactly
    why rmsnorm_bwd's dw accumulator and ssm_scan's chunk carry declare
    their reduction axes "arbitrary").
    """
    n = len(model.grid)
    bases = [(0,) * n, tuple(g - 1 for g in model.grid)]
    for ref in model.refs:
        if ref.role != "out":
            continue
        for axis in range(n):
            if model.semantics[axis] != "parallel" or model.grid[axis] < 2:
                continue
            for base in bases:
                for j in range(min(model.grid[axis], 8) - 1):
                    a = list(base)
                    b = list(base)
                    a[axis], b[axis] = j, j + 1
                    ia = tuple(ref.index_map(*a))
                    ib = tuple(ref.index_map(*b))
                    if ia == ib:
                        return (
                            f"{model.kernel}: output ref {ref.name!r} is "
                            f"invariant along parallel grid axis {axis} "
                            f"(coords {tuple(a)} and {tuple(b)} both write "
                            f"block {ia}) — write-write race; declare the "
                            f"axis 'arbitrary' or index the output by it"
                        )
    return None


def check_oob(model: GridModel) -> Optional[str]:
    """Flag index maps that address blocks outside the padded array dims."""
    for ref in model.refs:
        for coord in _grid_corners(model.grid):
            idx = tuple(ref.index_map(*coord))
            if len(idx) != len(ref.block):
                return (
                    f"{model.kernel}: ref {ref.name!r} index map returns "
                    f"rank {len(idx)} for block rank {len(ref.block)}"
                )
            for d, (i, blk, dim) in enumerate(zip(idx, ref.block, ref.dims)):
                if i < 0 or (i + 1) * blk > dim:
                    return (
                        f"{model.kernel}: ref {ref.name!r} block index "
                        f"{idx} at grid coord {coord} spans "
                        f"[{i * blk}, {(i + 1) * blk}) outside padded dim "
                        f"{dim} on axis {d}"
                    )
    return None


def check_alignment(
    model: GridModel, profile: HardwareProfile, dtype: str = "float32"
) -> Optional[str]:
    """TPU lane/sublane tiling alignment (skipped off-TPU).

    Only axes a block actually *tiles* (block extent < padded dim) need
    alignment — a block spanning the full dim is laid out by Mosaic's
    whole-array padding and is always representable. For tiled axes, the
    last block dim must divide by the lane count and the second-to-last by
    the per-dtype sublane count — no exemption for an extent of 1: Mosaic
    refuses a (1, block_q) block that tiles its axis.
    """
    if not profile.name.startswith("tpu"):
        return None
    for ref in model.refs:
        dt = ref.dtype or dtype
        sub = sublanes_for(profile, dt)
        blk, dims = ref.block, ref.dims
        if len(blk) >= 1 and blk[-1] < dims[-1] and blk[-1] % profile.lanes:
            return (
                f"{model.kernel}: ref {ref.name!r} last block dim "
                f"{blk[-1]} tiles axis of {dims[-1]} but is not a multiple "
                f"of {profile.lanes} lanes ({profile.name})"
            )
        if len(blk) >= 2 and blk[-2] < dims[-2] and blk[-2] % sub:
            return (
                f"{model.kernel}: ref {ref.name!r} second-to-last block dim "
                f"{blk[-2]} tiles axis of {dims[-2]} but is not a multiple "
                f"of {sub} sublanes for {dt} ({profile.name})"
            )
    return None


def check_vmem(
    model: GridModel, profile: HardwareProfile, dtype: str = "float32"
) -> Optional[str]:
    """A stated scoped-VMEM need at ``dtype`` over the per-kernel limit
    (skipped off-TPU and for models that state none)."""
    if not profile.name.startswith("tpu") or model.vmem_need is None:
        return None
    need = model.vmem_need(_DTYPE_BYTES.get(str(dtype), 4))
    if need > SCOPED_VMEM_BYTES:
        return (
            f"{model.kernel}: needs {need} B of scoped VMEM at {dtype}, "
            f"over the {SCOPED_VMEM_BYTES} B limit ({profile.name})"
        )
    return None


def check_model(
    model: GridModel, profile: HardwareProfile, dtype: str = "float32"
) -> Optional[Tuple[str, str]]:
    """(category, reason) for the first failed check, in severity order:
    races and OOB are kernel bugs regardless of platform; alignment and
    VMEM are platform-specific infeasibilities (pruned configs, not bugs)."""
    reason = check_races(model)
    if reason:
        return ("race", reason)
    reason = check_oob(model)
    if reason:
        return ("oob", reason)
    reason = check_alignment(model, profile, dtype)
    if reason:
        return ("align", reason)
    reason = check_vmem(model, profile, dtype)
    if reason:
        return ("vmem", reason)
    return None


# ---------------------------------------------------------------------------
# Space-level verdicts
# ---------------------------------------------------------------------------


def resolve_profile(
    platform: Union[str, HardwareProfile, None]
) -> HardwareProfile:
    if platform is None:
        return detect_platform()
    if isinstance(platform, HardwareProfile):
        return platform
    return PROFILES.get(platform) or detect_platform(platform)


def config_verdict(
    kernel: str,
    config: Dict[str, Any],
    platform: Union[str, HardwareProfile, None] = None,
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
    dtype: Optional[str] = None,
) -> Optional[Tuple[str, str]]:
    """None if the config is legal for ``kernel`` on ``platform``, else the
    first (category, reason): 'build' | 'race' | 'oob' | 'align' | 'vmem'.
    ``dtype`` is the element type the kernel runs at (default: the one its
    nominal shapes were registered with)."""
    builder = _GRID_MODELS.get(kernel)
    if builder is None:
        return None                  # no model declared → nothing to check
    profile = resolve_profile(platform)
    models = build_models(kernel, config, shapes)
    if models is None:
        return (
            "build",
            f"{kernel}: kernel rejects config {config} at these shapes",
        )
    for m in models:
        verdict = check_model(m, profile, dtype or builder.dtype)
        if verdict:
            return verdict
    return None


def space_illegal(
    kernel: str,
    platform: Union[str, HardwareProfile, None] = None,
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
    dtype: Optional[str] = None,
) -> Dict[str, Tuple[str, str]]:
    """config_key → (category, reason) over the kernel's whole space."""
    builder = _GRID_MODELS.get(kernel)
    if builder is None or builder.space is None:
        return {}
    profile = resolve_profile(platform)
    out: Dict[str, Tuple[str, str]] = {}
    for cfg in builder.space.enumerate():
        verdict = config_verdict(kernel, cfg, profile, shapes, dtype)
        if verdict:
            out[builder.space.config_key(cfg)] = verdict
    return out


def space_report(
    kernel: str,
    platform: Union[str, HardwareProfile, None] = None,
    shapes: Optional[Sequence[Tuple[int, ...]]] = None,
) -> Dict[str, Any]:
    """Counts the pass-2 checker and ``campaign status`` report per kernel:
    total / legal / per-category illegal / redundant (configs whose realized
    models are signature-identical to a surviving config at these shapes)."""
    builder = _GRID_MODELS.get(kernel)
    profile = resolve_profile(platform)
    report: Dict[str, Any] = {
        "kernel": kernel,
        "platform": profile.name,
        "total": 0,
        "legal": 0,
        "illegal": 0,
        "by_category": {},
        "redundant": 0,
        "reasons": [],
    }
    if builder is None or builder.space is None:
        return report
    signatures = set()
    for cfg in builder.space.enumerate():
        report["total"] += 1
        verdict = config_verdict(kernel, cfg, profile, shapes)
        if verdict:
            cat, reason = verdict
            report["illegal"] += 1
            report["by_category"][cat] = report["by_category"].get(cat, 0) + 1
            if len(report["reasons"]) < 8:
                report["reasons"].append(reason)
            continue
        report["legal"] += 1
        models = build_models(kernel, cfg, shapes)
        sig = tuple(m.signature() for m in models) if models else None
        if sig is not None:
            if sig in signatures:
                report["redundant"] += 1
            signatures.add(sig)
    return report
