"""Variant evaluators + the correctness gate.

The paper's loop is: transform → compile → execute → compare-with-reference →
keep metrics. An :class:`Evaluator` implements 'compile → execute → metrics'
for one platform; :func:`correctness_gate` implements 'compare with
reference'. Two evaluators:

* :class:`WallClockEvaluator` — empirically times the jitted variant on this
  process's devices (the paper's own method; used on CPU for kernels and jnp
  paths).
* :class:`CostModelEvaluator` — for the TPU target we cannot execute on:
  lowers + compiles the variant for a (possibly fake-device) mesh and scores
  it by its dominant roofline term, derived from ``cost_analysis()`` plus
  collective bytes parsed out of the compiled HLO. This is the evaluator the
  sharding-layout tuning uses; it is also the §Roofline machinery.
"""
from __future__ import annotations

import dataclasses
import math
import re
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .platform import HardwareProfile, TPU_V5E, detect_platform

# ---------------------------------------------------------------------------
# Correctness gate
# ---------------------------------------------------------------------------

_TOL = {
    jnp.float32.dtype: (1e-5, 1e-5),
    jnp.bfloat16.dtype: (2e-2, 2e-2),
    jnp.float16.dtype: (1e-2, 1e-2),
}


def tolerance_for(dtype) -> Tuple[float, float]:
    return _TOL.get(jnp.dtype(dtype), (1e-5, 1e-5))


def correctness_gate(out, ref, rtol: Optional[float] = None, atol: Optional[float] = None) -> bool:
    """True iff `out` matches the reference pytree within dtype tolerance.

    Structure, shape, and NaN discipline: mismatched tree *structures* fail
    even when leaf counts happen to agree; a NaN in `out` where the
    reference is finite fails; NaNs in the same positions as reference NaNs
    pass (the reference defines them as expected). Tolerance is dtype-aware
    — the coarser of the two leaves' dtypes decides (a bf16 variant judged
    against an f32 reference gets bf16 tolerance), evaluated *before* the
    float32 upcast used for comparison. Zero-size leaves trivially pass.
    """
    if jax.tree_util.tree_structure(out) != jax.tree_util.tree_structure(ref):
        return False
    outs = jax.tree_util.tree_leaves(out)
    refs = jax.tree_util.tree_leaves(ref)
    for o, r in zip(outs, refs):
        if rtol is not None:
            rt, at = rtol, atol
        else:
            rt_o, at_o = tolerance_for(getattr(o, "dtype", np.float32))
            rt_r, at_r = tolerance_for(getattr(r, "dtype", np.float32))
            rt, at = max(rt_o, rt_r), max(at_o, at_r)
        o = np.asarray(o, dtype=np.float32)
        r = np.asarray(r, dtype=np.float32)
        if o.shape != r.shape:
            return False
        if not r.size:
            continue
        scale = max(1.0, float(np.max(np.abs(r[np.isfinite(r)]), initial=0.0)))
        if np.any(np.isnan(o) & ~np.isnan(r)):
            return False
        if not np.allclose(o, r, rtol=rt or 1e-5, atol=(at or 1e-5) * scale,
                           equal_nan=True):
            return False
    return True


# ---------------------------------------------------------------------------
# Measurements
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Measurement:
    objective: float             # seconds, lower is better; inf on failure
    ok: bool
    error: Optional[str] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Evaluator:
    name = "base"

    def evaluate(self, fn: Callable, args: Sequence[Any], reference=None) -> Measurement:
        raise NotImplementedError


class WallClockEvaluator(Evaluator):
    """Median-of-k wall time of the jitted variant (after compile + warmup).

    This is the paper's measurement, verbatim: each variant is compiled,
    executed, timed, and its output compared to the reference output. A
    variant that fails to compile or run, or fails the correctness gate,
    scores ``inf`` and the search moves on — unless ``strict``, where it
    raises: for checks in which any failing variant is a fault to report.
    """

    name = "wallclock"

    def __init__(self, repeats: int = 5, warmup: int = 2, rtol=None, atol=None,
                 strict: bool = False):
        self.repeats = repeats
        self.warmup = warmup
        self.rtol = rtol
        self.atol = atol
        self.strict = strict

    def evaluate(self, fn: Callable, args: Sequence[Any], reference=None) -> Measurement:
        try:
            jfn = jax.jit(fn)
            out = jfn(*args)
            jax.block_until_ready(out)
        except Exception as e:  # invalid variant (bad tile, OOM, ...) — prune
            if self.strict:
                raise
            return Measurement(math.inf, False, error=f"{type(e).__name__}: {e}")

        if reference is not None and not correctness_gate(out, reference, self.rtol, self.atol):
            if self.strict:
                raise AssertionError(f"{fn}: correctness gate failed")
            return Measurement(math.inf, False, error="correctness gate failed")

        for _ in range(self.warmup):
            jax.block_until_ready(jfn(*args))
        times = []
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*args))
            times.append(time.perf_counter() - t0)
        times.sort()
        med = times[len(times) // 2]
        return Measurement(med, True, meta={"times": times, "best": times[0]})


# ---------------------------------------------------------------------------
# HLO analysis: flops / bytes / collective bytes  (shared with §Roofline)
# ---------------------------------------------------------------------------

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\b",
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s32|u32|s16|u16|s8|u8|pred|s64|u64)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*")
_WHILE_RE = re.compile(
    r"\bwhile\(.*?\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)"
)
_CALL_RE = re.compile(r"\b(?:call|conditional)\(.*?\).*?to_apply=%?([\w.\-]+)")
_CONST_RE = re.compile(r"%?([\w.\-]+)\s*=\s*s(?:32|64)\[\]\s*constant\((\d+)\)")
_CMP_RE = re.compile(
    r"compare\(\s*%?([\w.\-]+),\s*%?([\w.\-]+)\s*\),\s*direction=(LT|LE|GT|GE)"
)


def _shape_bytes(shape_str: str) -> int:
    """Sum of tensor bytes in an HLO result-shape string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str) -> Dict[str, list]:
    """Map computation name -> list of body lines. Entry stored as '__entry__'."""
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.rstrip()
        if stripped.endswith("{") and ("->" in stripped or stripped.startswith("ENTRY")):
            m = _COMP_HEADER_RE.match(stripped)
            if m:
                name = m.group(1)
                if stripped.startswith("ENTRY"):
                    comps["__entry__"] = []
                    comps[name] = comps["__entry__"]
                    cur = name
                else:
                    comps[name] = []
                    cur = name
                continue
        if cur is not None:
            if stripped == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _trip_count(cond_lines: list) -> int:
    """Extract the loop trip count from a while-condition computation."""
    consts = {}
    for line in cond_lines:
        m = _CONST_RE.search(line)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for line in cond_lines:
        m = _CMP_RE.search(line)
        if m:
            a, b, d = m.groups()
            c = consts.get(b, consts.get(a))
            if c is not None:
                return c + 1 if d in ("LE", "GE") else c
    # fallback: largest plausible integer constant
    vals = [v for v in consts.values() if 1 <= v <= 10_000_000]
    return max(vals) if vals else 1


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Trip-count-aware per-kind byte totals of every collective.

    XLA cost analysis visits while-loop bodies ONCE, which silently drops a
    ~num_layers× factor for scanned models. This walks the computation call
    graph from ENTRY, multiplying collective bytes inside each while body by
    its parsed trip count (nested loops compose multiplicatively). Bytes are
    result-shape bytes; async -start ops count the largest tuple element to
    avoid double-counting operand aliases.
    """
    comps = _split_computations(hlo_text)

    raw: Dict[str, Dict[str, int]] = {}
    calls: Dict[str, list] = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        kinds: Dict[str, int] = {}
        sub = []
        for line in lines:
            mc = _COLLECTIVE_RE.match(line)
            if mc:
                shape_str, kind, is_start = mc.group(1), mc.group(2), mc.group(3)
                b = _shape_bytes(shape_str)
                if is_start and shape_str.startswith("("):
                    elems = [_shape_bytes(s) for s in re.findall(r"\w+\[[\d,]*\]", shape_str)]
                    b = max(elems) if elems else b
                kinds[kind] = kinds.get(kind, 0) + b
                continue
            mw = _WHILE_RE.search(line)
            if mw:
                cond, body = mw.groups()
                sub.append((body, _trip_count(comps.get(cond, []))))
                continue
            for mcall in _CALL_RE.finditer(line):
                sub.append((mcall.group(1), 1))
        raw[name] = kinds
        calls[name] = sub

    memo: Dict[str, Dict[str, int]] = {}

    def total(name: str, depth=0) -> Dict[str, int]:
        if name in memo or depth > 64:
            return memo.get(name, {})
        out = dict(raw.get(name, {}))
        for child, trips in calls.get(name, []):
            for k, v in total(child, depth + 1).items():
                out[k] = out.get(k, 0) + v * trips
        memo[name] = out
        return out

    # entry name: the computation aliased to __entry__
    entry_kinds: Dict[str, int] = {}
    for name in comps:
        if name != "__entry__" and comps[name] is comps["__entry__"]:
            entry_kinds = total(name)
            break

    flat_count = sum(
        1
        for name, lines in comps.items()
        if name != "__entry__"
        for line in lines
        if _COLLECTIVE_RE.match(line)
    )

    return {
        "bytes_by_kind": entry_kinds,
        "total_bytes": sum(entry_kinds.values()),
        "count": flat_count,
    }


@dataclasses.dataclass
class RooflineTerms:
    """The three §Roofline terms, in seconds, for one compiled step."""

    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    hlo_bytes: float
    collective_bytes: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Lower-bound step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self) | {
            "dominant": self.dominant,
            "step_time_s": self.step_time_s,
        }


def roofline_from_compiled(
    compiled,
    profile: Optional[HardwareProfile] = None,
    chips: Optional[int] = None,
    hlo_text: Optional[str] = None,
) -> RooflineTerms:
    """Derive the three roofline terms from a compiled executable.

    ``profile`` defaults to the detected platform's peaks; a cost model of
    another chip (a dry-run on host devices) passes that chip's profile.

    cost_analysis() reports whole-program FLOPs/bytes (already per the SPMD
    module, i.e. per device). Collective bytes come from the HLO text. The
    collective term divides by links-per-chip≈1 conservative model: bytes on
    the busiest kind / link bandwidth.
    """
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0)))
    profile = profile or detect_platform()
    n = chips or len(jax.devices())
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_stats(text)
    coll_bytes = float(coll["total_bytes"])
    return RooflineTerms(
        compute_s=flops / profile.peak_flops_bf16,
        memory_s=bytes_accessed / profile.hbm_bandwidth,
        collective_s=coll_bytes / profile.ici_bandwidth,
        flops=flops,
        hlo_bytes=bytes_accessed,
        collective_bytes=coll_bytes,
        chips=n,
    )


class CostModelEvaluator(Evaluator):
    """Score a variant by lowering+compiling it and taking the roofline bound.

    `fn` must be a zero-arg thunk returning a `jax.stages.Compiled` (the
    tuner wires mesh/shardings/ShapeDtypeStructs into the thunk). Objective =
    max(compute, memory, collective) seconds — the overlap-optimistic step
    bound; minimizing it is minimizing the dominant term, which is the §Perf
    loop's instruction.
    """

    name = "costmodel"

    def __init__(self, profile: HardwareProfile = TPU_V5E, chips: Optional[int] = None):
        self.profile = profile
        self.chips = chips

    def evaluate(self, fn: Callable, args: Sequence[Any] = (), reference=None) -> Measurement:
        try:
            compiled = fn(*args)
            terms = roofline_from_compiled(compiled, self.profile, self.chips)
        except Exception as e:
            return Measurement(math.inf, False, error=f"{type(e).__name__}: {e}")
        return Measurement(
            terms.step_time_s,
            True,
            meta={"roofline": terms.to_json()},
        )
