"""The dispatch runtime: scoped tuned contexts + pluggable resolution.

This module is the deployment half of the annotation story. ``@tunable``
declares *what* can specialize (:mod:`repro.core.annotate`); the runtime
decides, per call site, *which* implementation and config actually runs —
and makes that decision scoped, swappable, and observable:

* **Scoped contexts** — a :class:`TunedRuntime` pins a tuning database, a
  mode (``"kernel"`` | ``"reference"`` | ``"auto"``), and a resolution
  policy for everything executed under ``with`` it::

      with repro.runtime(db=serve_db, mode="kernel") as rt:
          engine.serve()            # every kernel dispatch uses serve_db
      print(rt.telemetry.report())

  Runtimes nest (inner wins; unspecified fields inherit from the enclosing
  runtime at construction) and live on a context-local stack, so serving,
  campaign evaluation, and tests each pin their own db/mode without
  cross-talk — including across threads: a fresh thread starts at the
  process-default runtime, never at another thread's scope.

* **Pluggable resolution** — the exact→cover→heuristic chain that used to
  be hard-coded in ``tune_or_lookup`` is a pipeline of
  :class:`ResolutionPolicy` objects. The default is
  ``(ExactHit, TuneNow, CoverSet, Heuristic, Reference)``; pass
  ``policy=(ExactHit(), Reference())`` for a "run only measured configs,
  else fall back to reference" deployment, or insert a custom policy (an
  object with ``name`` and ``resolve(request)``) anywhere in the chain.

* **Telemetry** — every dispatch records which tier served which
  kernel×bucket (:class:`Telemetry`; tiers ``override | exact | tune |
  cover | heuristic | reference`` plus cache hits), tagged with the
  dispatch *phase*: ``fwd`` for forward sites, ``bwd`` for gradient sites
  resolved inside a backward dispatch plan. This is the paper's
  sustained-performance accounting: after a warmed serving run,
  ``telemetry.snapshot()`` shows exactly how much traffic ran on tuned
  records vs cover-set entries vs the vendor-baseline heuristic — and
  after a tuned train step, whether the *gradient* sites hit too.

* **Tuned backward plane** — in kernel mode, a tunable whose dispatch spec
  declares ``vjp="dispatch"`` + a ``bwd`` plan differentiates through
  *dispatch sites*: the bound variant is wrapped in a ``jax.custom_vjp``
  whose backward calls ``spec.bwd(ct, *canonical_args, **kwargs)``, and
  that plan routes each gradient through ``dispatch(...)`` again (matmul's
  dL/dx and dL/dw are transposed-operand matmul dispatches; flash
  attention / rmsnorm / softmax-xent resolve their own ``*_bwd``
  tunables). Every backward call therefore gets its own database key,
  policy resolution, and ``bwd``-tagged telemetry row — a campaign
  pre-tunes gradients exactly like forwards, and a tuned train step stops
  paying reference-speed backward recomputes. ``runtime(...,
  bwd_dispatch=False)`` restores the old reference-VJP recompute (the
  fwd-only-tuned baseline the benchmarks compare against).

* **Resolution cache** — per-runtime ``{db key: Resolution}``; repeated jit
  traces of the same shape bucket stop re-hitting the database (see
  ``benchmarks/dispatch_overhead.py`` for the cold/warm gap). Bounded:
  LRU-evicted past ``cache_capacity`` entries with an optional
  ``cache_ttl`` (evictions show up in telemetry), so very-long-lived
  servers cannot grow it without limit. ``clear_cache()`` after mutating
  the database mid-flight.

* **Platform + sharding aware keys** — keys are namespaced under the
  *detected* platform (``repro.core.platform.detect_platform``; override
  via ``REPRO_PLATFORM`` / ``set_platform_override`` / a per-runtime
  ``platform=``), and inside an active ``mesh_context`` batch-leading args
  are keyed on their per-device *local shard* shapes — the shapes a
  sharding-aware campaign (``plan_training_jobs``) tuned.

Deployment entry points are generated from the registry
(:func:`entry_point` / :func:`dispatch`), so adding a kernel is one
``@tunable(..., dispatch=DispatchSpec(...))`` decorator with zero edits
anywhere else. The old global-mode API (``ops.set_kernel_mode`` /
``ops.kernels_enabled`` / ``ops.<kernel>``) completed its deprecation cycle
and is gone — ``kernels/ops.py`` survives only as the migration guide.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import functools
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

from .annotate import DispatchSpec, Tunable, get_tunable
from .database import TuningDatabase, default_db
from .params import Config
from .platform import detect_platform, platform_override

# Import order is safe: repro.obs's collector/tracing layers are
# stdlib-only (obs.drift, which does import core modules, is lazy), and so
# is the fault-injection harness (one bool check when no plan is active).
from ..obs.collect import current_collector as _obs_collector
from ..obs.trace import span as _obs_span
from ..testing.faults import fault_point as _fault_point

_MODES = ("kernel", "reference", "auto")


class DispatchFault(RuntimeError):
    """A guarded dispatch's own fault signal (e.g. a failed non-finite
    probe) — raised and caught inside the guard, quarantining the bucket."""

_platform_name: Optional[str] = None


def _platform() -> str:
    """Effective platform key: the override escape hatch if set, else the
    memoized fingerprint (the backend cannot change within a process, and
    ``jax.devices()`` per dispatch would dominate warm resolution)."""
    ov = platform_override()
    if ov:
        return ov
    global _platform_name
    if _platform_name is None:
        _platform_name = detect_platform().name
    return _platform_name

_backend_tpu: Optional[bool] = None


def _on_tpu() -> bool:
    """Whether JAX's default backend is a TPU (memoized like the platform
    key: the backend cannot change within a process)."""
    global _backend_tpu
    if _backend_tpu is None:
        import jax

        _backend_tpu = jax.default_backend() == "tpu"
    return _backend_tpu

# Resolution tiers, in the order the default pipeline consults them.
# "bgtune" is the BackgroundTune tier (repro.core.bgtune): a miss served by
# the heuristic config while an async tuner works the bucket toward "exact".
TIERS = ("override", "exact", "tune", "bgtune", "cover", "heuristic", "reference")

# Dispatch phases: forward sites, gradient sites (dispatches made while a
# backward dispatch plan is executing), and optimizer-update sites (the
# trainer tags its parameter update "opt" — no kernel dispatches live there
# today, so the tag exists for phase-resolved *timing*, not tier counts).
# Ambient, not threaded through call signatures: a bwd plan is ordinary
# model-layer code calling dispatch().
PHASES = ("fwd", "bwd", "opt")

_phase_ctx: "contextvars.ContextVar[str]" = contextvars.ContextVar(
    "repro_dispatch_phase", default="fwd"
)


@contextlib.contextmanager
def dispatch_phase(phase: str):
    """Tag every dispatch in this scope with `phase` ('fwd'|'bwd'|'opt').

    The runtime enters ``dispatch_phase("bwd")`` around a dispatch spec's
    backward plan, so telemetry separates gradient-site resolutions from
    forward ones — the accounting behind "the train step's backward FLOPs
    run on tuned records too".
    """
    if phase not in PHASES:
        raise ValueError(f"phase {phase!r} not in {PHASES}")
    tok = _phase_ctx.set(phase)
    try:
        yield
    finally:
        _phase_ctx.reset(tok)


def current_phase() -> str:
    return _phase_ctx.get()


# ---------------------------------------------------------------------------
# Resolution requests / results / policies
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResolutionRequest:
    """Everything a policy may consult to resolve one kernel×bucket."""

    tunable: Tunable
    args: tuple                      # canonicalized positional args
    key: str                         # full database key (platform+bucket+dtype)
    key_extra: str
    db: TuningDatabase
    platform: str
    runtime: "TunedRuntime"
    # Per-call effective tuning permissions (runtime defaults, possibly
    # overridden by the resolve() caller — e.g. warmup(allow_tune=True)
    # must not mutate a runtime other threads are dispatching through).
    allow_tune: bool = False
    tune_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Resolution:
    """Outcome of resolving one kernel×bucket.

    ``config=None`` means "execute the reference implementation" (the
    terminal :class:`Reference` tier); otherwise the config is bound as a
    kernel variant.

    ``key`` is the database key the resolution answered (``None`` only for
    tiers that never compute one — reference mode, ``config=`` overrides).
    ``cache=False`` keeps the resolution out of the runtime's resolution
    cache, so the next resolve re-runs the pipeline — how the BackgroundTune
    tier stays hot-swappable (every resolve re-consults ExactHit until the
    promoted record lands) and how quarantined buckets re-probe. ``probe``
    marks a resolution whose first guarded execution should be validated
    (exception guard + optional non-finite check) before the health book
    clears it.
    """

    config: Optional[Config]
    tier: str
    key: Optional[str] = None
    cache: bool = True
    probe: bool = False


class ResolutionPolicy:
    """One tier of the resolution pipeline.

    ``resolve`` returns a :class:`Resolution` to stop the chain, or ``None``
    to pass the request to the next policy. ``name`` is the telemetry tier
    label.
    """

    name = "policy"

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        raise NotImplementedError

    def __repr__(self) -> str:
        return f"<{type(self).__name__}>"


class ExactHit(ResolutionPolicy):
    """A stored record for this exact key: zero-cost specialization."""

    name = "exact"

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        rec = req.db.lookup(req.key)
        if rec is not None and req.tunable.space.is_valid(rec.config):
            return Resolution(dict(rec.config), self.name)
        return None


class TuneNow(ResolutionPolicy):
    """Tune on the spot (writes the record) — only if the runtime allows it."""

    name = "tune"

    def __init__(self, **tune_kwargs: Any):
        self.tune_kwargs = tune_kwargs

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        if not req.allow_tune:
            return None
        from .tuner import autotune  # late: tuner imports annotate/database

        kwargs = dict(self.tune_kwargs)
        kwargs.update(req.tune_kwargs)
        res = autotune(
            req.tunable, req.args, db=req.db, key_extra=req.key_extra, **kwargs
        )
        return Resolution(dict(res.best_config), self.name)


class CoverSet(ResolutionPolicy):
    """Nearest 'few fit most' cover entry: measured config, unseen bucket."""

    name = "cover"

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        # Rank neighbours by the shapes the key was computed from (already
        # bucketed and — under a sharded mesh — localized to the per-device
        # shard), so cover transfer is consistent with exact-hit keying.
        from .database import split_key

        shapes = split_key(req.key)[2]
        for entry in req.db.lookup_cover(req.tunable.name, req.platform, shapes):
            cfg = entry.get("config")
            if cfg is not None and req.tunable.space.is_valid(cfg):
                return Resolution(dict(cfg), self.name)
        return None


class Heuristic(ResolutionPolicy):
    """The shape heuristic default — the 'vendor baseline'. Always succeeds."""

    name = "heuristic"

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        return Resolution(req.tunable.default_config(*req.args), self.name)


class Reference(ResolutionPolicy):
    """Terminal tier: run the reference implementation, not a kernel variant.

    In the default pipeline :class:`Heuristic` always resolves first, so
    this only fires in trimmed pipelines such as ``(ExactHit(),
    Reference())`` — "tuned configs or bust".
    """

    name = "reference"

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        return Resolution(None, self.name)


def default_policy() -> Tuple[ResolutionPolicy, ...]:
    return (ExactHit(), TuneNow(), CoverSet(), Heuristic(), Reference())


# ---------------------------------------------------------------------------
# Health book (guarded execution)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Health:
    level: str              # "record" (this db record) | "kernel" (any variant)
    fails: int = 0
    until: float = 0.0      # monotonic stamp the quarantine lapses (probe due)
    backoff: float = 0.0    # current re-probe interval


class HealthBook:
    """Per-runtime quarantine ledger for faulting kernel executions.

    Keyed like the resolution cache (full db keys). Two quarantine levels:
    ``"record"`` — the stored/measured config for this bucket faulted, but
    the kernel itself may be fine (resolution skips the db-record tiers and
    serves the heuristic); ``"kernel"`` — the heuristic config faulted too,
    so no variant is trusted for this bucket (resolution goes straight to
    reference). Entries re-probe after an exponential backoff (capped), so
    a transient fault — or a record fixed by a re-tune — heals without a
    restart; a persistent fault re-quarantines with a longer interval.
    Bounded: past ``capacity`` entries the oldest-lapsing are dropped (a
    dropped entry just means one extra probe).
    """

    def __init__(self, base_s: float = 5.0, max_s: float = 300.0,
                 capacity: int = 1024):
        self.base_s = float(base_s)
        self.max_s = float(max_s)
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._entries: Dict[str, _Health] = {}

    def consult(self, key: str) -> Optional[Tuple[str, str]]:
        """None when healthy; ("probe"|"blocked", level) when quarantined."""
        with self._lock:
            h = self._entries.get(key)
            if h is None:
                return None
            state = "probe" if time.monotonic() >= h.until else "blocked"
            return state, h.level

    def quarantine(self, key: str, level: str) -> _Health:
        with self._lock:
            h = self._entries.get(key)
            if h is None:
                h = self._entries[key] = _Health(level=level)
            elif level == "kernel":
                h.level = "kernel"      # escalate; never de-escalate here
            h.fails += 1
            h.backoff = min(self.max_s, self.base_s * (2 ** (h.fails - 1)))
            h.until = time.monotonic() + h.backoff
            while len(self._entries) > self.capacity:
                victim = min(self._entries, key=lambda k: self._entries[k].until)
                del self._entries[victim]
            return h

    def record_ok(self, key: str) -> None:
        with self._lock:
            self._entries.pop(key, None)

    def __len__(self) -> int:
        return len(self._entries)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        now = time.monotonic()
        with self._lock:
            return {
                k: {"level": h.level, "fails": h.fails,
                    "backoff_s": h.backoff, "probe_in_s": max(0.0, h.until - now)}
                for k, h in self._entries.items()
            }


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------


class Telemetry:
    """Per-runtime counters: which tier served each kernel×bucket.

    ``tiers``    — total dispatches per tier.
    ``by_key``   — ``{db key: {tier: count}}`` (reference-mode and explicit
                   ``config=`` dispatches, which never compute a bucket key,
                   are recorded under ``"<kernel>|*"``).
    ``phases``   — ``{phase: {tier: count}}`` for ``fwd`` vs ``bwd``
                   dispatch sites (the tuned-backward-plane accounting: a
                   fully pre-tuned train step shows ``exact``-only counts
                   under BOTH phases).
    ``by_key_phase`` — ``{phase: {db key: {tier: count}}}``: the per-site
                   breakdown split by phase, so a gate can name the exact
                   gradient bucket that fell off the tuned path.
    ``cache_hits`` / ``calls`` — resolution-cache effectiveness.
    ``cache_evictions`` — entries dropped by the cache's LRU/TTL bound (a
                   nonzero rate on a short-lived run usually means the
                   capacity is too small for the working set).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            self.tiers: Dict[str, int] = {}
            self.by_key: Dict[str, Dict[str, int]] = {}
            self.phases: Dict[str, Dict[str, int]] = {}
            self.by_key_phase: Dict[str, Dict[str, Dict[str, int]]] = {}
            self.calls = 0
            self.cache_hits = 0
            self.cache_evictions = 0

    def record(self, kernel: str, key: Optional[str], tier: str,
               cached: bool = False, phase: Optional[str] = None) -> None:
        k = key if key is not None else f"{kernel}|*"
        phase = phase if phase is not None else _phase_ctx.get()
        with self._lock:
            self.calls += 1
            if cached:
                self.cache_hits += 1
            self.tiers[tier] = self.tiers.get(tier, 0) + 1
            per = self.by_key.setdefault(k, {})
            per[tier] = per.get(tier, 0) + 1
            ph = self.phases.setdefault(phase, {})
            ph[tier] = ph.get(tier, 0) + 1
            pk = self.by_key_phase.setdefault(phase, {}).setdefault(k, {})
            pk[tier] = pk.get(tier, 0) + 1
        # Fold into the ambient obs collector: the same accounting becomes a
        # tagged counter next to the latency histograms (one enabled-check
        # when nobody is collecting). Keys are deliberately NOT a tag — the
        # per-key breakdown stays in this class; tag cardinality stays
        # kernel × tier × phase × hit/miss.
        col = _obs_collector()
        if col.enabled:
            col.counter(
                "dispatch.calls", kernel=kernel, tier=tier, phase=phase,
                cached="hit" if cached else "miss",
            )

    def record_eviction(self, count: int = 1) -> None:
        with self._lock:
            self.cache_evictions += count

    @property
    def cache_hit_rate(self) -> float:
        return self.cache_hits / self.calls if self.calls else 0.0

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            total = self.calls or 1
            return {
                "calls": self.calls,
                "cache_hits": self.cache_hits,
                "cache_hit_rate": self.cache_hit_rate,
                "cache_evictions": self.cache_evictions,
                "tiers": dict(self.tiers),
                "tier_rates": {t: n / total for t, n in self.tiers.items()},
                "by_key": {k: dict(v) for k, v in self.by_key.items()},
                "phases": {p: dict(v) for p, v in self.phases.items()},
                "by_key_phase": {
                    p: {k: dict(v) for k, v in per.items()}
                    for p, per in self.by_key_phase.items()
                },
            }

    def write(self, path: str) -> None:
        """Export the snapshot as JSON — the artifact `campaign status
        --telemetry` / benchmarks/campaign_report.py consume (one exporter
        shared by the launchers' --telemetry-out flags)."""
        import json

        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1, sort_keys=True)

    def report(self) -> str:
        """Human-readable sustained-performance accounting."""
        snap = self.snapshot()
        lines = [
            "dispatch telemetry: %d calls, %d cache hits (%.0f%%), %d evictions"
            % (snap["calls"], snap["cache_hits"], 100 * snap["cache_hit_rate"],
               snap["cache_evictions"])
        ]
        for tier in TIERS:
            if tier in snap["tiers"]:
                lines.append(
                    f"  tier {tier:<9} {snap['tiers'][tier]}"
                    f" ({100 * snap['tier_rates'][tier]:.0f}%)"
                )
        for phase in PHASES:
            per = snap["phases"].get(phase)
            if per:
                detail = ", ".join(f"{t}={per[t]}" for t in TIERS if t in per)
                lines.append(f"  phase {phase:<8} {sum(per.values())} ({detail})")
        for key in sorted(snap["by_key"]):
            per = snap["by_key"][key]
            detail = ", ".join(f"{t}={per[t]}" for t in TIERS if t in per)
            lines.append(f"  {key}: {detail}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# The runtime
# ---------------------------------------------------------------------------

_INHERIT = object()

# Context-local stack of active runtimes. contextvars give us both asyncio-
# and thread-isolation: a new thread starts with an empty stack and falls
# back to the process-default runtime.
_stack: "contextvars.ContextVar[Tuple[TunedRuntime, ...]]" = contextvars.ContextVar(
    "repro_runtime_stack", default=()
)

_root_lock = threading.Lock()
_root: Optional["TunedRuntime"] = None


class TunedRuntime:
    """A scoped dispatch context: db × mode × policy × cache × telemetry.

    Parameters left unspecified inherit from the runtime that is active at
    construction time (ultimately the process-default runtime), so
    ``repro.runtime(mode="reference")`` inside a serving scope keeps the
    serving database while flipping the implementation path.

    ``db=None`` is meaningful: it means "whatever :func:`default_db`
    resolves to at call time" — the process-default runtime uses it so
    ``set_default_db`` keeps working mid-session.

    ``platform=None`` (the default) namespaces database keys under the
    *detected* platform (:func:`repro.core.platform.detect_platform`,
    honouring the override escape hatch) — callers no longer wire a platform
    string. Pass an explicit name to pin a runtime to a foreign namespace
    (e.g. inspecting a v5e artifact from a dev host).

    The resolution cache is bounded: `cache_capacity` entries, LRU-evicted
    (a long-lived server cycling through many shape buckets cannot grow it
    without limit), plus an optional `cache_ttl` in seconds after which an
    entry re-resolves — evictions are counted in ``telemetry``.
    """

    def __init__(
        self,
        db: Union[TuningDatabase, None, object] = _INHERIT,
        mode: Union[str, object] = _INHERIT,
        policy: Union[Sequence[ResolutionPolicy], None, object] = _INHERIT,
        allow_tune: Union[bool, object] = _INHERIT,
        tune_kwargs: Union[Dict[str, Any], None, object] = _INHERIT,
        platform: Union[str, None, object] = _INHERIT,
        cache_capacity: Union[int, object] = _INHERIT,
        cache_ttl: Union[float, None, object] = _INHERIT,
        bwd_dispatch: Union[bool, object] = _INHERIT,
        guard: Union[bool, object] = _INHERIT,
        guard_nonfinite: Union[bool, object] = _INHERIT,
        name: str = "",
        _is_root: bool = False,
    ):
        parent = None if _is_root else current_runtime()
        self.db = db if db is not _INHERIT else (parent.db if parent else None)
        self.mode = mode if mode is not _INHERIT else (parent.mode if parent else "auto")
        if self.mode not in _MODES:
            raise ValueError(f"mode {self.mode!r} not in {_MODES}")
        pol = policy if policy is not _INHERIT else (parent.policy if parent else None)
        self.policy: Tuple[ResolutionPolicy, ...] = (
            tuple(pol) if pol is not None else default_policy()
        )
        self.allow_tune = bool(
            allow_tune if allow_tune is not _INHERIT
            else (parent.allow_tune if parent else False)
        )
        tk = tune_kwargs if tune_kwargs is not _INHERIT else None
        self.tune_kwargs: Dict[str, Any] = dict(tk or {})
        self.platform: Optional[str] = (
            platform if platform is not _INHERIT
            else (parent.platform if parent else None)
        )
        cap = (
            cache_capacity if cache_capacity is not _INHERIT
            else (parent.cache_capacity if parent else 4096)
        )
        self.cache_capacity = max(0, int(cap))
        self.cache_ttl: Optional[float] = (
            cache_ttl if cache_ttl is not _INHERIT
            else (parent.cache_ttl if parent else None)
        )
        # Whether kernel-mode dispatch differentiates through the tuned
        # backward plane (vjp="dispatch" specs). False restores the
        # reference-VJP recompute — the fwd-only-tuned baseline.
        self.bwd_dispatch = bool(
            bwd_dispatch if bwd_dispatch is not _INHERIT
            else (parent.bwd_dispatch if parent else True)
        )
        # Guarded execution: a faulting kernel variant quarantines its db key
        # in the health book and the dispatch falls through to heuristic /
        # reference instead of raising. guard=False restores raise-through
        # (debugging a kernel wants the traceback, not a silent downgrade).
        self.guard = bool(
            guard if guard is not _INHERIT else (parent.guard if parent else True)
        )
        # Opt-in: on a bucket's first (probe) resolution, a concrete kernel
        # output containing non-finite values counts as a fault. Off by
        # default — under jit the output is a tracer and unobservable, and
        # legitimate kernels can emit inf masks.
        self.guard_nonfinite = bool(
            guard_nonfinite if guard_nonfinite is not _INHERIT
            else (parent.guard_nonfinite if parent else False)
        )
        self.health = HealthBook()
        self.name = name or ("default" if _is_root else f"runtime@{id(self):x}")
        self.telemetry = Telemetry()
        # key -> (db it was resolved against, Resolution, monotonic stamp),
        # LRU-ordered. The db reference is validated on lookup so a swapped
        # database (rt.db reassignment, or set_default_db for db=None
        # runtimes) can never serve a stale resolution from its predecessor;
        # the stamp enforces cache_ttl.
        self._cache: "collections.OrderedDict[str, Tuple[TuningDatabase, Resolution, float]]" = (
            collections.OrderedDict()
        )
        self._cache_lock = threading.Lock()

    # -- scoping -------------------------------------------------------------
    # Deliberately token-free: one runtime instance may be entered
    # concurrently from several threads AND from interleaved asyncio tasks
    # on one thread (each task/thread sees its own copy of the contextvar
    # stack). A contextvar Token would have to be reset in the exact context
    # that created it; popping the innermost occurrence of `self` from the
    # current context's stack is equivalent for our usage and safe in all of
    # the above.
    def __enter__(self) -> "TunedRuntime":
        _stack.set(_stack.get() + (self,))
        return self

    def __exit__(self, *exc) -> None:
        s = _stack.get()
        for i in range(len(s) - 1, -1, -1):
            if s[i] is self:
                _stack.set(s[:i] + s[i + 1:])
                return

    # -- mode ----------------------------------------------------------------
    @property
    def kernel_mode_active(self) -> bool:
        """Whether dispatch takes the kernel path (vs reference).

        ``"auto"`` takes it on a TPU, where the kernels compile natively.
        Elsewhere they only run interpreted, so ``"auto"`` reads
        ``REPRO_USE_PALLAS`` lazily (CI legs opt in with ``=1``).
        """
        if self.mode == "kernel":
            return True
        if self.mode == "reference":
            return False
        return _on_tpu() or os.environ.get("REPRO_USE_PALLAS", "0") == "1"

    # -- cache ---------------------------------------------------------------
    def clear_cache(self) -> None:
        with self._cache_lock:
            self._cache.clear()

    def _cache_evict(self, key: str) -> None:
        with self._cache_lock:
            self._cache.pop(key, None)

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def _cache_get(self, key: str, db: TuningDatabase) -> Optional[Resolution]:
        now = time.monotonic()
        with self._cache_lock:
            hit = self._cache.get(key)
            if hit is None:
                return None
            cached_db, res, stamp = hit
            if cached_db is not db:
                return None
            if self.cache_ttl is not None and now - stamp > self.cache_ttl:
                del self._cache[key]
                self.telemetry.record_eviction()
                return None
            self._cache.move_to_end(key)        # LRU touch
            return res

    def _cache_put(self, key: str, db: TuningDatabase, res: Resolution) -> None:
        if self.cache_capacity <= 0:
            return
        with self._cache_lock:
            self._cache[key] = (db, res, time.monotonic())
            self._cache.move_to_end(key)
            evicted = 0
            while len(self._cache) > self.cache_capacity:
                self._cache.popitem(last=False)
                evicted += 1
        if evicted:
            self.telemetry.record_eviction(evicted)

    # -- resolution ----------------------------------------------------------
    def resolve(self, tunable: Union[str, Tunable], args: Sequence[Any],
                key_extra: str = "",
                allow_tune: Optional[bool] = None,
                tune_kwargs: Optional[Dict[str, Any]] = None,
                dp_dims: Optional[Dict[int, int]] = None) -> Resolution:
        """Run the policy pipeline for (tunable, args), with caching.

        Returns the cached :class:`Resolution` when this bucket key was
        resolved before under this runtime against the same database
        (telemetry still counts the call, flagged as a cache hit).

        ``allow_tune`` / ``tune_kwargs`` override the runtime's defaults for
        THIS call only (how warmup grants TuneNow permission without
        mutating a runtime other threads may be dispatching through). A
        cached resolution wins over ``allow_tune=True`` — ``clear_cache()``
        first to force re-tuning of already-resolved buckets.

        ``dp_dims`` overrides which dim of which arg is keyed at its local
        shard size under a sharded mesh (see ``tuner._args_key``) — backward
        dispatch sites with transposed operands pass it.
        """
        from .tuner import _args_key  # late: tuner imports this module's deps

        tunable = _as_tunable(tunable)
        db = self.db if self.db is not None else default_db()
        platform = self.platform or _platform()
        col = _obs_collector()
        t0 = time.perf_counter() if col.enabled else 0.0
        key = _args_key(tunable, args, platform, key_extra, dp_dims=dp_dims)
        # Health first: a quarantined bucket must not serve its cached (or
        # freshly re-resolved) faulting config. "blocked" short-circuits to
        # the degraded tier; "probe" (backoff lapsed) re-runs the pipeline
        # uncached with probe=True so the guard re-validates before the
        # health book clears the entry.
        probe = False
        skip_record_tiers = False
        if self.guard:
            h = self.health.consult(key)
            if h is not None:
                state, level = h
                if state == "probe":
                    probe = True
                elif level == "kernel":
                    res = Resolution(None, "reference", key=key, cache=False)
                    self.telemetry.record(tunable.name, key, res.tier)
                    if col.enabled:
                        col.observe(
                            "dispatch.resolve_s", time.perf_counter() - t0,
                            tier=res.tier, phase=_phase_ctx.get(), cached="miss",
                        )
                    return res
                else:
                    skip_record_tiers = True
        if not (probe or skip_record_tiers):
            hit = self._cache_get(key, db)
            if hit is not None:
                self.telemetry.record(tunable.name, key, hit.tier, cached=True)
                if col.enabled:
                    col.observe(
                        "dispatch.resolve_s", time.perf_counter() - t0,
                        tier=hit.tier, phase=_phase_ctx.get(), cached="hit",
                    )
                return hit
        req = ResolutionRequest(
            tunable=tunable, args=tuple(args), key=key, key_extra=key_extra,
            db=db, platform=platform, runtime=self,
            allow_tune=self.allow_tune if allow_tune is None else bool(allow_tune),
            tune_kwargs={**self.tune_kwargs, **(tune_kwargs or {})},
        )
        pipeline = self.policy
        if skip_record_tiers:
            # Record-level quarantine: the stored/measured config for this
            # bucket faulted — resolve among the non-db tiers only.
            pipeline = tuple(
                p for p in pipeline if p.name not in ("exact", "tune", "cover")
            )
        res: Optional[Resolution] = None
        for pol in pipeline:
            res = pol.resolve(req)
            if res is not None:
                break
        if res is None:
            # An exhausted custom pipeline falls back to reference execution.
            res = Resolution(None, "reference")
        res.key = key
        if probe or skip_record_tiers:
            res.cache = False
            res.probe = probe
        elif self.guard and self.guard_nonfinite and res.config is not None:
            # First-resolve warmup probe: the guarded dispatch validates this
            # execution's output; the cached copy is a plain resolution.
            res = dataclasses.replace(res, probe=True)
        if res.cache:
            self._cache_put(key, db, dataclasses.replace(res, probe=False))
        self.telemetry.record(tunable.name, key, res.tier)
        if col.enabled:
            # Per-tier resolution latency: a 'tune' row is a full search, an
            # 'exact' miss is one db lookup, a 'hit' is the cache fast path.
            col.observe(
                "dispatch.resolve_s", time.perf_counter() - t0,
                tier=res.tier, phase=_phase_ctx.get(), cached="miss",
            )
        return res

    # -- dispatch ------------------------------------------------------------
    def dispatch(self, tunable: Union[str, Tunable], *args,
                 config: Optional[Config] = None,
                 dp_dims: Optional[Dict[int, int]] = None, **kwargs):
        """Execute one tunable through this runtime's resolution chain.

        Reference mode wins over everything — including ``config=`` — just
        like the old ``ops.*`` wrappers: it is the escape hatch for hosts
        where the kernel cannot lower at all (multi-pod dry-runs), so an
        explicit config must not force a kernel there. In kernel mode,
        ``config=`` bypasses resolution (tier ``override``); otherwise the
        resolved config is bound as a kernel variant on the canonicalized
        arguments, and the :class:`Reference` tier executes the dispatch
        spec's reference fn on the *original* arguments.

        The kernel path is differentiable: with ``vjp="dispatch"`` and a
        declared backward plan (and ``bwd_dispatch`` enabled on this
        runtime), gradients are themselves dispatch sites resolved through
        this same chain under ``dispatch_phase("bwd")``; with
        ``vjp="reference"`` the bound variant's backward recomputes the
        reference implementation's VJP. ``dp_dims`` overrides local-shape
        keying per arg (backward sites with transposed operands).

        Under an enabled obs collector each dispatch runs inside a
        ``span("dispatch")`` (kernel + phase on the event), so resolution
        and execution cost shows up in the span tree; disabled collectors
        skip straight to the implementation — one branch, no span object.
        """
        col = _obs_collector()
        if col.enabled:
            name = tunable.name if isinstance(tunable, Tunable) else str(tunable)
            with _obs_span("dispatch", kernel=name, phase=_phase_ctx.get()):
                return self._dispatch_impl(tunable, args, config, dp_dims, kwargs)
        return self._dispatch_impl(tunable, args, config, dp_dims, kwargs)

    def _dispatch_impl(self, tunable, args, config, dp_dims, kwargs):
        tunable = _as_tunable(tunable)
        spec = tunable.dispatch or _DEFAULT_SPEC
        if not self.kernel_mode_active:
            self.telemetry.record(tunable.name, None, "reference")
            return _reference_call(tunable, spec, args, kwargs)
        if _jvp_nesting(args) >= 2:
            # Second-order autodiff (`jax.grad(jax.grad(...))`): the outer
            # linearization re-traces the inner custom_vjp's forward with
            # JVP tangents attached, and the raw Pallas call inside has no
            # JVP rule. Differentiate through the reference implementation
            # instead — first-order dispatch (depth 1) stays on the tuned
            # kernel path, so training never takes this branch.
            self.telemetry.record(tunable.name, None, "reference")
            return _reference_call(tunable, spec, args, kwargs)
        if config is not None:
            # Explicit config= stays unguarded: the caller pinned a variant
            # by hand (tests, benchmarks) and wants the real traceback.
            self.telemetry.record(tunable.name, None, "override")
            cargs, restore = spec.canon(args)
            return restore(_kernel_call(self, tunable, spec, config, cargs, kwargs,
                                        dp_dims))
        cargs, restore = spec.canon(args)
        res = self.resolve(tunable, cargs, key_extra=spec.extra_for(kwargs),
                           dp_dims=dp_dims)
        if res.config is None:
            return _reference_call(tunable, spec, args, kwargs)
        if not self.guard:
            _fault_point(f"dispatch.kernel:{tunable.name}", tier=res.tier)
            return restore(_kernel_call(self, tunable, spec, res.config, cargs,
                                        kwargs, dp_dims))
        return self._guarded_call(tunable, spec, res, args, cargs, restore, kwargs,
                                  dp_dims)

    def _guarded_call(self, tunable, spec, res, args, cargs, restore, kwargs,
                      dp_dims):
        """Execute a resolved kernel variant behind the fault guard.

        On exception (or a failed non-finite probe) the bucket's db key is
        quarantined in the health book and execution falls through the
        remaining tiers — heuristic config first (when the faulting tier was
        a stored/measured record and the heuristic differs), reference
        terminally — so a miscompiled variant or poisoned record degrades a
        site instead of taking down the run. Exceptions at trace time are
        caught the same as concrete-execution ones (dispatch under jit runs
        at trace time); KeyboardInterrupt/SystemExit still propagate. The
        fall-through execution records an extra telemetry row under the
        tier that actually served, so a gate can see both the resolution
        and the degradation.
        """
        key = res.key
        try:
            rule = _fault_point(f"dispatch.kernel:{tunable.name}", tier=res.tier)
            out = _kernel_call(self, tunable, spec, res.config, cargs, kwargs,
                               dp_dims)
            if rule is not None and rule.kind == "nan":
                out = _nan_corrupt(out)
            if res.probe:
                if self.guard_nonfinite and _has_nonfinite(out):
                    raise DispatchFault(
                        f"non-finite output from {tunable.name} during "
                        "first-resolve probe"
                    )
                self.health.record_ok(key)
            return restore(out)
        except Exception as e:
            level = (
                "record" if res.tier in ("exact", "tune", "cover") else "kernel"
            )
            self._note_quarantine(tunable, key, res.tier, level, e)
        if level == "record":
            hcfg = tunable.default_config(*cargs)
            if hcfg != res.config:
                try:
                    _fault_point(f"dispatch.kernel:{tunable.name}", tier="heuristic")
                    out = _kernel_call(self, tunable, spec, hcfg, cargs, kwargs,
                                       dp_dims)
                    self.telemetry.record(tunable.name, key, "heuristic")
                    return restore(out)
                except Exception as e2:
                    self._note_quarantine(tunable, key, "heuristic", "kernel", e2)
            else:
                # The heuristic IS the faulting config; retrying is pointless.
                self.health.quarantine(key, "kernel")
        self.telemetry.record(tunable.name, key, "reference")
        return _reference_call(tunable, spec, args, kwargs)

    def _note_quarantine(self, tunable, key, tier, level, exc) -> None:
        self.health.quarantine(key, level)
        self._cache_evict(key)
        col = _obs_collector()
        if col.enabled:
            col.counter(
                "dispatch.quarantine", kernel=tunable.name, tier=tier, level=level
            )
        # Fires even when metric collection is off: a silently-degraded site
        # is exactly the hazard warn_once exists for.
        col.warn_once(
            "dispatch.quarantine", key=f"{key}|{level}", kernel=tunable.name,
            tier=tier, level=level, error=f"{type(exc).__name__}: {exc}",
        )

    # -- fusion policy -------------------------------------------------------
    def fusion_wins(self, tunable: Union[str, Tunable], *args, **kwargs) -> bool:
        """Whether a fused-epilogue site should dispatch *fused* here.

        The resolution-policy hook behind ``kernels/fused.py``: a model
        layer asks "does the database say fusion wins for this call?"
        before routing through a fused tunable instead of its unfused
        ops. True iff the kernel path is active AND the active database
        holds an exact record (with a still-valid config) for the
        canonicalized call — i.e. the fused site would resolve ExactHit.
        A campaign that measured the fused variant as a win banks that
        record; sites it never tuned (or where fusion lost and the job
        was dropped) keep their unfused dispatch chain, so e2e ExactHit
        coverage is invariant under this hook. Pure lookup: no telemetry
        rows, no cache mutation, no tuning.
        """
        if not self.kernel_mode_active:
            return False
        from .tuner import _args_key  # late: tuner imports this module's deps

        try:
            tunable = _as_tunable(tunable)
        except KeyError:
            return False
        spec = tunable.dispatch or _DEFAULT_SPEC
        cargs, _ = spec.canon(args)
        db = self.db if self.db is not None else default_db()
        platform = self.platform or _platform()
        key = _args_key(tunable, cargs, platform, spec.extra_for(kwargs))
        rec = db.lookup(key)
        return rec is not None and tunable.space.is_valid(rec.config)

    def __repr__(self) -> str:
        db = "default" if self.db is None else (self.db.path or "memory")
        plat = self.platform or "detected"
        return (
            f"<TunedRuntime {self.name} mode={self.mode} db={db} "
            f"platform={plat} policy=({', '.join(p.name for p in self.policy)})>"
        )


_DEFAULT_SPEC = DispatchSpec()


def _kernel_call(runtime: "TunedRuntime", tunable: Tunable, spec: DispatchSpec,
                 config: Config, cargs: tuple, kwargs: Dict[str, Any],
                 dp_dims: Optional[Dict[int, int]] = None):
    """Execute one bound kernel variant on canonical args, trainably.

    Under a mesh that splits the batch, the variant runs per device
    (:func:`_per_device`): a Mosaic kernel cannot be partitioned by XLA.

    Pallas kernels have no transpose rules, so a bare variant inside
    ``jax.grad`` fails. Three backward strategies, per ``spec.vjp``:

    * ``"dispatch"`` (with a declared ``spec.bwd`` and the runtime's
      ``bwd_dispatch`` enabled) — the variant is wrapped in a
      ``jax.custom_vjp`` whose backward executes the spec's backward plan
      under ``dispatch_phase("bwd")``: each gradient is a dispatch site of
      its own, resolved through the active runtime's policy pipeline with
      its own database key and telemetry row. The tuned backward plane.
    * ``"reference"`` — backward runs the VJP of the reference
      implementation on the same (canonical) arguments: mathematically the
      reference gradient, at the cost of one reference recompute (the
      fwd-only-tuned baseline; also the fallback when a dispatch-vjp
      tunable runs under ``bwd_dispatch=False``).
    * ``"none"`` — the bare variant (backward-plane tunables themselves).

    The *residual contract* (``spec.residuals > 0``) threads forward
    intermediates into the backward plan: the variant returns
    ``(primal, *aux)``; ``fwd`` saves ``(args, primal, aux)`` as the
    ``custom_vjp`` residuals; the plan is called
    ``bwd(ct, *args, primal, *aux, **kwargs)``; the caller only ever sees
    the primal. With ``vjp="reference"`` the aux outputs are simply
    discarded (the reference VJP recomputes everything, as before).
    """
    import jax

    variant = _per_device(functools.partial(tunable.variant(**config), **kwargs),
                          spec, cargs, dp_dims)
    ref = spec.reference_for(tunable)
    n_res = spec.residuals
    mode = spec.vjp
    if mode == "dispatch" and (spec.bwd is None or not runtime.bwd_dispatch):
        mode = "reference"
    if mode == "none" or (mode == "reference" and ref is None):
        out = variant(*cargs)
        return out[0] if n_res else out

    # kwargs (eps/causal/window/...) are schedule-or-semantics flags, never
    # differentiated: bound by closure above, so custom_vjp sees arrays only.
    @jax.custom_vjp
    def run(*a):
        out = variant(*a)
        return out[0] if n_res else out

    def fwd(*a):
        out = variant(*a)
        if n_res:
            return out[0], (a, out[0], tuple(out[1:]))
        return out, (a, None, ())

    if mode == "dispatch":
        def bwd(res, ct):
            a, primal, aux = res
            with dispatch_phase("bwd"):
                if n_res:
                    grads = spec.bwd(ct, *a, primal, *aux, **kwargs)
                else:
                    grads = spec.bwd(ct, *a, **kwargs)
            return _match_cotangents(grads, a)
    else:
        def bwd(res, ct):
            a, _, _ = res
            return jax.vjp(lambda *p: ref(*p, **kwargs), *a)[1](ct)

    run.defvjp(fwd, bwd)
    try:
        return run(*cargs)
    except TypeError as e:
        if "forward-mode" not in str(e):
            raise
        # `jax.jvp` / `jax.linearize` over a dispatch site: custom_vjp has
        # no forward-mode rule. Fall back to the reference implementation
        # (jvp-able jnp math) on the canonical args — the caller's restore
        # still applies to our return value.
        runtime.telemetry.record(tunable.name, None, "reference")
        return ref(*cargs, **kwargs)


def _per_device(fn: Callable, spec: DispatchSpec, cargs: tuple,
                dp_dims: Optional[Dict[int, int]]) -> Callable:
    """``fn`` run on each device's shard of the batch, via ``jax.shard_map``.

    Only inside a mesh context that splits the batch (the trainer's scope,
    ``dp_degree > 1``); elsewhere ``fn`` itself. The sharded dims are the
    ones the database key localizes (``spec.data_parallel_args``, or this
    call's ``dp_dims``), so each device runs exactly the shapes its key
    names; every other arg is replicated. Outputs stay sharded on their
    leading dim (replicated when no arg is split), except those that sum
    over the sharded dim
    (``spec.reduced_outputs``, or all of them when ``dp_dims`` moves the
    sharded dim into the contraction), which are summed over the data axes.
    A batch the degree does not divide is left unsplit, as its key is.
    """
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ..distributed import sharding as shd

    ctx, dp = shd.current_mesh_layout(), shd.current_dp_degree()
    if ctx is None or not dp or dp <= 1:
        return fn
    mesh, layout = ctx
    axes = shd.data_axes_for(mesh, layout, dp)
    if not axes:
        return fn
    ax = axes if len(axes) > 1 else axes[0]
    dims = (dp_dims if dp_dims is not None
            else {i: 0 for i in spec.data_parallel_args})
    in_specs = []
    for i, a in enumerate(cargs):
        d, shape = dims.get(i), np.shape(a)
        if d is None or len(shape) <= d:
            in_specs.append(P())
        elif shape[d] % dp:
            return fn
        else:
            in_specs.append(P(*([None] * d), ax))
    out_tree = jax.tree_util.tree_structure(jax.eval_shape(fn, *cargs))
    # Nothing split (a kernel that localizes no arg): every device computes
    # the whole result, which is then replicated, not sharded.
    split = any(s != P() for s in in_specs)
    reduced = set(range(out_tree.num_leaves) if dp_dims is not None or not split
                  else spec.reduced_outputs)
    out_specs = jax.tree_util.tree_unflatten(
        out_tree, [P() if i in reduced else P(ax) for i in range(out_tree.num_leaves)]
    )

    def local(*a):
        leaves = jax.tree_util.tree_leaves(fn(*a))
        return jax.tree_util.tree_unflatten(out_tree, [
            jax.lax.psum(x, ax) if split and i in reduced else x
            for i, x in enumerate(leaves)
        ])

    # check_vma off: a pallas_call's out_shape states no per-axis variance.
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)


def _jvp_nesting(args) -> int:
    """Depth of forward-mode (JVP) tracer nesting across ``args``.

    ``jax.grad`` linearizes through one JVP trace (depth 1 — the depth
    ``custom_vjp`` handles); ``jax.grad(jax.grad(...))`` stacks a second
    (depth 2 — the depth it cannot). Walking ``.primal`` is cheap and
    version-stable: a ``JVPTracer``'s primal is the tracer of the
    enclosing trace.
    """
    from jax.interpreters import ad

    deepest = 0
    for x in args:
        d = 0
        while isinstance(x, ad.JVPTracer) and d < 8:
            d += 1
            x = x.primal
        if d > deepest:
            deepest = d
    return deepest


def _match_cotangents(grads, primals) -> tuple:
    """Align a backward plan's outputs with custom_vjp's cotangent contract.

    The plan returns one gradient per canonical primal, ``None`` for
    non-differentiable args. JAX expects a ``float0`` cotangent for integer
    primals (labels and the like) and the primal's dtype for inexact ones.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    grads = tuple(grads)
    if len(grads) != len(primals):
        raise ValueError(
            f"backward plan returned {len(grads)} gradients for "
            f"{len(primals)} primals"
        )
    out = []
    for g, x in zip(grads, primals):
        dtype = jnp.result_type(x)
        if not jnp.issubdtype(dtype, jnp.inexact):
            out.append(np.zeros(np.shape(x), jax.dtypes.float0))
        elif g is None:
            out.append(jnp.zeros(jnp.shape(x), dtype))
        else:
            out.append(g.astype(dtype))
    return tuple(out)


def _has_nonfinite(out) -> bool:
    """True when a *concrete* output contains NaN/inf float values.

    Traced outputs (dispatch under jit) are unobservable here and count as
    finite — the probe is a warmup-time check, not a jit-time one.
    """
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(out):
        if isinstance(leaf, jax.core.Tracer):
            return False
        try:
            a = np.asarray(leaf)
        except Exception:
            continue
        if a.dtype.kind == "f" and not np.isfinite(a).all():
            return True
    return False


def _nan_corrupt(out):
    """Replace concrete float outputs with NaNs (fault kind="nan")."""
    import jax
    import jax.numpy as jnp

    def corrupt(x):
        if isinstance(x, jax.core.Tracer) or not hasattr(x, "dtype"):
            return x
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.full_like(x, jnp.nan)
        return x

    return jax.tree_util.tree_map(corrupt, out)


def _reference_call(tunable: Tunable, spec: DispatchSpec, args, kwargs):
    ref = spec.reference_for(tunable)
    if ref is None:
        raise TypeError(
            f"tunable {tunable.name!r} has no reference implementation to "
            "dispatch to in reference mode; declare one via @tunable("
            "reference=...) or DispatchSpec(reference=...)"
        )
    return ref(*args, **kwargs)


def _as_tunable(t: Union[str, Tunable]) -> Tunable:
    if isinstance(t, Tunable):
        return t
    try:
        return get_tunable(t)
    except KeyError:
        ensure_registered()
        return get_tunable(t)


def ensure_registered() -> None:
    """Import the modules whose @tunable decorators populate the registry.

    This is the ONE list of tunable-bearing modules (the campaign planner's
    ``_register_tunables`` delegates here) — extend it when a new module
    grows ``@tunable`` sites. The upward imports are deliberately lazy:
    they run at first dispatch-by-name, never at ``repro.core`` import.
    """
    from .. import kernels  # noqa: F401
    from ..models import tunables  # noqa: F401


# ---------------------------------------------------------------------------
# Module-level API (what `repro` re-exports)
# ---------------------------------------------------------------------------


def _root_runtime() -> TunedRuntime:
    global _root
    if _root is None:
        with _root_lock:
            if _root is None:
                _root = TunedRuntime(
                    db=None, mode="auto", policy=None, allow_tune=False,
                    tune_kwargs=None, name="default", _is_root=True,
                )
    return _root


def current_runtime() -> TunedRuntime:
    """The innermost active runtime, or the process-default one."""
    s = _stack.get()
    return s[-1] if s else _root_runtime()


def runtime(
    db: Union[TuningDatabase, None, object] = _INHERIT,
    mode: Union[str, object] = _INHERIT,
    policy: Union[Sequence[ResolutionPolicy], None, object] = _INHERIT,
    allow_tune: Union[bool, object] = _INHERIT,
    tune_kwargs: Union[Dict[str, Any], None, object] = _INHERIT,
    platform: Union[str, None, object] = _INHERIT,
    cache_capacity: Union[int, object] = _INHERIT,
    cache_ttl: Union[float, None, object] = _INHERIT,
    bwd_dispatch: Union[bool, object] = _INHERIT,
    guard: Union[bool, object] = _INHERIT,
    guard_nonfinite: Union[bool, object] = _INHERIT,
    name: str = "",
) -> TunedRuntime:
    """Create a scoped dispatch runtime (use as ``with repro.runtime(...)``)."""
    return TunedRuntime(
        db=db, mode=mode, policy=policy, allow_tune=allow_tune,
        tune_kwargs=tune_kwargs, platform=platform,
        cache_capacity=cache_capacity, cache_ttl=cache_ttl,
        bwd_dispatch=bwd_dispatch, guard=guard,
        guard_nonfinite=guard_nonfinite, name=name,
    )


def dispatch(tunable: Union[str, Tunable], *args,
             config: Optional[Config] = None, **kwargs):
    """Dispatch through whichever runtime is active at the call."""
    return current_runtime().dispatch(tunable, *args, config=config, **kwargs)


def fusion_wins(tunable: Union[str, Tunable], *args, **kwargs) -> bool:
    """Whether the active runtime's database says fusion wins here.

    See :meth:`TunedRuntime.fusion_wins` — the resolution-policy hook the
    model layer consults before routing a site through a fused-epilogue
    tunable (``matmul_bias_act`` / ``rmsnorm_matmul``) instead of its
    unfused dispatch chain.
    """
    return current_runtime().fusion_wins(tunable, *args, **kwargs)


def entry_point(name: str) -> Callable:
    """An auto-generated deployment entry point for a registered tunable.

    The returned callable has the old ``ops.<kernel>`` contract —
    ``fn(*args, config=None, **call_kwargs)`` — and routes through
    :func:`current_runtime`, so it honours whatever scope is active where
    it is *called*, not where it was created.
    """

    def call(*args, config: Optional[Config] = None, **kwargs):
        return current_runtime().dispatch(name, *args, config=config, **kwargs)

    call.__name__ = name
    call.__qualname__ = name
    call.__doc__ = (
        f"Registry-dispatched deployment entry point for tunable {name!r} "
        "(resolution: the active TunedRuntime's policy pipeline)."
    )
    return call
