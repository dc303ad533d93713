"""BackgroundTune: always-on dynamic tuning under live traffic.

ROADMAP item 2, built for the serving path's latency contract: a resolution
miss must never pay a tuning search inline. The :class:`BackgroundTune`
policy answers a miss with the heuristic config *immediately* (tier
``"bgtune"``, uncached) while handing the bucket to a
:class:`BackgroundTuner` — a bounded-queue worker thread that runs the full
autotune loop off the request path and hot-swaps the winning record into
the live database. Because bgtune resolutions are never cached, every
subsequent resolve re-consults :class:`~.runtime.ExactHit` first, so the
moment the record lands the bucket flips to the tuned config with zero
coordination — the fleet converges to 100% ExactHit with no request-path
stalls (Petrovič et al. 2019: dynamic autotuning pays off only when a slow
or failed candidate cannot stall the application's critical path).

Failure is steady-state here, same contract as the dispatch guard:

* the queue is bounded — an overloaded tuner *sheds* jobs (counted, and the
  shed bucket is re-offered by a later resolve) rather than growing without
  limit;
* the worker retries each job with backoff, and a job that exhausts its
  attempts is parked (warn_once + counter) so it cannot spin the worker;
* a worker *crash* (anything escaping the per-job ``except Exception``,
  e.g. the harness's ``InjectedWorkerCrash``) kills the worker loop only:
  the policy notices the dead worker and demotes itself — resolution falls
  through to plain Heuristic, and resolve never blocks on the tuner.

Promotion lands on the *request's* database key: the worker re-materializes
arguments at the key's (already bucketed, already shard-localized) shapes,
runs ``autotune(save=False)``, and ``db.put``s a record under exactly
``req.key`` — so an ExactHit follows on the very next resolve. With
``export_path`` set, every promotion also rewrites a standalone delta
database (promoted records only) via the same atomic write-to-temp path, a
fleet's mechanism for shipping freshly-learned records to its peers.

Obs: ``bgtune.queue_depth`` gauge, ``bgtune.promotions`` counter,
``bgtune.promote_latency_s`` histogram (enqueue → record live), plus
``bgtune.shed`` / ``bgtune.failures`` counters. The worker thread starts
with a fresh contextvar context, so the collector active at *offer* time is
captured with the job and re-entered around its execution.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Optional, Tuple

from ..obs.collect import ObsCollector, current_collector as _obs_collector
from ..testing.faults import fault_point as _fault_point
from .database import Record, TuningDatabase, now, split_key
from .runtime import (
    CoverSet,
    ExactHit,
    Heuristic,
    Reference,
    Resolution,
    ResolutionPolicy,
    ResolutionRequest,
    TunedRuntime,
    _as_tunable,
)


@dataclasses.dataclass
class _BgJob:
    """One queued tuning task, self-contained for the worker thread."""

    kernel: str
    key: str
    key_extra: str
    arg_shapes: Tuple[Tuple[int, ...], ...]
    arg_dtypes: Tuple[str, ...]
    db: TuningDatabase
    collector: ObsCollector
    enqueued: float                    # monotonic stamp (promote latency)


class BackgroundTuner:
    """Bounded async tuner: a worker thread promoting records off-path.

    ``budget`` is the per-job search budget (coordinate descent by default;
    ``search_factory(job) -> SearchAlgorithm`` overrides per job).
    ``device`` pins tuning measurements to a spare accelerator
    (``jax.default_device``) so search traffic never contends with serving.
    ``max_attempts``/``backoff_s`` bound per-job retries; ``max_queue``
    bounds memory. ``export_path`` keeps a standalone delta database of
    promoted records current on disk.

    Lifecycle: the worker starts lazily on the first :meth:`offer`;
    :meth:`drain` waits for the queue to empty (tests/shutdown);
    :meth:`stop` ends the worker. ``accepting`` is False once the worker
    has died or been stopped — the :class:`BackgroundTune` policy checks it
    and demotes itself rather than queueing into the void.
    """

    def __init__(
        self,
        budget: int = 16,
        evaluator: Optional[Any] = None,
        search_factory: Optional[Callable[[_BgJob], Any]] = None,
        max_queue: int = 64,
        max_attempts: int = 3,
        backoff_s: float = 0.05,
        export_path: Optional[str] = None,
        device: Optional[Any] = None,
        arg_seed: int = 0,
        name: str = "bgtune",
    ):
        self.budget = int(budget)
        self.evaluator = evaluator
        self.search_factory = search_factory
        self.max_attempts = max(1, int(max_attempts))
        self.backoff_s = float(backoff_s)
        self.export_path = export_path
        self.device = device
        self.arg_seed = int(arg_seed)
        self.name = name
        self._q: "queue.Queue[_BgJob]" = queue.Queue(maxsize=max(1, int(max_queue)))
        self._lock = threading.Lock()
        self._seen: set = set()        # keys queued, running, or finished
        self._inflight = 0             # queued + currently-running jobs
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()
        self._death: Optional[str] = None
        self._promoted: list = []      # Records, in promotion order
        self.promotions = 0
        self.failures = 0
        self.shed = 0

    # -- lifecycle -----------------------------------------------------------
    @property
    def accepting(self) -> bool:
        """Whether offers will (eventually) be worked: not stopped, worker
        not dead. True before the lazy first start."""
        if self._stopped.is_set() or self._death is not None:
            return False
        t = self._thread
        return t is None or t.is_alive()

    def _ensure_started(self) -> None:
        if self._thread is not None:
            return
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=f"repro-{self.name}", daemon=True
                )
                self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stopped.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until all offered jobs have finished (or the worker died).
        Returns True when the queue fully drained within `timeout`."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                idle = self._inflight == 0
            # A worker that died (even before this call) left its job
            # unfinished: that is never a drained queue.
            if self._death is not None:
                return False
            if idle:
                return True
            if not self.accepting:
                return False
            time.sleep(0.005)
        return False

    # -- intake ---------------------------------------------------------------
    def offer(self, req: ResolutionRequest) -> bool:
        """Enqueue one bucket for background tuning (idempotent per key).

        Never blocks: a full queue sheds the offer (the key is released so
        a later resolve re-offers it). Returns False only when the tuner is
        no longer accepting at all.
        """
        if not self.accepting:
            return False
        key = req.key
        with self._lock:
            if key in self._seen:
                return True
            self._seen.add(key)
        col = _obs_collector()
        job = _BgJob(
            kernel=req.tunable.name,
            key=key,
            key_extra=req.key_extra,
            # The key's shapes are already bucketed (and, under a sharded
            # mesh, localized to the per-device shard) — materializing at
            # exactly these shapes re-derives exactly this key, so the
            # promoted record is an ExactHit for the live traffic.
            arg_shapes=split_key(key)[2],
            arg_dtypes=tuple(
                str(a.dtype) for a in req.args if hasattr(a, "dtype")
            ),
            db=req.db,
            collector=col,
            enqueued=time.monotonic(),
        )
        try:
            self._q.put_nowait(job)
        except queue.Full:
            with self._lock:
                self._seen.discard(key)
                self.shed += 1
            if col.enabled:
                col.counter("bgtune.shed", kernel=job.kernel)
            return True
        with self._lock:
            self._inflight += 1
        if col.enabled:
            col.gauge("bgtune.queue_depth", float(self._q.qsize()))
        self._ensure_started()
        return True

    # -- worker ---------------------------------------------------------------
    def _run(self) -> None:
        while not self._stopped.is_set():
            try:
                job = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            try:
                self._run_job(job)
            except BaseException as e:  # noqa: BLE001 — crash isolation
                # Anything that escaped the per-job retry loop (an injected
                # InjectedWorkerCrash, KeyboardInterrupt delivered here, a
                # MemoryError) kills THIS worker only. Record the cause so
                # `accepting` flips and the policy demotes to Heuristic —
                # the resolve path never notices beyond a tier change.
                self._death = f"{type(e).__name__}: {e}"
                job.collector.warn_once(
                    "bgtune.worker_dead", key=self.name,
                    kernel=job.kernel, error=self._death,
                )
                return
            finally:
                with self._lock:
                    self._inflight -= 1

    def _run_job(self, job: _BgJob) -> None:
        col = job.collector
        last: Optional[Exception] = None
        for attempt in range(1, self.max_attempts + 1):
            try:
                _fault_point(f"bgtune.worker:{job.kernel}", attempt=attempt)
                self._tune_one(job)
            except Exception as e:
                last = e
                time.sleep(self.backoff_s * attempt)
                continue
            latency = time.monotonic() - job.enqueued
            with self._lock:
                self.promotions += 1
            if col.enabled:
                col.counter("bgtune.promotions", kernel=job.kernel)
                col.observe(
                    "bgtune.promote_latency_s", latency, kernel=job.kernel
                )
                col.gauge("bgtune.queue_depth", float(self._q.qsize()))
            self._export_delta()
            return
        # Attempts exhausted: park the key (it stays in _seen, so resolve
        # keeps serving the heuristic for this bucket without re-queueing a
        # job that cannot succeed).
        with self._lock:
            self.failures += 1
        if col.enabled:
            col.counter("bgtune.failures", kernel=job.kernel)
        col.warn_once(
            "bgtune.job_failed", key=job.key, kernel=job.kernel,
            attempts=self.max_attempts,
            error=f"{type(last).__name__}: {last}" if last else "unknown",
        )

    def _tune_one(self, job: _BgJob) -> None:
        # Upward imports are lazy: the campaign layer imports core freely.
        from ..campaign.planner import TuningJob
        from ..campaign.runner import materialize_args
        from .search import CoordinateDescent
        from .tuner import autotune

        tunable = _as_tunable(job.kernel)
        args = materialize_args(
            TuningJob(
                kernel=job.kernel,
                arg_shapes=job.arg_shapes,
                arg_dtypes=job.arg_dtypes,
                key_extra=job.key_extra,
            ),
            seed=self.arg_seed,
        )
        search = (
            self.search_factory(job) if self.search_factory
            else CoordinateDescent(budget=self.budget)
        )
        dev = contextlib.nullcontext()
        if self.device is not None:
            import jax

            dev = jax.default_device(self.device)
        # Scoped runtime, same discipline as the campaign runner: nested
        # dispatches inside variant/reference evaluation resolve against the
        # job's db without touching the process default (the worker thread's
        # context starts at the root runtime, never the serving scope).
        with dev, TunedRuntime(db=job.db, name=f"{self.name}-worker"):
            res = autotune(
                tunable, args, search=search, evaluator=self.evaluator,
                db=job.db, key_extra=job.key_extra, save=False,
            )
        # Promote under the REQUEST's key, not a freshly-derived one: the
        # two agree by construction (bucketing is idempotent), but the
        # request key is the contract ExactHit will be consulted with.
        rec = Record(
            key=job.key,
            config=dict(res.best_config),
            objective=res.best_objective,
            evaluator=type(self.evaluator).__name__.replace(
                "Evaluator", ""
            ).lower() if self.evaluator is not None else "wallclock",
            evaluations=res.evaluations,
            timestamp=now(),
            meta={
                "source": "bgtune",
                "default_objective": res.default_objective,
            },
        )
        # db.put is lock-guarded and (for file-backed dbs) atomic on disk —
        # this is the hot swap: the next resolve's ExactHit sees it.
        job.db.put(rec)
        with self._lock:
            self._promoted.append(rec)

    def _export_delta(self) -> None:
        """Rewrite the standalone delta database of promoted records."""
        if not self.export_path:
            return
        with self._lock:
            recs = list(self._promoted)
        delta = TuningDatabase(None)
        for r in recs:
            delta.put(r, save=False)
        delta.path = self.export_path
        delta.save()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "accepting": self.accepting,
                "queue_depth": self._q.qsize(),
                "inflight": self._inflight,
                "promotions": self.promotions,
                "failures": self.failures,
                "shed": self.shed,
                "death": self._death,
            }

    def __repr__(self) -> str:
        return (
            f"<BackgroundTuner {self.name} accepting={self.accepting} "
            f"promotions={self.promotions} failures={self.failures}>"
        )


class BackgroundTune(ResolutionPolicy):
    """Resolution tier: serve the heuristic now, tune in the background.

    Sits between ExactHit and CoverSet in :func:`background_policy` — ahead
    of CoverSet deliberately: a cover hit caches and would end the story at
    a transferred config, whereas this tier keeps the bucket uncached until
    the background job promotes a measured *exact* record. Returns ``None``
    (demoting to whatever follows) once the tuner stops accepting — a dead
    worker turns the pipeline into plain heuristic serving, never an error.
    """

    name = "bgtune"

    def __init__(self, tuner: BackgroundTuner):
        self.tuner = tuner

    def resolve(self, req: ResolutionRequest) -> Optional[Resolution]:
        if not self.tuner.offer(req):
            return None
        # cache=False is the hot-swap hook: every resolve of this bucket
        # re-runs the pipeline, so ExactHit wins the moment the promoted
        # record lands in the db.
        return Resolution(
            req.tunable.default_config(*req.args), self.name, cache=False
        )


def background_policy(tuner: BackgroundTuner) -> Tuple[ResolutionPolicy, ...]:
    """The serving pipeline for always-on dynamic tuning.

    ``(ExactHit, BackgroundTune, CoverSet, Heuristic, Reference)`` — no
    TuneNow: the whole point is that nothing tunes on the request path.
    CoverSet/Heuristic still terminate the chain when the tuner demotes.
    """
    return (ExactHit(), BackgroundTune(tuner), CoverSet(), Heuristic(), Reference())
