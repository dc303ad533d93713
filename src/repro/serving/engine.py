"""Continuous-batching serving engine: slot pool, in-flight admission,
per-slot completion.

The engine owns a fixed pool of ``max_batch`` *slots*. Each slot is one
batch row of a shared cache pytree (allocated once at ``max_seq`` capacity)
plus host-side per-slot state: the request occupying it, its absolute
position, its sampling RNG, and the tokens emitted so far. The serve loop
is::

    admit   — while a slot is free and a request has arrived, right-pad its
              prompt to a power-of-two bucket, prefill it at batch 1, and
              *insert* the fresh cache into the slot (a full overwrite —
              nothing from the previous occupant survives);
    decode  — ONE jitted step over the whole pool per tick, with a per-slot
              position vector; inactive slots decode a dummy token that is
              never read;
    retire  — a slot whose request hit its own ``max_new_tokens`` is freed
              immediately and the next queued request is admitted mid-flight,
              while the other slots keep decoding.

Compare :class:`LockStepEngine` (the old static batcher, kept for
regression benchmarks): it packs a whole batch, decodes until *every*
member finishes, and only then admits new traffic. On skewed workloads the
slot pool strictly reduces total decode steps (see
``tests/test_serving_throughput.py`` and ``benchmarks/serving_throughput.py``).

jit-key invariant: admission prefills compile one (1, seq-bucket) key per
power-of-two bucket and decode compiles ONE (max_batch,) pool key — exactly
the slot-pool buckets ``campaign.planner.serving_buckets`` enumerates, so a
campaign-exported per-platform database warmed via :meth:`ServingEngine.warmup`
keeps hitting while the batch composition changes continuously. Database
bucket keys are unchanged from the static engine (same ``shape_bucket``
discipline), so existing campaign exports stay valid.

Equivalence contract: greedy (and seeded-temperature) outputs are
token-for-token identical to running each request alone, for any arrival
pattern — causal masking keeps right-pad tokens out of real positions,
window caches are ring-aligned to the true prompt length, and decode masks
each slot's unwritten cache rows (property-tested in
``tests/test_serving_continuous.py``). Archs with SSM mixers prefill at the
exact prompt length instead (a state polluted by pad tokens cannot be
masked after the fact); MoE archs need capacity headroom, as ever, since
expert capacity couples batch rows.

Timing: the engine has a virtual tick clock (1 tick = one pool decode
step; ``Request.arrival_time`` is in ticks) for deterministic scheduling
tests, and an injectable wall clock for latency. ``latency_s`` measures
admission → the request's own last token, so late-admitted requests are
not charged for time they spent unqueued or for earlier occupants' work;
``submitted_s`` and ``first_token_s`` are read off the same clock, so
``first_token_s - submitted_s`` is the time to first token.

Spans (``repro.obs``; free when no collector is enabled), all on the
thread that calls :meth:`ServingEngine.serve`, and never overlapping at the
top level:

    serve.admit   — one admission: prefill, the first token's sample (which
                    waits for the prefill to run) and the cache insert's
                    enqueue; tags ``request`` (submission order), ``slot``,
                    ``prompt_len``, ``bucket``;
    serve.tick    — one pool decode step, from building its inputs to the
                    last retirement; tags ``tick`` (``decode_steps`` before
                    it), ``active``, ``queued``; its children:
      serve.decode  — building the token/position arrays and enqueueing the
                      decode step;
      serve.fetch   — the step's picked token ids to the host (waits for the
                      decode step); the logits too only when an active slot
                      samples (``temperature > 0``);
      serve.sample  — per-slot sampling and retirement.

Greedy picks are made on the device: the prefill and decode jits return
``argmax(logits, -1)`` beside the logits (the first index of the maximum,
as ``np.argmax`` picks it), so a tick of greedy slots copies ``max_batch``
ids, not the pool's logits. Every served token still comes out of
:func:`_sample_one`; a greedy slot hands it a :class:`_Picked` row, whose
argmax the device already took. ``stats["device_pick_ticks"]`` counts the
ticks that copied ids only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.database import shape_bucket
from ..core.runtime import TunedRuntime
from ..distributed import sharding as shd
from ..models import lm
from ..models.transformer import RunConfig
from ..obs.collect import current_collector as _obs_collector
from ..obs.trace import span as _obs_span


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # [len] int32
    max_new_tokens: int = 16
    temperature: float = 0.0        # 0 = greedy
    seed: int = 0
    arrival_time: float = 0.0       # engine ticks (decode steps); 0 = already here
    # filled by the engine:
    output: Optional[np.ndarray] = None
    submitted_s: float = 0.0        # engine clock at submit()
    first_token_s: float = 0.0      # engine clock when the first token was sampled
    latency_s: float = 0.0          # admission -> THIS request's last token (wall)
    latency_steps: int = 0          # admission -> last token, in decode ticks
    queue_steps: int = 0            # arrival -> admission, in decode ticks
    admitted_step: int = -1
    finished_step: int = -1
    slot: int = -1
    # admission backpressure (structured shed response): submit() refused
    # this request because the engine queue was at EngineConfig.max_queue.
    shed: bool = False
    shed_reason: str = ""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8              # slot-pool width (= the one decode jit key)
    max_seq: int = 256              # per-slot cache capacity (prefill + decode)
    min_prefill_bucket: int = 16    # smallest admission-prefill seq bucket
    max_queue: int = 0              # bounded admission queue (0 = unbounded):
    #                                 past this depth submit() sheds instead of
    #                                 queueing — backpressure, not OOM


def _with_ids(out):
    """(logits, caches) -> (logits, caches, ids): ``ids`` [B] int32 is the
    greedy pick of each row, made on the device."""
    logits, caches = out
    return logits, caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)


class _Picked:
    """A row of logits as far as a greedy pick reads it: the index of its
    maximum, which the device already took, and its length."""

    __slots__ = ("index", "size")

    def __init__(self, index: int, size: int):
        self.index, self.size = index, size

    def argmax(self) -> int:
        return self.index

    def __len__(self) -> int:
        return self.size


def _sample_one(logits_row, req: Request, rng) -> int:
    """The next token of one request from its row of logits (a host array,
    or a :class:`_Picked` row for a greedy request)."""
    if req.temperature <= 0:
        return int(logits_row.argmax())
    z = logits_row / req.temperature
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


@dataclasses.dataclass
class _Slot:
    req: Request
    rng: Any
    cur: int                        # next token to feed
    pos: int                        # absolute position `cur` will occupy
    max_new: int
    emitted: List[int]
    t_admit: float


class ServingEngine:
    """Slot-pool continuous-batching engine (see module docstring)."""

    def __init__(
        self,
        cfg: ArchConfig,
        run: RunConfig,
        params,
        mesh: jax.sharding.Mesh,
        layout: shd.Layout,
        ecfg: EngineConfig = EngineConfig(),
        clock: Callable[[], float] = time.perf_counter,
        runtime: Optional[TunedRuntime] = None,
    ):
        if cfg.frontend is not None:
            raise NotImplementedError(
                "the engine serves token-in/token-out archs; frontend "
                "archs need an embedding service in front"
            )
        self.cfg, self.run, self.ecfg = cfg, run, ecfg
        self.params = params
        self.mesh, self.layout = mesh, layout
        self.clock = clock
        # Engine-pinned dispatch runtime: every prefill/decode trace (and
        # warmup resolution) runs under this scope, so the engine's db/mode
        # and telemetry are isolated from other engines and from tests.
        # None = legacy behavior: dispatch reads whatever runtime is ambient
        # at serve time.
        self.runtime = runtime
        self._has_ssm = any(
            spec.mixer != "attn" for seg in cfg.segments() for spec in seg.pattern
        )
        self._prefill = jax.jit(
            lambda p, toks, L: _with_ids(lm.prefill(
                p, {"tokens": toks}, cfg, run, cache_len=ecfg.max_seq, true_len=L
            ))
        )
        self._decode = jax.jit(
            lambda p, t, c, pos: _with_ids(lm.decode_step(p, t, c, pos, cfg, run))
        )
        self._insert = jax.jit(lm.insert_cache)
        self._caches = lm.init_cache(cfg, ecfg.max_batch, ecfg.max_seq)
        self._slots: List[Optional[_Slot]] = [None] * ecfg.max_batch
        self.queue: List[Request] = []
        self._order = 0
        # Graceful degradation: a fault escaping a prefill/decode call (one
        # the dispatch guard could not absorb — e.g. an unguarded runtime, or
        # a failure outside any dispatch site) flips the engine onto separate
        # reference-path jits; sticky until reset_degraded(). Lazy: the
        # fallback jits and their pinned reference-mode runtime are only
        # built on first fault.
        self.degraded = False
        self._ref_rt: Optional[TunedRuntime] = None
        self._prefill_ref = None
        self._decode_ref = None
        self.reset_stats()

    # ----------------------------------------------------------------- stats
    def reset_stats(self) -> None:
        self.stats: Dict[str, int] = {
            "decode_steps": 0,        # pool decode invocations (= ticks)
            "device_pick_ticks": 0,   # ticks that copied the picked ids only
            "prefill_calls": 0,
            "prefill_tokens": 0,      # padded (bucketed) prefill tokens
            "slot_steps_active": 0,   # slot·steps that produced a kept token
            "slot_steps_idle": 0,     # slot·steps burned on empty slots
            "tokens_out": 0,
            "requests_shed": 0,       # submissions refused at max_queue
            "degraded_calls": 0,      # prefill/decode calls served by the
            #                           reference fallback after a fault
        }

    def _scope(self):
        """The engine's runtime scope (no-op when no runtime is pinned)."""
        return self.runtime if self.runtime is not None else contextlib.nullcontext()

    # --------------------------------------------------------- degraded path
    def reset_degraded(self) -> None:
        """Re-arm the kernel path after an operator fixed the fault."""
        self.degraded = False

    def _note_degraded(self, site: str, exc: Exception) -> None:
        self.degraded = True
        col = _obs_collector()
        if col.enabled:
            col.counter("serve.degraded", site=site)
        # warn_once fires even with metrics off — a silently-degraded engine
        # is the hazard class this plane exists for.
        col.warn_once(
            "serve.degraded", key=site, site=site,
            error=f"{type(exc).__name__}: {exc}",
        )

    def _ref_scope(self):
        """Pinned reference-mode runtime for the fallback jits (lazy).

        jit specializes on shapes, not on ambient contextvars — the fallback
        needs its OWN jit objects, traced under a reference-mode scope, or it
        would reuse the kernel-path executable and re-fault identically.
        """
        if self._ref_rt is None:
            with self._scope():
                # Construction-time inheritance picks up the engine runtime's
                # db/platform; only the mode flips.
                self._ref_rt = TunedRuntime(mode="reference", name="engine-degraded")
        return self._ref_rt

    def _run_prefill(self, toks, L):
        if not self.degraded:
            try:
                with self._scope():
                    return self._prefill(self.params, toks, L)
            except Exception as e:  # fault mid-admission: demote, complete
                self._note_degraded("prefill", e)
        self.stats["degraded_calls"] += 1
        if self._prefill_ref is None:
            cfg, run, ecfg = self.cfg, self.run, self.ecfg
            self._prefill_ref = jax.jit(
                lambda p, t, n: _with_ids(lm.prefill(
                    p, {"tokens": t}, cfg, run, cache_len=ecfg.max_seq, true_len=n
                ))
            )
        with self._scope(), self._ref_scope():
            return self._prefill_ref(self.params, toks, L)

    def _run_decode(self, tokens, pos):
        if not self.degraded:
            try:
                with self._scope():
                    return self._decode(self.params, tokens, self._caches, pos)
            except Exception as e:  # fault mid-tick: demote, complete the tick
                self._note_degraded("decode", e)
        self.stats["degraded_calls"] += 1
        if self._decode_ref is None:
            cfg, run = self.cfg, self.run
            self._decode_ref = jax.jit(
                lambda p, t, c, q: _with_ids(lm.decode_step(p, t, c, q, cfg, run))
            )
        # self._caches is only reassigned from a call that RETURNED, so the
        # retry reruns the identical inputs — completed requests stay
        # bit-identical to a fault-free run (the equivalence contract).
        with self._scope(), self._ref_scope():
            return self._decode_ref(self.params, tokens, self._caches, pos)

    # ----------------------------------------------------------------- queue
    def submit(self, req: Request) -> bool:
        """Queue a request; returns False (with a structured shed response
        on the request) when admission backpressure refuses it."""
        L = len(req.prompt)
        if not 1 <= L < self.ecfg.max_seq:
            raise ValueError(
                f"prompt length {L} not in [1, max_seq={self.ecfg.max_seq})"
            )
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.ecfg.max_queue > 0 and len(self.queue) >= self.ecfg.max_queue:
            req.shed = True
            req.shed_reason = (
                f"queue_full: depth {len(self.queue)} at "
                f"max_queue={self.ecfg.max_queue}"
            )
            self.stats["requests_shed"] += 1
            col = _obs_collector()
            if col.enabled:
                col.counter("serve.shed", reason="queue_full")
            return False
        req._order = self._order          # submission order, for serve()'s return
        req.submitted_s = self.clock()
        self._order += 1
        self.queue.append(req)
        return True

    def _bucket_len(self, prompt_len: int) -> int:
        if self._has_ssm:
            # SSM state integrates every input token — pad tokens cannot be
            # masked out after the fact, so SSM archs prefill exact-length.
            return prompt_len
        b = max(self.ecfg.min_prefill_bucket, shape_bucket((prompt_len,))[0])
        return min(b, self.ecfg.max_seq)

    # ------------------------------------------------------------- admission
    def _admit(self, req: Request, slot: int, now: int, done: List[Request]) -> None:
        L = len(req.prompt)
        sb = self._bucket_len(L)
        with _obs_span("serve.admit", request=req._order, slot=slot,
                       prompt_len=L, bucket=sb):
            toks = np.zeros((1, sb), np.int32)
            toks[0, :L] = req.prompt
            logits, cache, ids = self._run_prefill(
                jnp.asarray(toks), jnp.asarray(L, jnp.int32)
            )
            self.stats["prefill_calls"] += 1
            self.stats["prefill_tokens"] += sb

            req.admitted_step = now
            req.queue_steps = max(0, now - int(np.ceil(req.arrival_time)))
            req.slot = slot
            t_admit = self.clock()
            rng = np.random.default_rng(req.seed)
            if req.temperature <= 0:
                row = _Picked(int(np.asarray(ids)[0]), logits.shape[-1])
            else:
                row = np.asarray(logits, np.float32)[0]
            first = _sample_one(row, req, rng)
            req.first_token_s = self.clock()
            col = _obs_collector()
            if col.enabled:
                col.counter("serve.requests")
            max_new = min(req.max_new_tokens, self.ecfg.max_seq - L)
            state = _Slot(req=req, rng=rng, cur=first, pos=L, max_new=max_new,
                          emitted=[first], t_admit=t_admit)
            if len(state.emitted) >= max_new:
                self._finish(state, now)      # one-token request: never occupies
                done.append(req)
                return
            self._caches = self._insert(self._caches, cache, jnp.asarray(slot, jnp.int32))
            self._slots[slot] = state

    def _finish(self, state: _Slot, now: int) -> None:
        req = state.req
        req.output = np.asarray(state.emitted, np.int32)
        req.finished_step = now
        req.latency_steps = now - req.admitted_step
        req.latency_s = self.clock() - state.t_admit
        self.stats["tokens_out"] += len(state.emitted)
        col = _obs_collector()
        if col.enabled:
            n = len(state.emitted)
            col.observe("serve.latency_s", req.latency_s)
            if n:
                col.observe("serve.per_token_s", req.latency_s / n)
                col.counter("serve.tokens", n)

    # ----------------------------------------------------------------- serve
    def serve(self) -> List[Request]:
        """Run until the queue drains; return requests in submission order."""
        pending = sorted(self.queue, key=lambda r: r.arrival_time)
        self.queue = []
        done: List[Request] = []
        now = 0
        B = self.ecfg.max_batch

        def active() -> int:
            return sum(s is not None for s in self._slots)

        while pending or active():
            if not active() and pending and pending[0].arrival_time > now:
                now = int(np.ceil(pending[0].arrival_time))
            # in-flight admission: fill every free slot with arrived traffic
            free = [i for i in range(B) if self._slots[i] is None]
            while free and pending and pending[0].arrival_time <= now:
                i = free.pop(0)
                self._admit(pending.pop(0), i, now, done)
                if self._slots[i] is None:   # finished at admission: reusable
                    free.append(i)
            n_act = active()
            if not n_act:
                continue

            with _obs_span("serve.tick", tick=self.stats["decode_steps"],
                           active=n_act, queued=len(pending)):
                with _obs_span("serve.decode"):
                    tokens = np.zeros((B, 1), np.int32)
                    pos = np.zeros((B,), np.int32)
                    for i, s in enumerate(self._slots):
                        if s is not None:
                            tokens[i, 0] = s.cur
                            pos[i] = s.pos
                    logits, self._caches, ids = self._run_decode(
                        jnp.asarray(tokens), jnp.asarray(pos)
                    )
                self.stats["decode_steps"] += 1
                self.stats["slot_steps_active"] += n_act
                self.stats["slot_steps_idle"] += B - n_act
                now += 1
                sampling = any(s is not None and s.req.temperature > 0
                               for s in self._slots)
                with _obs_span("serve.fetch"):
                    ids_np = np.asarray(ids)
                    if sampling:
                        logits_np = np.asarray(logits, np.float32)
                if not sampling:
                    self.stats["device_pick_ticks"] += 1
                with _obs_span("serve.sample"):
                    for i, s in enumerate(self._slots):
                        if s is None:
                            continue
                        row = (logits_np[i] if s.req.temperature > 0
                               else _Picked(int(ids_np[i]), logits.shape[-1]))
                        nxt = _sample_one(row, s.req, s.rng)
                        s.emitted.append(nxt)
                        s.pos += 1
                        s.cur = nxt
                        if len(s.emitted) >= s.max_new:
                            self._finish(s, now)
                            done.append(s.req)
                            self._slots[i] = None     # freed: next arrival admits here
        return sorted(done, key=lambda r: r._order)

    # ---------------------------------------------------------------- warmup
    def serving_buckets(self) -> List[tuple]:
        """The (batch, seq-bucket) jit/db keys this engine can hit."""
        from ..campaign.planner import serving_buckets

        return serving_buckets(self.ecfg.max_batch, self.ecfg.max_seq,
                               min_seq=self.ecfg.min_prefill_bucket)

    def warmup(
        self,
        db=None,
        allow_tune: bool = False,
        install: bool = True,
        max_tokens: int = 65536,
        **tune_kwargs,
    ) -> Dict[str, Dict]:
        """Pre-resolve kernel configs for every slot-pool bucket this engine serves.

        This is the deployment end of a tuning campaign: pair the generic
        engine with a campaign-exported per-platform database and every
        admission-prefill (1, seq-bucket) and decode-pool (max_batch,) key
        the engine will jit resolves its kernel configs up front through the
        engine's dispatch runtime — its resolution cache is hot and its
        telemetry records which tier (exact / cover / heuristic / ...)
        serves each bucket, so no request pays resolution or heuristic-miss
        cost mid-flight. With `allow_tune=True` missing buckets are tuned on
        the spot instead (an online mini-campaign for this engine only).

        Database plumbing: with an engine-pinned runtime, a passed `db` is
        pinned on that runtime (scoped — nothing global is touched, and
        `install` is ignored). Without one, the legacy behavior holds:
        `install=True` makes `db` the process-wide default, because serve-
        time dispatch then reads the ambient runtime, whose database is
        ``default_db()`` — warming one database while serving reads another
        would silently waste the artifact.

        Returns {db_key: resolved config} for observability (``None`` for a
        bucket a custom policy pipeline routed to reference execution).
        """
        from ..core.annotate import get_tunable
        from ..core.database import default_db, set_default_db
        from ..core.runtime import current_runtime
        from ..core.platform import detect_platform
        from ..campaign.planner import plan_serving_jobs
        from ..campaign.runner import materialize_args

        rt = self.runtime
        if rt is not None:
            if db is not None and db is not rt.db:
                # Buckets resolved under the previous database are stale;
                # the db-identity check in resolve() would skip them anyway,
                # but dropping them keeps cache_size honest.
                rt.db = db
                rt.clear_cache()
        else:
            if db is not None and install:
                set_default_db(db)
            # Serve-time dispatch will read the ambient runtime; warm that
            # same runtime so its resolution cache actually gets hit.
            rt = current_runtime()
            if db is not None:
                effective = rt.db if rt.db is not None else default_db()
                if effective is not db:
                    # install=False, or warmup invoked inside a scope pinned
                    # to some other database: the caller asked for *this*
                    # artifact, so resolve against it on an ephemeral scoped
                    # runtime (serve-time caching is forfeit by construction
                    # here — the served db is a different one).
                    rt = TunedRuntime(db=db, name="warmup")

        platform = detect_platform().name
        jobs = plan_serving_jobs(
            self.cfg, self.ecfg.max_batch, self.ecfg.max_seq,
            max_tokens=max_tokens,
        )
        if allow_tune:
            # Cached resolutions would shadow TuneNow for already-seen
            # buckets; the caller asked for an online mini-campaign.
            rt.clear_cache()
        resolved: Dict[str, Dict] = {}
        for job in jobs:
            key = job.db_key(platform)
            if key in resolved:
                continue
            tunable = get_tunable(job.kernel)
            args = materialize_args(job)
            # Per-call permission grant: never mutates the runtime, which
            # other serving threads may be dispatching through right now.
            res = rt.resolve(
                tunable, args, key_extra=job.key_extra,
                allow_tune=allow_tune or None,
                tune_kwargs=tune_kwargs or None,
            )
            resolved[key] = res.config
        return resolved


class LockStepEngine:
    """The old static batcher, kept as the regression baseline.

    Packs up to ``max_batch`` queued requests, left-pads to a shared prefill
    length, then decodes lock-step until the *longest* member finishes; new
    traffic waits for the whole batch. ``stats["decode_steps"]`` counts the
    same unit as the continuous engine, so the two are directly comparable.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        run: RunConfig,
        params,
        mesh: jax.sharding.Mesh,
        layout: shd.Layout,
        ecfg: EngineConfig = EngineConfig(),
        clock: Callable[[], float] = time.perf_counter,
    ):
        if cfg.frontend is not None:
            raise NotImplementedError("token-in/token-out archs only")
        self.cfg, self.run, self.ecfg = cfg, run, ecfg
        self.params = params
        self.mesh, self.layout = mesh, layout
        self.clock = clock
        self._prefill = jax.jit(
            lambda p, b: lm.prefill(p, b, cfg, run, cache_len=ecfg.max_seq)
        )
        self._decode = jax.jit(
            lambda p, t, c, pos: lm.decode_step(p, t, c, pos, cfg, run)
        )
        self.queue: List[Request] = []
        self.stats: Dict[str, int] = {"decode_steps": 0, "tokens_out": 0}

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def run_batch(self, reqs: List[Request]) -> List[Request]:
        t0 = self.clock()
        B = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        logits, caches = self._prefill(self.params, {"tokens": jnp.asarray(toks)})
        max_new = min(max(r.max_new_tokens for r in reqs), self.ecfg.max_seq - plen)

        outs = np.zeros((B, max_new), np.int32)
        rngs = [np.random.default_rng(r.seed) for r in reqs]
        cur = np.asarray(
            [_sample_one(np.asarray(logits, np.float32)[i], r, rngs[i])
             for i, r in enumerate(reqs)], np.int32)
        done_at = np.zeros((B,), np.float64)
        for step in range(max_new):
            outs[:, step] = cur
            t_now = self.clock() - t0
            for i, r in enumerate(reqs):
                if r.max_new_tokens == step + 1:
                    done_at[i] = t_now
            pos = jnp.asarray(plen + step, jnp.int32)
            logits, caches = self._decode(
                self.params, jnp.asarray(cur)[:, None], caches, pos
            )
            self.stats["decode_steps"] += 1
            cur = np.asarray(
                [_sample_one(np.asarray(logits, np.float32)[i], r, rngs[i])
                 for i, r in enumerate(reqs)], np.int32)

        dt = self.clock() - t0
        for i, r in enumerate(reqs):
            r.output = outs[i, : r.max_new_tokens]
            r.latency_s = float(done_at[i]) if done_at[i] > 0 else dt
            self.stats["tokens_out"] += len(r.output)
        return reqs

    def serve(self) -> List[Request]:
        """Drain the queue in max_batch groups (arrival times ignored)."""
        done: List[Request] = []
        while self.queue:
            batch, self.queue = (
                self.queue[: self.ecfg.max_batch],
                self.queue[self.ecfg.max_batch:],
            )
            done.extend(self.run_batch(batch))
        return done
