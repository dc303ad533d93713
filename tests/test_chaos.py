"""Chaos suite: seeded fault plans against the real serving / training /
campaign stacks, asserting the fault-isolation contracts end to end.

The acceptance gate for the dispatch guard: a kernel-mode engine with
injected kernel faults on every tunable the model dispatches (matmul,
rmsnorm, flash_attention) serves a request batch with outputs IDENTICAL to
a fault-free reference engine — the guard absorbs each fault at trace time,
quarantines the bucket, and bakes the reference implementation into the
compiled program, so degradation is invisible except in telemetry.

Everything here is deterministic: fault plans are seeded, traffic is
seeded, and every drill asserts exactly which faults fired.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.campaign import CampaignManifest, plan_jobs, run_campaign
from repro.campaign.scheduler import build_manifest
from repro.configs import get_config
from repro.core import Record, TunedRuntime, TuningDatabase
from repro.core.evaluate import Evaluator, Measurement
from repro.data.pipeline import DataConfig
from repro.distributed.sharding import Layout
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.models.transformer import RunConfig
from repro.obs.export import format_snapshot
from repro.optim import adamw
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.testing import FaultPlan, FaultRule
from repro.train.checkpoint import Checkpointer
from repro.train.trainer import Trainer, TrainerConfig

RUN = RunConfig(remat="none", loss_chunk=16, q_chunk=16, k_chunk=16)
MAX_SEQ = 64
# (prompt_len, max_new, prompt_seed) — a small mixed batch
SCHEDULE = ((3, 6, 0), (9, 5, 1), (12, 4, 2))
# every tunable the reduced qwen2 serving path dispatches in kernel mode
SERVING_TUNABLES = ("matmul", "rmsnorm", "flash_attention")


def _prompt(cfg, length, seed):
    rs = np.random.RandomState(10_000 + 17 * length + seed)
    return rs.randint(0, cfg.vocab_size, length).astype(np.int32)


def _serve_schedule(cfg, eng):
    for length, max_new, seed in SCHEDULE:
        assert eng.submit(Request(prompt=_prompt(cfg, length, seed),
                                  max_new_tokens=max_new))
    done = eng.serve()
    assert len(done) == len(SCHEDULE), "a request was lost to a fault"
    return [r.output for r in done]


@pytest.fixture(scope="module")
def served_ref():
    """Model + the fault-free reference baseline for SCHEDULE."""
    cfg = get_config("qwen2_0_5b").reduced()
    params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
    ref_eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=3, max_seq=MAX_SEQ),
        runtime=TunedRuntime(mode="reference", name="chaos-ref"),
    )
    return cfg, params, _serve_schedule(cfg, ref_eng)


# ---------------------------------------------------------------------------
# The serving gate: guarded dispatch under kernel faults
# ---------------------------------------------------------------------------


def test_guarded_engine_with_faulted_kernels_matches_reference(
    served_ref, tmp_path
):
    cfg, params, ref_out = served_ref
    rt = TunedRuntime(
        db=TuningDatabase(None), mode="kernel", guard=True, name="chaos-kern"
    )
    eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=3, max_seq=MAX_SEQ), runtime=rt,
    )
    plan = FaultPlan(
        [FaultRule(site=f"dispatch.kernel:{k}") for k in SERVING_TUNABLES],
        seed=1, name="serving-chaos",
    )
    col = obs.collect(name="chaos-serve")
    with col, plan:
        out = _serve_schedule(cfg, eng)

    # The contract: byte-for-byte the reference engine's tokens, no request
    # dropped, no exception surfaced to the caller — only telemetry knows.
    for got, want in zip(out, ref_out):
        np.testing.assert_array_equal(got, want)

    # Every serving tunable faulted at least once and was quarantined.
    assert {s.split(":")[1] for s, _, _ in plan.fired} == set(SERVING_TUNABLES)
    snap = rt.telemetry.snapshot()
    assert snap["tiers"].get("reference", 0) >= len(SERVING_TUNABLES)
    assert len(rt.health) >= len(SERVING_TUNABLES)
    quarantine_warns = [
        e for e in col.events("warning") if e["name"] == "dispatch.quarantine"
    ]
    assert quarantine_warns, "quarantine must be visible in the event log"
    assert all("InjectedFault" in e["error"] for e in quarantine_warns)

    # Satellite: the quarantine counter surfaces through every obs exporter.
    osnap = col.snapshot()
    assert "dispatch.quarantine" in osnap["counters"]
    assert "dispatch.quarantine" in format_snapshot(osnap)
    prom = str(tmp_path / "chaos.prom")
    col.write_prom(prom)
    with open(prom) as f:
        assert "dispatch_quarantine" in f.read()


def test_unguarded_fault_degrades_engine_not_requests(served_ref):
    """A fault the dispatch guard cannot absorb (guard=False: the operator
    opted out) escapes into the engine, which flips onto its reference
    fallback jits and still completes every request bit-identically."""
    cfg, params, ref_out = served_ref
    rt = TunedRuntime(
        db=TuningDatabase(None), mode="kernel", guard=False,
        name="chaos-unguarded",
    )
    eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=3, max_seq=MAX_SEQ), runtime=rt,
    )
    plan = FaultPlan([FaultRule(site="dispatch.kernel:*")], name="unguarded")
    col = obs.collect(name="chaos-degrade")
    with col, plan:
        out = _serve_schedule(cfg, eng)
    for got, want in zip(out, ref_out):
        np.testing.assert_array_equal(got, want)

    assert eng.degraded
    assert eng.stats["degraded_calls"] > 0
    assert any(e["name"] == "serve.degraded" for e in col.events("warning"))
    # sticky until an operator re-arms it
    eng.reset_degraded()
    assert not eng.degraded


def test_decode_fault_degrades_onto_reference_jit_that_picks_on_device(served_ref):
    """A fault escaping the decode step alone (its prefill bucket compiled
    before the plan) moves every tick onto the reference decode jit, which
    picks greedy tokens on the device as the kernel jit does."""
    cfg, params, ref_out = served_ref
    rt = TunedRuntime(
        db=TuningDatabase(None), mode="kernel", guard=False,
        name="chaos-decode",
    )
    eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=3, max_seq=MAX_SEQ), runtime=rt,
    )
    assert {eng._bucket_len(n) for n, _, _ in SCHEDULE} == {16}
    eng.submit(Request(prompt=_prompt(cfg, 3, 0), max_new_tokens=1))
    eng.serve()                                   # prefill compiled, no decode
    eng.reset_stats()
    plan = FaultPlan([FaultRule(site="dispatch.kernel:*")], name="decode-fault")
    with plan:
        out = _serve_schedule(cfg, eng)
    for got, want in zip(out, ref_out):
        np.testing.assert_array_equal(got, want)
    assert plan.fired and eng.degraded
    st = eng.stats
    assert st["degraded_calls"] == st["decode_steps"] > 0
    assert st["device_pick_ticks"] == st["decode_steps"]


def test_submit_sheds_with_structured_response_at_max_queue(served_ref):
    cfg, params, _ = served_ref
    eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=1, max_seq=MAX_SEQ, max_queue=1),
    )
    first = Request(prompt=_prompt(cfg, 3, 0), max_new_tokens=2)
    extra = Request(prompt=_prompt(cfg, 3, 1), max_new_tokens=2)
    col = obs.collect(name="chaos-shed")
    with col:
        assert eng.submit(first) is True
        assert eng.submit(extra) is False
    assert extra.shed and "queue_full" in extra.shed_reason
    assert not first.shed
    assert eng.stats["requests_shed"] == 1
    assert "serve.shed" in col.snapshot()["counters"]
    # the shed is backpressure, not corruption: the queued request serves
    (done,) = eng.serve()
    assert done is first and len(done.output) == 2


# ---------------------------------------------------------------------------
# Training: injected step faults recover to the fault-free trajectory
# ---------------------------------------------------------------------------

CFG_TRAIN = get_config("qwen2_0_5b").reduced()
DATA = DataConfig(seed=0, batch_size=8, seq_len=32)
OPT = adamw.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60, grad_clip=1.0)


def _make_trainer(tmp_path, steps):
    run = dataclasses.replace(RUN, microbatches=1)
    return Trainer(
        CFG_TRAIN, run, make_host_mesh(), Layout(), DATA, OPT,
        TrainerConfig(
            total_steps=steps, checkpoint_every=5,
            checkpoint_dir=str(tmp_path / "ckpt"), async_checkpoint=False,
        ),
    )


def test_injected_step_faults_recover_to_same_loss(tmp_path):
    steps = 10
    clean = _make_trainer(tmp_path / "clean", steps)
    clean_final = None
    for _ in range(steps):
        clean_final = clean.run_one_step()["loss"]

    chaotic = _make_trainer(tmp_path / "chaos", steps)
    plan = FaultPlan(
        [FaultRule(site="train.step:7", times=1, message="injected node loss")]
    )
    with plan:
        metrics = chaotic.train()
    assert plan.count("train.step:7") == 1, "the drill must actually fire"
    assert chaotic.step == steps
    # restore-and-replay reconverges on the uninterrupted trajectory
    assert abs(metrics["loss"] - clean_final) < 1e-5, (
        metrics["loss"], clean_final,
    )


# ---------------------------------------------------------------------------
# Checkpointer: async write failures surface on the training thread
# ---------------------------------------------------------------------------


def test_async_checkpoint_write_failure_surfaces_and_never_commits(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=5)
    tree = {"w": np.arange(8, dtype=np.float32)}
    # the write runs on the background thread: install(), don't scope
    plan = FaultPlan([
        FaultRule(site="checkpoint.write:2", message="disk full"),
        FaultRule(site="checkpoint.write:4", message="disk full again"),
    ])
    plan.install()
    try:
        ckpt.save_async(1, tree)
        ckpt.wait()                                   # step 1: fine
        ckpt.save_async(2, tree)
        with pytest.raises(RuntimeError, match="async checkpoint failed"):
            ckpt.wait()                               # surfaced, not swallowed
        assert ckpt.all_steps() == [1], "a failed write must never commit"
        # the NEXT save_async also surfaces a pending failure (it waits first)
        ckpt.save_async(4, tree)
        with pytest.raises(RuntimeError, match="async checkpoint failed"):
            ckpt.save_async(5, tree)
        assert plan.count("checkpoint.write:*") == 2
        # and the error is cleared once raised: the pipeline keeps going
        ckpt.save_async(6, tree)
        ckpt.wait()
        assert ckpt.all_steps() == [1, 6]
    finally:
        plan.uninstall()


# ---------------------------------------------------------------------------
# Campaign: retries, poison quarantine, timeouts, interrupt flush
# ---------------------------------------------------------------------------

_ARCHES = ["qwen2_0_5b"]
_PLAN_KW = dict(
    train_shapes=("train_4k",), serving=(2, 32), reduced=True,
    max_tokens=64, max_seq=32,
)


class SurrogateEvaluator(Evaluator):
    """Config-only objective: campaign mechanics without timing noise."""

    name = "surrogate"

    def evaluate(self, fn, args, reference=None):
        import math

        config = getattr(fn, "keywords", {})
        score = 0.05
        for v in config.values():
            if isinstance(v, (int, float)) and v > 0:
                score += abs(math.log2(v) - math.log2(64))
        return Measurement(score, True)


class InterruptingEvaluator(SurrogateEvaluator):
    """Delivers SIGINT (as KeyboardInterrupt) after N evaluations."""

    def __init__(self, after: int):
        self.after = after
        self.calls = 0

    def evaluate(self, fn, args, reference=None):
        self.calls += 1
        if self.calls > self.after:
            raise KeyboardInterrupt("operator ctrl-C")
        return super().evaluate(fn, args, reference)


def _mini_manifest(tmp_path, name, kernels=("rmsnorm",), budget=20):
    jobs = plan_jobs(_ARCHES, kernels=kernels, **_PLAN_KW)
    m = build_manifest(jobs, total_budget=10_000, path=str(tmp_path / name))
    for j in m.jobs:
        j.budget = budget
    m.save()
    return m


def test_job_retry_then_succeed_banks_attempts(tmp_path):
    m = _mini_manifest(tmp_path, "m.json")
    db = TuningDatabase(str(tmp_path / "db.json"))
    with FaultPlan([FaultRule(site="campaign.job:*", times=1)]) as plan:
        run_campaign(m, db, evaluator=SurrogateEvaluator(), max_jobs=1,
                     max_attempts=3)
    assert plan.count("campaign.job:*") == 1
    done = [j for j in m.jobs if j.status == "done"]
    assert len(done) == 1 and done[0].attempts == 2 and done[0].error == ""
    # persisted: a resume sees the banked attempt count
    m2 = CampaignManifest.load(str(tmp_path / "m.json"))
    assert [j.attempts for j in m2.jobs if j.status == "done"] == [2]


def test_job_exhausting_attempts_is_poisoned_and_resume_skips_it(tmp_path):
    m = _mini_manifest(tmp_path, "m.json")
    db = TuningDatabase(str(tmp_path / "db.json"))
    n_jobs = len(m.jobs)
    col = obs.collect(name="chaos-campaign")
    with col, FaultPlan([FaultRule(site="campaign.job:*")]) as plan:
        summary = run_campaign(m, db, evaluator=SurrogateEvaluator(),
                               max_jobs=1, max_attempts=2)
    assert plan.count("campaign.job:*") == 2          # both attempts failed
    assert summary["poisoned"] == 1
    poisoned = [j for j in m.jobs if j.status == "poisoned"]
    assert len(poisoned) == 1
    assert poisoned[0].attempts == 2
    assert "InjectedFault" in poisoned[0].error
    assert any(e["name"] == "campaign.job_poisoned"
               for e in col.events("warning"))

    # fault cleared, campaign resumed: the poison pill is never re-run
    m2 = CampaignManifest.load(str(tmp_path / "m.json"))
    assert m2.counts()["poisoned"] == 1
    summary = run_campaign(m2, TuningDatabase(str(tmp_path / "db.json")),
                           evaluator=SurrogateEvaluator())
    assert summary["done"] == n_jobs - 1
    assert summary["poisoned"] == 1


def test_job_timeout_bounds_a_stuck_job(tmp_path):
    m = _mini_manifest(tmp_path, "m.json")
    db = TuningDatabase(str(tmp_path / "db.json"))
    # first attempt of the first job hangs (well past the timeout); with a
    # job_timeout the attempt body runs on a worker thread, so the plan must
    # be installed process-globally, not contextvar-scoped
    plan = FaultPlan(
        [FaultRule(site="campaign.job:*", kind="latency", delay_s=1.5, times=1)]
    )
    plan.install()
    try:
        run_campaign(m, db, evaluator=SurrogateEvaluator(), max_jobs=1,
                     job_timeout=0.2, max_attempts=1)
    finally:
        plan.uninstall()
    stuck = [j for j in m.jobs if j.status == "poisoned"]
    assert len(stuck) == 1
    assert "exceeded --job-timeout" in stuck[0].error


def test_keyboard_interrupt_flushes_manifest_and_telemetry(tmp_path):
    m = _mini_manifest(tmp_path, "m.json")
    db = TuningDatabase(str(tmp_path / "db.json"))
    with pytest.raises(KeyboardInterrupt):
        run_campaign(m, db, evaluator=InterruptingEvaluator(after=3))

    # the manifest on disk reflects the interrupt exactly: nothing done,
    # the in-flight job still pending with its attempt banked, telemetry
    # and the interrupted marker flushed for the post-mortem.
    m2 = CampaignManifest.load(str(tmp_path / "m.json"))
    assert m2.counts()["done"] == 0
    inflight = [j for j in m2.jobs if j.attempts > 0]
    assert len(inflight) == 1 and inflight[0].status == "pending"
    assert m2.meta.get("interrupted")       # stamped (interrupt timestamp)
    assert "telemetry" in m2.meta

    # resume runs to completion, re-running the interrupted job
    summary = run_campaign(m2, TuningDatabase(str(tmp_path / "db.json")),
                           evaluator=SurrogateEvaluator())
    assert summary["done"] == len(m2.jobs) and summary["poisoned"] == 0
    assert [j for j in m2.jobs if j.attempts == 2]    # the replayed one
