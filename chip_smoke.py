#!/usr/bin/env python3
"""Bring-up check: the tuned serve and train path on TPU at Qwen2-0.5B's
published widths (24 layers, d_model 896, 14/2 heads of 64, d_ff 4864,
vocab 151936, bf16), random weights drawn from ``--seed``.

    python3 chip_smoke.py            # one chip: serve, tune, train
    python3 chip_smoke.py --chips 4  # four chips: data-parallel training
                                     # against the same batch on one device

Everything runs in this one process (a chip belongs to one process), through
the launchers' own builders (``repro.launch.serve`` / ``repro.launch.train``),
``ServingEngine``, ``Trainer`` and ``TunedRuntime`` in kernel mode, with the
dispatch guard off and an empty in-memory tuning database, so nothing can
fall back quietly. Phases:

* serve — 8 staggered requests, prompts of 16..300 tokens. The prefill
  logits of the shortest and the longest prompt are checked against the
  reference-mode runtime on the same weights, both judged by a float32
  oracle (the reference path on the weights upcast to f32).
* tune  — a campaign over ``matmul`` and ``rmsnorm`` at the buckets the serve
  phase hit (40 evaluations), exported, then served again pinned to the
  export: every tuned bucket must resolve at the ``exact`` tier.
  A strict evaluator turns any variant that fails to run or fails the
  correctness gate into a failed job, so no tuning trial fails silently.
* train — 3 steps of 8 x 1024 tokens; the losses must be finite and step 1's
  must match the reference-mode loss on the same weights and batch. Then the
  gradient of one microbatch is checked leaf by leaf, kernel path and bf16
  reference path both judged by a float32 oracle, as the logits are.
* train_dp (``--chips 4`` only) — 3 steps of 16 x 512 tokens on a 4x1
  data-parallel mesh, each step's loss and gradient norm matching the same
  steps on one device (a gradient that misses the sum over chips changes
  the norm).

Every phase also requires: no ``reference``-tier dispatch, no degraded engine
call, no quarantined bucket, no poisoned campaign job, a finite best
objective per job, and that every Pallas call traced into the prefill,
decode and train-step programs is a Mosaic kernel: none runs in interpret
mode, and each lowers to one ``tpu_custom_call``. The last line of stdout is one JSON
object; the exit code is 0 only if every phase passed. Without a TPU the
script exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2-0.5b"
# Tolerances (bf16 weights and activations, f32 accumulation on both paths,
# different tilings). Prefill logits are judged against a float32 oracle —
# the reference path on the same weights upcast to f32 at highest matmul
# precision: the kernel path may be off it by at most LOGITS_VS_BF16 times
# what the bf16 reference path is off it, and never by more than
# LOGITS_REL_L2_MAX. (On a TPU v5e the kernel and bf16 reference paths are
# 1.9e-2 to 2.2e-2 apart in rel L2 at these widths, each ~1.5e-2 off f32, so
# neither can be held to the other.)
LOGITS_VS_BF16 = 2.0
LOGITS_REL_L2_MAX = 0.1
# Step-1 loss, kernel vs reference mode, relative (3.6e-6 measured on a v5e).
LOSS_REL = 1e-4
# Gradients of one microbatch, per parameter leaf, judged like the logits:
# the kernel path off a float32 oracle by at most GRAD_VS_BF16 times what the
# bf16 reference path is off it (or GRAD_REL_L2_MIN, where the reference is
# exact, as in float32), and never by more than GRAD_REL_L2_MAX. A wrong
# backward kernel puts a leaf off by order one.
GRAD_VS_BF16 = 2.0
GRAD_REL_L2_MIN = 1e-3
GRAD_REL_L2_MAX = 0.5
# 4-chip vs 1-device, every step: losses (<= 1.3e-5 measured on v5e) and the
# global gradient norm, which a missing sum over chips cuts by half or more.
DP_LOSS_REL = 1e-4
DP_GRAD_NORM_REL = 1e-3


class PhaseFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailure(msg)


def log(msg):
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its own events)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration_secs, **kwargs):
        if event in self.EVENTS:
            self.total += duration_secs


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def tiers_by_site(rt):
    """{db key: {tier: count}} of one runtime, '|*' rows included."""
    return rt.telemetry.snapshot()["by_key"]


def check_runtime_clean(rt, label):
    """No reference-tier dispatch and no quarantine on a kernel-mode runtime."""
    rows = tiers_by_site(rt)
    check(rows, f"{label}: no dispatch was recorded")
    ref = sorted(k for k, per in rows.items() if per.get("reference"))
    check(not ref, f"{label}: reference-tier dispatches at {ref}")
    check(len(rt.health) == 0,
          f"{label}: quarantined buckets {sorted(rt.health.snapshot())}")


def report_tiers(rt, label):
    """Which tier served each kernel's sites, summed over its buckets."""
    kernels = {}
    for key, per in tiers_by_site(rt).items():
        tiers = kernels.setdefault(key.split("|")[0], {})
        for tier, n in per.items():
            tiers[tier] = tiers.get(tier, 0) + n
    for kernel in sorted(kernels):
        log(f"  {label} tier per site: {kernel}: {kernels[kernel]}")


def pallas_calls(jaxpr, seen=None):
    """Every pallas_call equation of a jaxpr and of its sub-jaxprs. A
    sub-jaxpr shared by several equations (one jit called twice) is walked
    once, as it lowers to one function."""
    seen = set() if seen is None else seen
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)          # ClosedJaxpr
                if hasattr(sub, "eqns") and id(sub) not in seen:
                    seen.add(id(sub))
                    yield from pallas_calls(sub, seen)


def check_mosaic(jitted, args, label):
    """Every Pallas call traced into the program runs as a Mosaic kernel:
    none in interpret mode (which would lower to plain HLO), and the
    lowered program holds one ``tpu_custom_call`` per call."""
    traced = jitted.trace(*args)
    calls = list(pallas_calls(traced.jaxpr.jaxpr))
    check(calls, f"{label}: no Pallas call traced (kernels not dispatched)")
    interp = sorted({e.params["name"] for e in calls if e.params["interpret"]})
    check(not interp, f"{label}: Pallas calls in interpret mode: {interp}")
    n = traced.lower().as_text().count("tpu_custom_call")
    check(n == len(calls), f"{label}: {len(calls)} Pallas calls traced but "
                           f"{n} tpu_custom_call in the lowered program")
    log(f"  {label}: {n} Pallas calls traced, each a Mosaic kernel")


def rel_l2(a, b):
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve_args(seed):
    from repro.launch import serve

    return serve.parser().parse_args([
        "--arch", ARCH, "--mesh", "1x1", "--mode", "kernel",
        "--max-seq", "512", "--max-prompt", "300",
        "--requests", "8", "--new-tokens", "8", "--seed", str(seed),
    ])


def kernel_runtime(db, name):
    import repro

    return repro.runtime(db=db, mode="kernel", guard=False, name=name)


def run_serve(args, rt, label):
    """Serve the launcher's requests; returns (cfg, engine, done)."""
    import jax.numpy as jnp

    from repro.launch import serve

    cfg, engine, rt = serve.build(args, runtime=rt)
    reqs = serve.make_requests(cfg, args)
    for r in reqs:
        engine.submit(r)
    done = engine.serve()
    check(len(done) == len(reqs), f"{label}: {len(done)} of {len(reqs)} served")
    for r in done:
        check(r.output is not None and len(r.output) == args.new_tokens,
              f"{label}: request of {len(r.prompt)} tokens emitted "
              f"{None if r.output is None else len(r.output)} tokens")
        check(((r.output >= 0) & (r.output < cfg.vocab_size)).all(),
              f"{label}: token out of vocabulary")
    check(engine.stats["degraded_calls"] == 0,
          f"{label}: {engine.stats['degraded_calls']} degraded engine calls")
    check_runtime_clean(rt, label)
    B = engine.ecfg.max_batch
    with engine._scope():
        check_mosaic(engine._decode, (
            engine.params, jnp.zeros((B, 1), jnp.int32), engine._caches,
            jnp.zeros((B,), jnp.int32)), f"{label} decode")
    log(f"  {label}: {len(done)} requests, prompt lengths "
        f"{sorted(len(r.prompt) for r in done)}, {engine.stats['decode_steps']} "
        f"decode steps, {engine.stats['prefill_calls']} prefills")
    return cfg, engine, done


def phase_serve(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core.database import TuningDatabase
    from repro.models import lm

    args = serve_args(seed)
    rt = kernel_runtime(TuningDatabase(None), "smoke-serve")
    cfg, engine, done = run_serve(args, rt, "serve")

    # Prefill logits: kernel path and bf16 reference path, same weights, both
    # against the f32 oracle.
    ecfg, run = engine.ecfg, engine.run
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), engine.params)
    ref_prefill = jax.jit(lambda p, t, n: lm.prefill(
        p, {"tokens": t}, cfg, run, cache_len=ecfg.max_seq, true_len=n)[0])
    oracle_prefill = jax.jit(lambda p, t, n: lm.prefill(
        p, {"tokens": t}, cfg32, run, cache_len=ecfg.max_seq, true_len=n)[0])
    worst = {}
    for r in (min(done, key=lambda r: len(r.prompt)),
              max(done, key=lambda r: len(r.prompt))):
        L = len(r.prompt)
        toks = np.zeros((1, engine._bucket_len(L)), np.int32)
        toks[0, :L] = r.prompt
        toks, n = jnp.asarray(toks), jnp.asarray(L, jnp.int32)
        with engine._scope():
            got = engine._prefill(engine.params, toks, n)[0]
            check_mosaic(engine._prefill, (engine.params, toks, n),
                         f"serve prefill of {L} tokens")
        with repro.runtime(db=TuningDatabase(None), mode="reference"):
            want = ref_prefill(engine.params, toks, n)
            with jax.default_matmul_precision("highest"):
                exact = oracle_prefill(p32, toks, n)
        got, want, exact = (np.asarray(a, np.float32) for a in (got, want, exact))
        check(got.shape == (1, cfg.vocab_size), f"prefill logits shape {got.shape}")
        check(np.isfinite(got).all(), f"non-finite prefill logits ({L} tokens)")
        err = {"kernel_vs_reference": rel_l2(got, want),
               "kernel_vs_f32": rel_l2(got, exact),
               "reference_vs_f32": rel_l2(want, exact)}
        for k, v in err.items():
            worst[k] = max(worst.get(k, 0.0), v)
        log(f"  serve prefill logits, {L} tokens: rel L2 {err!r}; top-1 "
            f"kernel {int(got.argmax())}, reference {int(want.argmax())}, "
            f"f32 {int(exact.argmax())}")
        limit = min(LOGITS_VS_BF16 * err["reference_vs_f32"], LOGITS_REL_L2_MAX)
        check(err["kernel_vs_f32"] <= limit,
              f"prefill logits ({L} tokens): kernel path off the f32 oracle by "
              f"{err['kernel_vs_f32']} > {limit}")
    report_tiers(rt, "serve")
    keys = {k for k in tiers_by_site(rt) if not k.endswith("|*")}
    del engine, p32
    gc.collect()
    return {"logits_rel_l2": worst}, cfg, keys


def phase_tune(seed, cfg, serve_keys):
    import numpy as np

    from repro.campaign import planner, runner, scheduler
    from repro.core.database import TuningDatabase
    from repro.core.evaluate import WallClockEvaluator
    from repro.core.platform import detect_platform

    platform = detect_platform().name
    args = serve_args(seed)
    jobs = [
        j for j in planner.plan_serving_jobs(
            cfg, 8, args.max_seq, kernels=("matmul", "rmsnorm"),
            max_tokens=1 << 20,
        )
        if j.db_key(platform) in serve_keys
    ]
    check(jobs, "tune: no planned matmul/rmsnorm job matches a served bucket")
    manifest = scheduler.build_manifest(jobs, total_budget=40, platform=platform)
    db = TuningDatabase(None)
    summary = runner.run_campaign(
        manifest, db,
        evaluator=WallClockEvaluator(repeats=3, warmup=1, strict=True),
    )
    log(f"  tune: {summary['jobs']} jobs, {summary['done']} done, "
        f"{summary['deferred']} deferred, {summary['evaluations_spent']} "
        f"evaluations")
    check(summary["poisoned"] == 0 and summary["failed"] == 0,
          f"tune: poisoned/failed jobs: "
          f"{[(j.kernel, j.error) for j in manifest.jobs if j.status in ('poisoned', 'failed')]}")
    tuned = [j for j in manifest.jobs if j.status == "done"]
    check(tuned, "tune: no job ran")
    for j in tuned:
        check(math.isfinite(j.best_objective) and j.best_objective > 0,
              f"tune: {j.kernel} {j.arg_shapes} best objective {j.best_objective}")
        log(f"  tune: {j.kernel} {j.arg_shapes} heuristic {j.default_objective!r} s "
            f"-> best {j.best_objective!r} s in {j.evaluations} evaluations, "
            "none failed")

    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, f"{platform}.db.json")
        runner.export_campaign_db(db, out, platform)
        rt = kernel_runtime(TuningDatabase(out), "smoke-serve-tuned")
        run_serve(args, rt, "serve (tuned)")
    rows = tiers_by_site(rt)
    missed = {j.db_key(platform): rows.get(j.db_key(platform)) for j in tuned
              if set(rows.get(j.db_key(platform), {})) != {"exact"}}
    check(not missed, f"tune: tuned buckets not served at the exact tier: {missed}")
    report_tiers(rt, "serve (tuned)")
    gc.collect()
    return {"jobs_tuned": len(tuned), "evaluations": summary["evaluations_spent"]}


def train_args(seed, mesh, batch, seq_len, tmp):
    from repro.launch import train

    return train.parser().parse_args([
        "--arch", ARCH, "--mesh", mesh, "--batch", str(batch),
        "--seq-len", str(seq_len), "--steps", "3", "--mode", "kernel",
        "--seed", str(seed), "--ckpt-dir", os.path.join(tmp, "ckpt"),
        "--ckpt-every", "1000",
    ])


def reference_loss(trainer):
    """Step 1's loss in reference mode on the trainer's weights: the mean
    over the step's microbatches, as the step computes it (and in its
    memory)."""
    import jax

    import repro
    from repro.core.database import TuningDatabase
    from repro.models import lm

    batch = trainer.data.next_batch()
    trainer.data.step -= 1                         # peek: step 1 consumes it
    cfg, run, k = trainer.cfg, trainer.run, trainer.run.microbatches
    loss = jax.jit(lambda p, b: lm.loss_fn(p, b, cfg, run)[0])
    with repro.runtime(db=TuningDatabase(None), mode="reference"):
        return sum(
            float(loss(trainer.params, {
                n: v.reshape(k, -1, *v.shape[1:])[i] for n, v in batch.items()
            }))
            for i in range(k)
        ) / k


def gradient_errors(trainer):
    """The gradient of one microbatch of the next batch on the trainer's
    weights, by the kernel path and by the bf16 reference path, each
    judged by a float32 oracle (the reference path on the weights upcast to
    f32 at highest matmul precision): {leaf: (kernel_vs_f32,
    reference_vs_f32, kernel_vs_reference)} in rel L2. Drops the optimizer
    state to make room."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.core.database import TuningDatabase
    from repro.models import lm

    trainer.opt_state = None
    gc.collect()
    cfg, run, k = trainer.cfg, trainer.run, trainer.run.microbatches
    mb = {n: v[: v.shape[0] // k] for n, v in trainer.data.next_batch().items()}
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)

    def grad(p, b, cfg=cfg):
        return jax.grad(lambda p: lm.loss_fn(p, b, cfg, run)[0])(p)

    def errors(p, b, g_kernel, g_reference):
        g32 = grad(f32(p), b, cfg32)
        rel = lambda g, r: jnp.linalg.norm(g - r) / jnp.linalg.norm(r)
        g_kernel, g_reference = f32(g_kernel), f32(g_reference)
        return (jax.tree_util.tree_map(rel, g_kernel, g32),
                jax.tree_util.tree_map(rel, g_reference, g32),
                jax.tree_util.tree_map(rel, g_kernel, g_reference))

    # One jit per mode: jit caches a trace by function, not by the runtime
    # in scope, so a shared function would serve the kernel trace twice.
    with trainer._scope():
        g_kernel = jax.jit(lambda p, b: grad(p, b))(trainer.params, mb)
    reference = repro.runtime(db=TuningDatabase(None), mode="reference")
    with reference:
        g_reference = jax.jit(lambda p, b: grad(p, b))(trainer.params, mb)
    with reference, jax.default_matmul_precision("highest"):
        errs = jax.jit(errors)(trainer.params, mb, g_kernel, g_reference)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(errs[0])[0]]
    values = zip(*(jax.tree_util.tree_leaves(e) for e in errs))
    return {p: tuple(map(float, v)) for p, v in zip(paths, values)}


def run_train(args, label, reference=False):
    """3 steps of the launcher's trainer; returns (per-step metrics,
    (reference_loss, gradient_errors) or None)."""
    import jax
    import numpy as np

    from repro.core.database import TuningDatabase
    from repro.launch import train

    trainer, rt = train.build(
        args, runtime=kernel_runtime(TuningDatabase(None), f"smoke-{label}"))
    ref_loss = reference_loss(trainer) if reference else None
    steps = []
    for _ in range(3):
        m = trainer.run_one_step()
        steps.append({"loss": m["loss"], "grad_norm": m["grad_norm"]})
        log(f"  {label} step {trainer.step}: loss {m['loss']!r}, grad norm "
            f"{m['grad_norm']!r} ({m['step_time_s']!r} s)")
    check(all(np.isfinite([v for m in steps for v in m.values()])),
          f"{label}: non-finite loss or gradient norm {steps}")
    batch = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), trainer.data.next_batch(), trainer._b_sh)
    trainer.data.step -= 1
    with trainer._scope():
        check_mosaic(trainer._train_step, (
            trainer.params, trainer.opt_state, trainer.ef_state, batch), label)
    del batch
    ref = (ref_loss, gradient_errors(trainer)) if reference else None
    check_runtime_clean(rt, label)
    report_tiers(rt, label)
    del trainer
    gc.collect()
    return steps, ref


def phase_train(seed):
    with tempfile.TemporaryDirectory() as tmp:
        steps, (ref, grad_errs) = run_train(
            train_args(seed, "1x1", 8, 1024, tmp), "train", reference=True)
    loss = steps[0]["loss"]
    err = abs(loss - ref) / abs(ref)
    log(f"  train step-1 loss {loss!r} vs reference {ref!r}: rel {err!r}")
    bad = {}
    for leaf, (kern, bf16, apart) in sorted(grad_errs.items(),
                                            key=lambda kv: -kv[1][0]):
        log(f"  train gradient {leaf}: rel L2 off f32, kernel {kern!r}, "
            f"bf16 reference {bf16!r}; kernel vs reference {apart!r}")
        limit = min(max(GRAD_VS_BF16 * bf16, GRAD_REL_L2_MIN), GRAD_REL_L2_MAX)
        if not kern <= limit:
            bad[leaf] = (kern, bf16)
    check(err <= LOSS_REL, f"train: step-1 loss off reference by {err} > {LOSS_REL}")
    check(not bad, f"train: gradient leaves (kernel, bf16 reference) off the "
                   f"f32 oracle beyond {GRAD_VS_BF16}x the reference's error "
                   f"(at least {GRAD_REL_L2_MIN}) or {GRAD_REL_L2_MAX}: {bad}")
    worst = [max(e[i] for e in grad_errs.values()) for i in range(3)]
    return {"steps": steps, "reference_loss": ref,
            "grad_rel_l2_max": dict(zip(
                ("kernel_vs_f32", "reference_vs_f32", "kernel_vs_reference"),
                worst))}


def phase_train_dp(seed):
    """Data-parallel on a 4x1 mesh against the same global batch on one
    device of the same host. 16 x 512 tokens: each of the step's 4
    microbatches then splits evenly over 4 chips, and one chip holds the
    whole batch's step."""
    with tempfile.TemporaryDirectory() as tmp:
        one, _ = run_train(train_args(seed, "1x1", 16, 512, tmp), "train 1 device")
    with tempfile.TemporaryDirectory() as tmp:
        four, _ = run_train(train_args(seed, "4x1", 16, 512, tmp), "train 4x1")
    for i, (a, b) in enumerate(zip(four, one)):
        err = {k: abs(a[k] - b[k]) / abs(b[k]) for k in ("loss", "grad_norm")}
        log(f"  step {i + 1}: 4x1 {a!r} vs 1 device {b!r}: rel {err!r}")
        check(err["loss"] <= DP_LOSS_REL,
              f"train 4x1: step {i + 1} loss off 1-device by {err['loss']} > "
              f"{DP_LOSS_REL}")
        check(err["grad_norm"] <= DP_GRAD_NORM_REL,
              f"train 4x1: step {i + 1} gradient norm off 1-device by "
              f"{err['grad_norm']} > {DP_GRAD_NORM_REL}")
    return {"steps_4x1": four, "steps_1": one}


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve, tune and train on one chip; 4: only the "
                         "data-parallel training check on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {len(devices)} "
              f"{devices[0].platform} device(s) ({devices[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.core.platform import detect_platform
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    profile = detect_platform()     # raises on a device_kind without peaks
    log(f"device: {devices[0].platform} {devices[0].device_kind!r} x "
        f"{len(devices)} ({profile.name}); jax {jax.__version__}; "
        f"compile cache {cache}")

    clock = CompileClock()
    served = {}

    def serve_phase():
        out, served["cfg"], served["keys"] = phase_serve(args.seed)
        return out

    phases = [
        ("serve", serve_phase),
        ("tune", lambda: phase_tune(args.seed, served["cfg"], served["keys"])),
        ("train", lambda: phase_train(args.seed)),
    ] if args.chips == 1 else [("train_dp", lambda: phase_train_dp(args.seed))]
    results, ok = {}, True
    for name, fn in phases:
        log(f"phase {name}")
        t0, c0 = time.perf_counter(), clock.total
        try:
            res = fn()
            status = "ok"
        except Exception as e:
            ok, res, status = False, {"error": f"{type(e).__name__}: {e}"}, "FAILED"
            traceback.print_exc()
        res.update(wall_s=time.perf_counter() - t0, compile_s=clock.total - c0,
                   peak_bytes_in_use=peak_bytes())
        results[name] = res
        log(f"phase {name} {status}: wall {res['wall_s']!r} s, compile "
            f"{res['compile_s']!r} s, peak_bytes_in_use {res['peak_bytes_in_use']}")
        if not ok:
            break
    log("results: " + json.dumps(results, sort_keys=True, default=str))
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
