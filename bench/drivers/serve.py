"""Serving cells: ``ServingEngine.submit`` and ``ServingEngine.serve`` on an
engine built as ``repro.launch.serve.build`` builds one, on the cell's mesh,
with the cell's slot pool and cache, the tuning database the cell names (none
in these cells), and the benchmark's clock. The configuration's model family
checks the program's model and gives the reference.

Set-up makes the weights from the seed (one jitted call, as the launcher
does) and serves one short request per prefill bucket the cell's traffic
reaches, which compiles those buckets, the cache insert and the decode pool.
The window then serves closed waves (``traffic.wave``): each wave is
submitted whole and ``serve()`` returns when it has drained; waves follow
until ``--seconds`` have passed, and the window ends with the wave in
progress. ``serve_tokens_per_s`` is every output token of the waves over
their wall time; ``tpot_p90_ms`` is the 90th percentile over the window's
requests of (admission to last token) / (tokens - 1).

Compared with the reference, after the window: a sample drawn from the seed
of the window's requests, with the one that emitted most tokens in it, until
it holds ``check.tokens`` served tokens. The reference runs once over each
prompt followed by its served tokens; a served token's gap is the reference's
best logit at that position minus the reference's logit of the served token.
The widest gap in the sample is compared.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import program
import traffic


def build(run, seed):
    """(engine, runtime): the launcher's construction with the cell's
    engine configuration and the benchmark's clock."""
    import dataclasses

    import jax

    import repro
    from repro.configs.base import SHAPES, get_config
    from repro.launch import defaults
    from repro.launch.mesh import make_mesh_from_spec
    from repro.models import lm
    from repro.serving.engine import EngineConfig, ServingEngine

    prog, e = run.cfg["program"], run.cell["engine"]
    cfg = get_config(prog["arch"])
    smoke = prog.get("smoke", False)
    if smoke:
        cfg = cfg.reduced()
    mesh = make_mesh_from_spec(run.cell["mesh"])
    layout = defaults.default_layout(cfg)
    rn = defaults.default_run(cfg, SHAPES["decode_32k"])
    if smoke:
        rn = dataclasses.replace(rn, q_chunk=32, k_chunk=max(32, e["max_seq"]), loss_chunk=32)
    run.family.check_model(cfg, run.cfg)
    params = jax.jit(lambda k: lm.init_params(k, cfg)[0])(jax.random.PRNGKey(seed))
    rt = repro.runtime(db=program.tuning_db(run), mode=e["mode"], name="bench-serve")
    engine = ServingEngine(cfg, rn, params, mesh, layout,
                           EngineConfig(max_batch=e["max_batch"], max_seq=e["max_seq"]),
                           clock=time.perf_counter, runtime=rt)
    return engine, rt


def _request(prompt, n):
    from repro.serving.engine import Request

    return Request(prompt=prompt, max_new_tokens=int(n), temperature=0.0)


def warm_up(engine, tr):
    """One two-token request per prefill bucket the traffic reaches, at the
    longest prompt of the wave that falls in it."""
    by_bucket = {}
    for L, _ in traffic.wave_sizes(tr):
        b = engine._bucket_len(L)
        by_bucket[b] = max(by_bucket.get(b, 0), L)
    for b, L in sorted(by_bucket.items()):
        engine.submit(_request(np.zeros(L, np.int32) + 1, 2))
        engine.serve()
    engine.reset_stats()
    return sorted(by_bucket)


def serve_wave(engine, tr, vocab, seed, index):
    """Serve wave ``index``; returns its requests, in submission order."""
    reqs = [_request(p, n) for p, n in traffic.wave(tr, vocab, seed, index)]
    for r in reqs:
        engine.submit(r)
    return engine.serve()


def sample(done, tokens, seed):
    """Requests drawn from the seed, the one with most output tokens first,
    until they hold ``tokens`` served tokens."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    first = max(range(len(done)), key=lambda i: len(done[i].output))
    picked, n = [], 0
    for i in [first] + [int(j) for j in rng.permutation(len(done)) if j != first]:
        picked.append(done[i])
        n += len(done[i].output)
        if n >= tokens:
            break
    return picked


def gaps(run, params, reqs, quant=None):
    """Per request, the gap of each served token under the family's
    reference; with ``quant``, the gap (under the reference) of the token a
    ``quant`` reference puts first at each of those positions."""
    R, cfg = run.family, run.cfg
    out = []
    for r in reqs:
        L, o = len(r.prompt), np.asarray(r.output)
        seq = np.concatenate([r.prompt, o[:-1]]).astype(np.int32)
        at = np.arange(L - 1, L - 1 + len(o))
        ref = np.asarray(R.logits_at(params, cfg, seq, at), np.float64)
        tok = o
        if quant is not None:
            tok = np.asarray(R.logits_at(params, cfg, seq, at, quant=quant)).argmax(-1)
        out.append(ref.max(-1) - ref[np.arange(len(o)), tok])
    return out


def free(engine):
    engine.params = None
    engine._caches = None
    gc.collect()


def run(run):
    import jax

    tr, vocab = run.cell["traffic"], run.cfg["vocab_size"]
    with run.phase("build"):
        engine, rt = build(run, run.seed)
    with run.phase("warm_up"):
        buckets = warm_up(engine, tr)
    compile_s = run.compiles.seconds
    done, wave_times, w = [], [], 0
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            tw = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.wave"):
                done.extend(serve_wave(engine, tr, vocab, run.seed, w))
            wave_times.append(time.perf_counter() - tw)
            w += 1
        elapsed = time.perf_counter() - t0
    st = dict(engine.stats)
    out_tokens = sum(len(r.output) for r in done)
    tpot = [r.latency_s / (len(r.output) - 1) for r in done if len(r.output) > 1]
    failed = len(done) if (st["degraded_calls"] or program.tiers(rt).get("reference")) else 0
    run.counters.update(stats=st, requests=[(len(r.prompt), len(r.output)) for r in done])
    if run.trace:
        run.kernels = program_kernels(engine, buckets)
    run.read_memory_peak()
    free(engine)

    params = run.family.make_params(run.cfg, run.seed)
    picked = sample(done, run.cell["check"]["tokens"], run.seed)
    with jax.default_matmul_precision("highest"):
        widest = max(float(g.max()) for g in gaps(run, params, picked))
    del params
    return {
        "metrics": {"serve_tokens_per_s": out_tokens / elapsed,
                    "tpot_p90_ms": 1e3 * float(np.percentile(tpot, 90)),
                    "setup_s": run.setup_s},
        "attempted": len(done), "failed": failed,
        "setup_compile_s": compile_s,
        "notes": [f"dispatch tiers at trace time: {program.tiers(rt)}",
                  f"mesh: {dict(engine.mesh.shape)}",
                  f"warm prefill buckets: {buckets}",
                  f"window: {w} waves, {len(done)} requests, {out_tokens} output tokens in "
                  f"{elapsed!r} s (waves {[round(x, 3) for x in wave_times]}); engine {st}",
                  f"checked {len(picked)} requests, "
                  f"{sum(len(r.output) for r in picked)} served tokens"],
        "checks": {"served_token_gap": (widest, run.cell["limits"]["served_token_gap"])},
    }


def program_kernels(engine, buckets):
    """{signature: kernel} of the Pallas calls in the prefill programs of the
    warm buckets and in the decode program."""
    import jax.numpy as jnp

    import roofline

    B = engine.ecfg.max_batch
    out = {}
    with engine._scope():
        for b in buckets:
            out.update(roofline.pallas_kernels(engine._prefill.trace(
                engine.params, jnp.zeros((1, b), jnp.int32), jnp.asarray(b, jnp.int32)).jaxpr.jaxpr))
        out.update(roofline.pallas_kernels(engine._decode.trace(
            engine.params, jnp.zeros((B, 1), jnp.int32), engine._caches,
            jnp.zeros((B,), jnp.int32)).jaxpr.jaxpr))
    return out
