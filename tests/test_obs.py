"""The observability plane: metric primitives, collector scoping, spans,
exporters, CLI, hot-path integration, and telemetry snapshot merging."""
import json
import math
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.obs.collect import ObsCollector, current_collector
from repro.obs.metrics import Counter, Gauge, Histogram, percentile_row, tags_key
from repro.obs.trace import current_span, span, span_tree


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

def test_histogram_quantiles_log_bucket_accuracy():
    h = Histogram()
    rs = np.random.RandomState(0)
    samples = rs.lognormal(mean=-7.0, sigma=1.0, size=5000)
    for v in samples:
        h.observe(v)
    for q in (0.50, 0.95, 0.99):
        exact = float(np.quantile(samples, q))
        approx = h.quantile(q)
        # 4 buckets/octave => bucket midpoint within ~9% of any member
        assert abs(approx - exact) / exact < 0.12, (q, approx, exact)
    assert h.count == 5000
    assert math.isclose(h.sum, float(samples.sum()), rel_tol=1e-9)


def test_histogram_small_sample_clamps_to_observed_range():
    h = Histogram()
    h.observe(3e-3)
    snap = h.snapshot()
    assert snap["p50"] == snap["p99"] == 3e-3   # clamped to min==max
    assert snap["count"] == 1


def test_histogram_zero_and_negative_share_underflow_bucket():
    h = Histogram()
    h.observe(0.0)
    h.observe(-1.0)
    assert h.count == 2
    assert h.min == -1.0 and h.max == 0.0
    # underflow midpoint is 0.0, already inside the observed range
    assert h.quantile(0.5) == 0.0


def test_histogram_merge_equals_union():
    a, b, u = Histogram(), Histogram(), Histogram()
    rs = np.random.RandomState(1)
    xs, ys = rs.rand(200) * 1e-3, rs.rand(300) * 1e-2
    for v in xs:
        a.observe(v)
        u.observe(v)
    for v in ys:
        b.observe(v)
        u.observe(v)
    a.merge(b)
    sa, su = a.snapshot(), u.snapshot()
    for field in ("count", "min", "max", "p50", "p95", "p99"):
        assert sa[field] == su[field], field
    assert math.isclose(sa["sum"], su["sum"])   # addition order differs


def test_empty_histogram_snapshot():
    assert Histogram().snapshot()["count"] == 0
    assert Histogram().quantile(0.5) == 0.0


def test_counter_gauge_and_tags_key():
    c, g = Counter(), Gauge()
    c.add()
    c.add(2.5)
    g.set(4)
    g.set(7)
    assert c.snapshot() == {"value": 3.5}
    assert g.snapshot() == {"value": 7.0, "updates": 2}
    assert tags_key({"b": 2, "a": "x"}) == (("a", "x"), ("b", "2"))


# ---------------------------------------------------------------------------
# collector: scoping, sampling, warnings, events
# ---------------------------------------------------------------------------

def test_default_collector_disabled_and_records_nothing():
    col = current_collector()
    assert not col.enabled
    obs.counter("t.never")
    obs.observe("t.never_h", 1.0)
    with obs.collect(name="t") as inner:
        obs.counter("t.yes")
    assert "t.never" not in inner.snapshot()["counters"]
    assert inner.snapshot()["counters"]["t.yes"][0]["value"] == 1


def test_nested_scopes_innermost_wins():
    with obs.collect(name="outer") as outer:
        with obs.collect(name="inner") as inner:
            assert current_collector() is inner
            obs.counter("c")
        assert current_collector() is outer
        obs.counter("c")
    assert inner.snapshot()["counters"]["c"][0]["value"] == 1
    assert outer.snapshot()["counters"]["c"][0]["value"] == 1


def test_thread_isolation():
    seen = {}

    def worker():
        # fresh thread: falls back to the (disabled) process default
        seen["col"] = current_collector()

    with obs.collect(name="main-scope"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert not seen["col"].enabled
    assert seen["col"].name == "default"


def test_tagged_rows_are_separate():
    with obs.collect(name="t") as col:
        col.counter("calls", kernel="matmul", tier="exact")
        col.counter("calls", kernel="matmul", tier="exact")
        col.counter("calls", kernel="rmsnorm", tier="cover")
    rows = col.snapshot()["counters"]["calls"]
    by_tags = {tuple(sorted(r["tags"].items())): r["value"] for r in rows}
    assert by_tags[(("kernel", "matmul"), ("tier", "exact"))] == 2
    assert by_tags[(("kernel", "rmsnorm"), ("tier", "cover"))] == 1


def test_sampling_deterministic_one_in_n():
    col = ObsCollector(name="s", sample_rate=0.25)
    hits = sum(col.sample() for _ in range(100))
    assert hits == 25
    always = ObsCollector(name="s1", sample_rate=1.0)
    assert all(always.sample() for _ in range(10))
    never = ObsCollector(name="s0", sample_rate=0.0)
    assert not any(never.sample() for _ in range(10))


def test_warn_once_dedup_and_fires_when_disabled():
    col = ObsCollector(name="w", enabled=False)
    assert col.warn_once("hazard", key="k1", detail="d") is True
    assert col.warn_once("hazard", key="k1") is False      # deduped
    assert col.warn_once("hazard", key="k2") is True       # distinct key
    warnings = col.events(kind="warning")
    assert len(warnings) == 2
    assert warnings[0]["key"] == "k1" and warnings[0]["detail"] == "d"
    # disabled collector still surfaces the hazard in its snapshot
    assert len(col.snapshot()["warnings"]) == 2


def test_event_ring_buffer_bounded():
    col = ObsCollector(name="rb", max_events=16)
    for i in range(100):
        col.event("e", i=i)
    evs = col.events()
    assert len(evs) == 16
    assert [e["i"] for e in evs] == list(range(84, 100))


def test_bad_event_kind_rejected():
    with pytest.raises(ValueError):
        ObsCollector(name="x").event("e", kind="bogus")


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_disabled_yields_none_and_records_nothing():
    with span("s") as sp:
        assert sp is None
    assert current_span() is None


def test_span_tree_and_histogram():
    with obs.collect(name="t") as col:
        with span("outer", step=3) as sp:
            assert current_span() is sp
            with span("inner") as child:
                assert child.parent_id == sp.span_id
            sp.set(extra="field")
        assert current_span() is None
    snap = col.snapshot()
    # histograms carry NO per-call tags (cardinality protection)...
    assert snap["histograms"]["span.outer"][0]["tags"] == {}
    assert snap["histograms"]["span.inner"][0]["count"] == 1
    # ...the tags live on the span events
    spans = {e["name"]: e for e in col.events(kind="span")}
    assert spans["outer"]["step"] == 3
    assert spans["outer"]["extra"] == "field"
    tree = span_tree(col.events())
    assert [e["name"] for e in tree[None]] == ["outer"]
    assert [e["name"] for e in tree[spans["outer"]["span_id"]]] == ["inner"]


def test_span_xla_annotations_do_not_crash():
    with obs.collect(name="t", xla_annotations=True) as col:
        with span("annotated"):
            pass
    assert col.snapshot()["histograms"]["span.annotated"][0]["count"] == 1


# ---------------------------------------------------------------------------
# export: snapshot round-trip, jsonl, prom, diff, percentile_row
# ---------------------------------------------------------------------------

def _sample_snapshot(scale=1.0):
    with obs.collect(name="exp") as col:
        col.counter("reqs", 3, route="a")
        col.gauge("depth", 7)
        for v in (1e-3, 2e-3, 4e-3):
            col.observe("lat_s", v * scale)
        col.event("boot", phase="init")
    return col


def test_snapshot_write_load_roundtrip(tmp_path):
    from repro.obs.export import load_snapshot, write_snapshot

    col = _sample_snapshot()
    p = str(tmp_path / "m.json")
    write_snapshot(col.snapshot(), p)
    snap = load_snapshot(p)
    assert snap["counters"]["reqs"][0] == {"tags": {"route": "a"}, "value": 3}
    assert snap["gauges"]["depth"][0]["value"] == 7
    assert snap["histograms"]["lat_s"][0]["count"] == 3


def test_load_snapshot_missing_path_exits():
    from repro.obs.export import load_snapshot

    with pytest.raises(SystemExit):
        load_snapshot("/nonexistent/metrics.json")


def test_jsonl_sink_appends(tmp_path):
    from repro.obs.export import read_jsonl, write_jsonl

    p = str(tmp_path / "events.jsonl")
    col = _sample_snapshot()
    write_jsonl(col.events(), p)
    write_jsonl([{"kind": "event", "name": "later"}], p)
    evs = read_jsonl(p)
    assert evs[-1]["name"] == "later"
    assert any(e["name"] == "boot" for e in evs)


def test_prom_textfile(tmp_path):
    p = str(tmp_path / "metrics.prom")
    _sample_snapshot().write_prom(p)
    text = open(p).read()
    assert '# TYPE repro_reqs counter' in text
    assert 'repro_reqs{route="a"} 3' in text
    assert 'repro_depth 7' in text
    assert 'repro_lat_s{quantile="0.95"}' in text
    assert 'repro_lat_s_count 3' in text


def test_diff_snapshots_names_the_shift():
    from repro.obs.export import diff_snapshots, format_diff

    a = _sample_snapshot().snapshot()
    b = _sample_snapshot(scale=10.0).snapshot()
    d = diff_snapshots(a, b)
    row = d["histograms"]["lat_s"][0]
    assert row["p50"]["ratio"] > 5
    assert "lat_s" in format_diff(d)
    assert "(no differences)" in format_diff(diff_snapshots(a, a))


def test_percentile_row_lookup():
    snap = _sample_snapshot().snapshot()
    row = percentile_row(snap, "lat_s")
    assert row["count"] == 3
    assert percentile_row(snap, "nope") is None
    assert percentile_row(snap, "reqs") is None          # not a histogram
    tagged = percentile_row(snap, "lat_s", tags={"missing": "t"})
    assert tagged is None


# ---------------------------------------------------------------------------
# CLI: report / diff
# ---------------------------------------------------------------------------

def test_cli_report_and_diff(tmp_path, capsys):
    from repro.obs.cli import main

    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    _sample_snapshot().write(a)
    _sample_snapshot(scale=10.0).write(b)
    assert main(["report", "--metrics", a, "--events", "5"]) == 0
    out = capsys.readouterr().out
    assert "obs snapshot [exp]" in out and "lat_s" in out
    assert main(["diff", a, b]) == 0
    assert "lat_s" in capsys.readouterr().out
    assert main(["report"]) == 2                         # needs an input
    assert main(["report", "--drift"]) == 2              # --drift needs --db


# ---------------------------------------------------------------------------
# hot-path integration: dispatch resolution + trainer-style phases
# ---------------------------------------------------------------------------

def test_runtime_resolve_records_metrics():
    import jax.numpy as jnp

    from repro.core.runtime import TunedRuntime
    from repro.kernels.matmul import matmul as matmul_tunable

    rt = TunedRuntime(mode="kernel", name="obs-test")
    x = jnp.zeros((32, 16), jnp.float32)
    w = jnp.zeros((16, 8), jnp.float32)
    with obs.collect(name="t") as col:
        rt.resolve(matmul_tunable, (x, w))
        rt.resolve(matmul_tunable, (x, w))               # cache hit
    snap = col.snapshot()
    rows = snap["histograms"]["dispatch.resolve_s"]
    cached = {r["tags"]["cached"] for r in rows}
    assert cached == {"hit", "miss"}
    calls = snap["counters"]["dispatch.calls"]
    assert all(r["tags"]["kernel"] == "matmul" for r in calls)
    assert sum(r["value"] for r in calls) == 2


def test_dispatch_runs_inside_span():
    import jax.numpy as jnp

    from repro.core.runtime import TunedRuntime
    from repro.kernels.matmul import matmul as matmul_tunable

    rt = TunedRuntime(mode="reference", name="obs-test")
    x = jnp.ones((8, 4), jnp.float32)
    w = jnp.ones((4, 4), jnp.float32)
    with obs.collect(name="t") as col, rt:
        rt.dispatch(matmul_tunable, x, w)
    spans = col.events(kind="span")
    assert [e["name"] for e in spans] == ["dispatch"]
    assert spans[0]["kernel"] == "matmul"
    assert spans[0]["phase"] == "fwd"


def test_dp_approx_key_warns_once():
    import jax.numpy as jnp

    from repro.core.runtime import TunedRuntime
    from repro.distributed import sharding as shd
    from repro.kernels.matmul import matmul as matmul_tunable
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    layout = shd.Layout()
    rt = TunedRuntime(mode="kernel", name="dp-approx-test")
    x = jnp.zeros((8, 4), jnp.float32)
    w = jnp.zeros((4, 4), jnp.float32)
    with obs.collect(name="t") as col:
        with shd.mesh_context(mesh, layout, dp_degree=1, dp_approx=True):
            rt.resolve(matmul_tunable, (x, w))
            rt.resolve(matmul_tunable, (x, w))
        # same key outside the approx scope: no new warning
        with shd.mesh_context(mesh, layout, dp_degree=1):
            rt.resolve(matmul_tunable, (x, w))
    warnings = col.events(kind="warning")
    assert len(warnings) == 1
    w0 = warnings[0]
    assert w0["name"] == "dispatch.local_key_approx"
    assert w0["key"].startswith("matmul|")               # includes the key


# ---------------------------------------------------------------------------
# telemetry snapshot merging across resumed campaign runs (satellite)
# ---------------------------------------------------------------------------

def _telemetry_snap(calls, tiers, phases, by_key, by_key_phase, hits=0):
    return {
        "calls": calls, "cache_hits": hits, "cache_evictions": 0,
        "cache_hit_rate": hits / calls if calls else 0.0,
        "tiers": tiers, "tier_rates": {t: n / calls for t, n in tiers.items()},
        "by_key": by_key, "phases": phases, "by_key_phase": by_key_phase,
    }


def test_merge_snapshots_accumulates_all_sections():
    from repro.campaign.runner import _merge_snapshots

    a = _telemetry_snap(
        4, {"exact": 3, "heuristic": 1},
        phases={"fwd": {"exact": 3}, "bwd": {"heuristic": 1}},
        by_key={"matmul|k1": {"exact": 3}, "rmsnorm|k2": {"heuristic": 1}},
        by_key_phase={"fwd": {"matmul|k1": {"exact": 3}},
                      "bwd": {"rmsnorm|k2": {"heuristic": 1}}},
        hits=2,
    )
    b = _telemetry_snap(
        6, {"exact": 2, "cover": 4},
        phases={"fwd": {"exact": 2, "cover": 1}, "opt": {"cover": 3}},
        by_key={"matmul|k1": {"exact": 2}, "xent|k3": {"cover": 4}},
        by_key_phase={"fwd": {"matmul|k1": {"exact": 2, "cover": 1}},
                      "opt": {"xent|k3": {"cover": 3}}},
        hits=1,
    )
    m = _merge_snapshots(a, b)
    assert m["calls"] == 10
    assert m["cache_hits"] == 3 and m["cache_hit_rate"] == 0.3
    assert m["tiers"] == {"exact": 5, "heuristic": 1, "cover": 4}
    assert m["tier_rates"]["exact"] == 0.5
    # phases: shared phase accumulates, disjoint phases survive
    assert m["phases"]["fwd"] == {"exact": 5, "cover": 1}
    assert m["phases"]["bwd"] == {"heuristic": 1}
    assert m["phases"]["opt"] == {"cover": 3}
    # by_key / by_key_phase: per-key tier counts add
    assert m["by_key"]["matmul|k1"] == {"exact": 5}
    assert m["by_key_phase"]["fwd"]["matmul|k1"] == {"exact": 5, "cover": 1}
    assert m["by_key_phase"]["bwd"]["rmsnorm|k2"] == {"heuristic": 1}
    assert m["by_key_phase"]["opt"]["xent|k3"] == {"cover": 3}


def test_merge_snapshots_none_prev_is_identity():
    from repro.campaign.runner import _merge_snapshots

    b = _telemetry_snap(2, {"exact": 2}, phases={"fwd": {"exact": 2}},
                        by_key={}, by_key_phase={})
    assert _merge_snapshots(None, b) is b
    assert _merge_snapshots({}, b) is b


def test_merge_snapshots_live_roundtrip():
    """Two real Telemetry snapshots merge to the union accounting —
    the resumed-campaign path in run_campaign."""
    from repro.campaign.runner import _merge_snapshots
    from repro.core.runtime import Telemetry, dispatch_phase

    t1, t2 = Telemetry(), Telemetry()
    t1.record("matmul", "matmul|a", "exact")
    with dispatch_phase("bwd"):
        t1.record("matmul", "matmul|a", "cover")
        t2.record("rmsnorm", "rmsnorm|b", "exact")
    t2.record("matmul", "matmul|a", "exact", cached=True)
    m = _merge_snapshots(t1.snapshot(), t2.snapshot())
    assert m["calls"] == 4
    assert m["phases"]["fwd"] == {"exact": 2}
    assert m["phases"]["bwd"] == {"cover": 1, "exact": 1}
    assert m["by_key_phase"]["fwd"]["matmul|a"] == {"exact": 2}
    assert m["by_key_phase"]["bwd"]["rmsnorm|b"] == {"exact": 1}


def test_run_campaign_merges_resumed_telemetry(tmp_path):
    """A resumed campaign accumulates the banked manifest telemetry instead
    of overwriting it (the `_merge_snapshots` call inside run_campaign)."""
    from repro.campaign import planner, runner, scheduler
    from repro.core.database import TuningDatabase
    from repro.core.evaluate import WallClockEvaluator
    from repro.core.runtime import Telemetry, dispatch_phase

    jobs = planner.plan_jobs(
        ["qwen2_0_5b"], train_shapes=[], serving=(2, 32),
        kernels=("rmsnorm",), reduced=True,
    )
    manifest = scheduler.build_manifest(
        jobs, total_budget=4, path=str(tmp_path / "m.json"),
        min_budget=2, max_budget=2,
    )
    assert manifest.jobs
    # bank a prior invocation's accounting the way run_campaign would
    prior = Telemetry()
    prior.record("matmul", "matmul|a", "exact")
    with dispatch_phase("bwd"):
        prior.record("matmul", "matmul|a", "cover")
    manifest.meta["telemetry"] = prior.snapshot()
    db = TuningDatabase(None)
    ev = WallClockEvaluator(repeats=1, warmup=0)
    runner.run_campaign(manifest, db, evaluator=ev, max_jobs=1)
    merged = manifest.meta["telemetry"]
    # the prior run's counts survived the resume (merge, not overwrite)
    assert merged["calls"] >= 2
    assert merged["by_key"]["matmul|a"] == {"exact": 1, "cover": 1}
    assert merged["phases"]["bwd"] == {"cover": 1}
    assert merged["by_key_phase"]["fwd"]["matmul|a"] == {"exact": 1}
    # ...and the persisted manifest round-trips it
    reloaded = scheduler.CampaignManifest.load(str(tmp_path / "m.json"))
    assert reloaded.meta["telemetry"]["by_key"]["matmul|a"] == {
        "exact": 1, "cover": 1}


# ---------------------------------------------------------------------------
# serving percentiles (satellite): engine histograms feed the stats report
# ---------------------------------------------------------------------------

def test_serving_engine_records_latency_histograms():
    import dataclasses

    import jax

    from repro.configs.base import SHAPES, get_config
    from repro.launch import defaults
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    cfg = get_config("qwen2_0_5b").reduced()
    run = defaults.default_run(cfg, SHAPES["decode_32k"])
    run = dataclasses.replace(run, q_chunk=32, k_chunk=64, loss_chunk=32)
    params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(
        cfg, run, params, make_host_mesh(), defaults.default_layout(cfg),
        EngineConfig(max_batch=2, max_seq=64),
    )
    rs = np.random.RandomState(0)
    with obs.collect(name="serve-test") as col:
        for i in range(3):
            engine.submit(Request(
                prompt=rs.randint(0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=4, temperature=0.0, seed=i,
            ))
        done = engine.serve()
    assert len(done) == 3
    snap = col.snapshot()
    adm = percentile_row(snap, "span.serve.admit")
    tok = percentile_row(snap, "serve.per_token_s")
    lat = percentile_row(snap, "serve.latency_s")
    assert adm["count"] == 3 and lat["count"] == 3 and tok["count"] == 3
    assert 0 < lat["p50"] and lat["p50"] <= lat["p99"]
    reqs = snap["counters"]["serve.requests"][0]["value"]
    assert reqs == 3
    assert snap["counters"]["serve.tokens"][0]["value"] == sum(
        len(r.output) for r in done
    )
