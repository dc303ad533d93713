"""Spans on the serving engine's loop and the trainer's step: where they
open and close, how they nest, that they change nothing the engine serves,
and that their times lie on the profiler's clock."""
import glob
import os
import time

import jax
import numpy as np
import pytest

import repro.obs as obs
from repro.configs import get_config
from repro.obs.trace import current_span, span

CFG = get_config("qwen2_0_5b").reduced()


@pytest.fixture(scope="module")
def params():
    from repro.models import lm

    return lm.init_params(jax.random.PRNGKey(0), CFG)[0]


class SpanClock:
    """Engine clock: each reading is 1.0 later than the last, and remembers
    the span open when it was taken."""

    def __init__(self):
        self.t, self.at = 0.0, {}

    def __call__(self) -> float:
        self.t += 1.0
        sp = current_span()
        self.at[self.t] = (sp.name, sp.tags.get("request")) if sp is not None else None
        return self.t


def _serve(params, clock):
    from repro.launch import defaults
    from repro.launch.mesh import make_host_mesh
    from repro.models.transformer import RunConfig
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    run = RunConfig(remat="none", loss_chunk=16, q_chunk=16, k_chunk=16)
    engine = ServingEngine(CFG, run, params, make_host_mesh(), defaults.default_layout(CFG),
                           EngineConfig(max_batch=2, max_seq=64), clock=clock)
    rs = np.random.RandomState(3)
    # (prompt length, new tokens, arrival tick): a one-token request, and
    # arrivals while the pool decodes
    for L, n, at in [(9, 5, 0), (20, 1, 0), (5, 7, 0), (12, 3, 2), (17, 4, 3)]:
        engine.submit(Request(prompt=rs.randint(0, CFG.vocab_size, L).astype(np.int32),
                              max_new_tokens=n, arrival_time=float(at)))
    return engine, engine.serve()


def test_engine_spans_nest_and_leave_outputs_unchanged(params):
    _, off = _serve(params, SpanClock())
    clock = SpanClock()
    with obs.collect(name="engine-spans") as col:
        engine, on = _serve(params, clock)
    assert [r.output.tolist() for r in on] == [r.output.tolist() for r in off]

    evs = col.events(kind="span")
    ticks = [e for e in evs if e["name"] == "serve.tick"]
    assert len(ticks) == engine.stats["decode_steps"] > 0
    assert [e["tick"] for e in ticks] == list(range(len(ticks)))
    assert all(1 <= e["active"] <= 2 and e["queued"] >= 0 for e in ticks)
    tick_ids = {e["span_id"] for e in ticks}
    for child in ("serve.decode", "serve.fetch", "serve.sample"):
        kids = [e for e in evs if e["name"] == child]
        assert sorted(e["parent_id"] for e in kids) == sorted(tick_ids), child
    admits = [e for e in evs if e["name"] == "serve.admit"]
    assert sorted(e["request"] for e in admits) == list(range(len(on)))
    assert all(e["parent_id"] is None for e in admits)
    # admissions and ticks never overlap in time
    for a in admits:
        assert all(a["end_ns"] <= t["start_ns"] or t["end_ns"] <= a["start_ns"]
                   for t in ticks)
    for e in admits:
        assert {"slot", "prompt_len", "bucket"} <= set(e) and e["bucket"] >= e["prompt_len"]

    for i, r in enumerate(on):
        assert r.submitted_s <= r.first_token_s
        # the first token is read off the clock inside the request's admission
        assert clock.at[r.first_token_s] == ("serve.admit", i)


def test_span_times_lie_on_the_profilers_clock(tmp_path):
    """A span's ``start_ns`` / ``end_ns`` match its profiler annotation,
    which carries the span's tags, at the profile's start time plus the
    annotation's own offset."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.collect(name="clock", xla_annotations=True) as col:
            with span("serve.tick", tick=7):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (ring,) = col.events(kind="span")
    assert ring["start_ns"] < ring["end_ns"]
    assert (ring["end_ns"] - ring["start_ns"]) * 1e-9 == pytest.approx(ring["dur_s"], abs=1e-3)

    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    start = next(v for p in pd.planes for k, v in p.stats if k == "profile_start_time")
    (ev,) = [e for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name == "serve.tick"]
    assert dict(ev.stats)["tick"] == 7
    assert abs(start + ev.start_ns - ring["start_ns"]) < 2e6
    assert abs(start + ev.end_ns - ring["end_ns"]) < 2e6


def test_train_step_span_covers_the_readback(tmp_path):
    from repro.data.pipeline import DataConfig
    from repro.distributed.sharding import Layout
    from repro.launch.mesh import make_host_mesh
    from repro.models.transformer import RunConfig
    from repro.optim import adamw
    from repro.train.trainer import Trainer, TrainerConfig

    tr = Trainer(CFG, RunConfig(remat="none", loss_chunk=16, q_chunk=16, k_chunk=16),
                 make_host_mesh(), Layout(), DataConfig(seed=0, batch_size=8, seq_len=32),
                 adamw.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60),
                 TrainerConfig(total_steps=3, checkpoint_every=100,
                               checkpoint_dir=str(tmp_path / "ckpt"), async_checkpoint=False))
    with obs.collect(name="train-spans") as col:
        times = [tr.run_one_step()["step_time_s"] for _ in range(3)]
    steps = [e for e in col.events(kind="span") if e["name"] == "train.step"]
    assert [e["step"] for e in steps] == [0, 1, 2]
    for e, dt in zip(steps, times):
        assert e["dur_s"] >= 0.9 * dt, (e["dur_s"], dt)
    assert "span.train.step" in col.snapshot()["histograms"]
    assert "train.step_s" not in col.snapshot()["histograms"]
