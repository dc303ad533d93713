"""Launch layer: build_cell/lower_cell must lower + compile every step kind
on a multi-device mesh (8 fake host devices, subprocess), and the dry-run
record machinery must produce roofline-ready numbers.
"""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.configs import SHAPES, get_config
from repro.configs.base import cell_is_runnable, input_specs


def test_cell_runnability_rule():
    ok, _ = cell_is_runnable(get_config("xlstm-1.3b"), SHAPES["long_500k"])
    assert ok
    ok, why = cell_is_runnable(get_config("qwen2.5-3b"), SHAPES["long_500k"])
    assert not ok and "sub-quadratic" in why
    ok, _ = cell_is_runnable(get_config("jamba-1.5-large-398b"), SHAPES["long_500k"])
    assert ok


def test_input_specs_layouts():
    cfg = get_config("musicgen-large")
    s = input_specs(cfg, SHAPES["train_4k"])
    assert s["embeds"].shape == (256, 4096, cfg.d_model)
    cfg = get_config("paligemma-3b")
    s = input_specs(cfg, SHAPES["prefill_32k"])
    assert s["embeds"].shape[1] == cfg.num_prefix
    assert s["tokens"].shape[1] == 32768 - cfg.num_prefix
    s = input_specs(cfg, SHAPES["decode_32k"])
    assert s["tokens"].shape == (128, 1)


_MINI_DRYRUN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax
    from repro.configs.base import ShapeSpec, get_config
    from repro.core.evaluate import collective_stats, roofline_from_compiled
    from repro.distributed.sharding import Layout
    from repro.launch import steps
    from repro.models.transformer import RunConfig

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("qwen2-0.5b").reduced()
    layout = Layout(counts=(("heads", cfg.num_heads), ("kv_heads", cfg.num_kv_heads)),
                    head_aware=True)
    results = {}
    cells = [
        ShapeSpec("t", 64, 8, "train"),
        ShapeSpec("p", 64, 8, "prefill"),
        ShapeSpec("d", 64, 8, "decode"),
    ]
    for shape in cells:
        run = RunConfig(remat="none", q_chunk=32, k_chunk=64, loss_chunk=32,
                        microbatches=2 if shape.kind == "train" else 1)
        cell = steps.build_cell(cfg, shape, mesh, layout, run)
        compiled = steps.lower_cell(cell, mesh).compile()
        hlo = compiled.as_text()
        coll = collective_stats(hlo)
        terms = roofline_from_compiled(compiled, chips=8, hlo_text=hlo)
        mem = compiled.memory_analysis()
        results[shape.kind] = {
            "collective_bytes": coll["total_bytes"],
            "n_collectives": coll["count"],
            "arg_bytes": int(mem.argument_size_in_bytes),
        }
    print("MINI_DRYRUN_JSON=" + json.dumps(results))
    """
)


def test_serve_launcher_builds_engine_on_explicit_mesh():
    """The serve launcher's own builder (what ``chip_smoke.py`` drives):
    ``--mesh`` picks the mesh, requests spread from 16 tokens up to
    ``--max-prompt``, and the engine serves them on the kernel path."""
    import repro
    from repro.core.database import TuningDatabase
    from repro.launch import serve

    args = serve.parser().parse_args([
        "--arch", "qwen2-0.5b", "--smoke", "--mesh", "1x1", "--requests", "3",
        "--new-tokens", "2", "--max-seq", "64", "--max-prompt", "40",
    ])
    rt = repro.runtime(db=TuningDatabase(None), mode="kernel", guard=False)
    cfg, engine, got_rt = serve.build(args, runtime=rt)
    assert got_rt is rt and engine.mesh.devices.shape == (1, 1)
    reqs = serve.make_requests(cfg, args)
    assert [len(r.prompt) for r in reqs][::2] == [16, 40]
    for r in reqs:
        engine.submit(r)
    done = engine.serve()
    assert [len(r.output) for r in done] == [2, 2, 2]
    tiers = rt.telemetry.snapshot()["tiers"]
    assert set(tiers) == {"heuristic"}, tiers


def test_train_launcher_takes_batch_and_seq_len(tmp_path):
    from repro.launch import train

    args = train.parser().parse_args([
        "--arch", "qwen2-0.5b", "--smoke", "--mesh", "1x1", "--batch", "4",
        "--seq-len", "32", "--steps", "1", "--mode", "reference",
        "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "1000",
    ])
    trainer, _ = train.build(args)
    batch = trainer.data.next_batch()
    assert batch["tokens"].shape == (4, 32)


def test_mini_dryrun_all_step_kinds():
    r = subprocess.run(
        [sys.executable, "-c", _MINI_DRYRUN],
        capture_output=True, text=True, timeout=560,
        # JAX_PLATFORMS=cpu: without it jax probes the bundled libtpu on this
        # image and hangs for minutes before falling back to CPU
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("MINI_DRYRUN_JSON=")),
        None,
    )
    assert line, f"stdout={r.stdout[-1500:]} stderr={r.stderr[-2500:]}"
    results = json.loads(line.split("=", 1)[1])
    assert set(results) == {"train", "prefill", "decode"}
    # a sharded train step must contain collectives (grad all-reduce at least)
    assert results["train"]["n_collectives"] > 0
    assert results["train"]["collective_bytes"] > 0
    for kind in results:
        assert results[kind]["arg_bytes"] > 0
