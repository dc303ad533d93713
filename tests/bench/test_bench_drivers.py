"""Each driver end to end at smoke widths on the CPU, past the look for a
chip, in a copy of the benchmark to which the smoke cells were added as
files; and the look for a chip itself."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import bench_smoke as B


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return B.make_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["smoke.train", "smoke.serve"])
def test_driver_end_to_end(bench, cell):
    rc, res = B.run_cell(bench, cell, seed=2**31 + 7)
    assert rc == 0 and res is not None
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0
    want = {"smoke.train": {"train_tokens_per_s", "setup_s"},
            "smoke.serve": {"serve_tokens_per_s", "setup_s"}}[cell]
    assert want <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


def test_traced_run_reads_counters_and_a_metric_added_as_a_file(bench):
    """--trace 1 reports the per-layer metrics of the cell; one added as a
    file (a reader and a BENCHMARK.json entry) is found by its name."""
    root = os.path.dirname(bench)
    with open(os.path.join(bench, "metrics", "waves_seen.py"), "w") as f:
        f.write("LAYER = 'engine'\nUNIT = 'requests'\nBETTER = 'higher'\n"
                "SOURCE = 'program_counter'\nMOVES = 'serve_tokens_per_s'\n"
                "WORKLOADS = ['smoke.serve']\n\n\n"
                "def read(run):\n    return float(len(run.counters['requests']))\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "waves_seen", "unit": "requests", "better": "higher",
                              "source": "program_counter", "layer": "engine",
                              "moves": "serve_tokens_per_s", "workloads": ["smoke.serve"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    rc, res = B.run_cell(bench, "smoke.serve", seed=11, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["waves_seen"]["value"] == res["attempted"]
    assert 0 < res["metrics"]["slot_occupancy"]["value"] <= 100
    assert "serve_tokens_per_s" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "busy_s" in res["device"] and res["device"]["window_s"] > 0


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(B.BENCH, "run.py"), "--workload",
         "qwen2-0.5b.train_8x1k", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=B.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_without_the_program_exits_nonzero_without_a_result(tmp_path, capsys):
    """A directory holding only BENCHMARK.json and the benchmark's files: the
    system under test is not beside it, so no result."""
    import run as harness

    bench = B.make_copy(tmp_path)
    rc = harness.main(["--workload", "smoke.train", "--seed", "1", "--seconds", "1"],
                      bench=bench, chip_check=False, compile_cache=False)
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
