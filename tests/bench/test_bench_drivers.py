"""Each driver end to end at smoke widths on the CPU, past the look for a
chip, in a copy of the benchmark to which the smoke cells were added as
files: on one device, on a 4x1 mesh of host devices, and for a second model
family added as files only; and the look for a chip itself."""
from __future__ import annotations

import hashlib
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
                "SOURCE = 'program_counter'\nMOVES = 'serve_tokens_per_s'\n\n\n"
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


def test_traced_window_is_cut_to_the_cells_trace_seconds(tmp_path):
    """A cell that states ``trace_seconds`` traces a window of at most that
    long; its untraced runs measure the whole ``--seconds``."""
    bench = B.make_copy(tmp_path)
    B._write(os.path.join(bench, "workloads", "smoke.train.json"),
             dict(B.TRAIN_CELL, trace_seconds=0.3))
    rc, res = B.run_cell(bench, "smoke.train", seed=2**31 + 31, seconds=2.0, trace=1)
    assert rc == 0 and res["correct"] is True, res
    assert 0.3 <= res["device"]["window_s"] < 1.0
    rc, res = B.run_cell(bench, "smoke.train", seed=2**31 + 31, seconds=2.0)
    assert rc == 0 and res["correct"] is True


def _digests(bench):
    out = {}
    for d, _, files in os.walk(bench):
        for f in files:
            if not f.endswith(".pyc"):
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.relpath(os.path.join(d, f), bench)] = hashlib.sha256(fh.read()).hexdigest()
    return out


TOY_FAMILY = '''"""A second model family for the test: the Qwen2 family's reference and
counts under another name, with a model check that records its calls."""
import os

import run as harness

_base = harness.load_family(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "qwen2")
matmul_params, train_flops, serve_flops = _base.matmul_params, _base.train_flops, _base.serve_flops
make_params, logits_at, train_readings = _base.make_params, _base.logits_at, _base.train_readings
leaf_norms, control_quant = _base.leaf_norms, _base.control_quant
CHECKED = []


def check_model(c, cfg):
    CHECKED.append(cfg["name"])
    _base.check_model(c, cfg)
'''


def test_a_second_family_is_added_as_files_and_list_appends(tmp_path):
    """A family module, a configuration that names it, a cell, and appends
    to BENCHMARK.json's lists: the cell runs through run.py, checks its model
    and decides ``correct`` by that family, and reports an existing per-layer
    metric that it joined by the list alone. No file under bench/ changed."""
    import run as harness

    bench = B.make_copy(tmp_path)
    before = _digests(bench)
    with open(os.path.join(bench, "families", "toy.py"), "w") as f:
        f.write(TOY_FAMILY)
    B._write(os.path.join(bench, "configs", "toy-smoke.json"),
             dict(B.SMOKE_CONFIG, name="toy-smoke", family="toy"))
    B._write(os.path.join(bench, "workloads", "toy-smoke.serve.json"),
             dict(B.SERVE_CELL, config="toy-smoke"))
    path = os.path.join(os.path.dirname(bench), "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-smoke", "source": B.SMOKE_CONFIG["source"],
                            "file": "bench/configs/toy-smoke.json", "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "toy-smoke.serve", "config": "toy-smoke",
                              "traffic": "serve", "chips": 1, "why": "toy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "slot_occupancy"):
            m["workloads"].append("toy-smoke.serve")
    B._write(path, spec)

    rc, res = B.run_cell(bench, "toy-smoke.serve", seed=2**31 + 21, trace=1)
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"slot_occupancy"}
    assert 0 < res["metrics"]["slot_occupancy"]["value"] <= 100
    assert harness.load_family(bench, "toy").CHECKED == ["toy-smoke"]
    rc, res = B.run_cell(bench, "toy-smoke.serve", seed=2**31 + 22)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"families/toy.py", "configs/toy-smoke.json",
                                        "workloads/toy-smoke.serve.json"}


def test_training_on_a_4x1_mesh_of_host_devices(tmp_path):
    """The training driver on the mesh its cell states: 4x1 data parallel
    over four host devices, each matmul's weight gradient summed over them
    (kernel-mode dispatch, as on a TPU), reads ``correct`` against the
    reference over the same global batches."""
    bench = B.make_copy(tmp_path)
    B.add_dp4_cell(bench)
    rc, res, err = B.run_on_host_devices(bench, "smoke.train_dp4", seed=2**31 + 5)
    assert rc == 0 and res["correct"] is True, (res, err[-4000:])
    assert res["device"]["count"] == 4 and res["attempted"] > 0
    assert "mesh: {'data': 4, 'model': 1}" in err
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


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
