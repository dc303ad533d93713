"""BENCHMARK.json against the files that make the benchmark: every cell,
configuration and per-layer metric is found by its name, and the file keeps
to the shape the benchmark's contract asks for."""
from __future__ import annotations

import importlib.util
import json
import os
import re

import pytest

import bench_smoke as B

with open(os.path.join(B.REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _module(path):
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    cells = len(SPEC["workloads"])
    # a full check of 24 cells fits: (2 + 14 cells) runs of run_seconds + 60,
    # 2 x 90 s of compile per cell and 1200 s spare, within 43200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, cells // 2)
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(B.REPO, p))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_file(cfg):
    assert NAME.match(cfg["name"])
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    with open(os.path.join(B.REPO, cfg["file"])) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])
    for k in cfg["reduced"]:
        assert not re.search(r"(size|_dim|_rank|heads|experts_per_tok)$", k), k


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_file(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    with open(os.path.join(B.BENCH, "workloads", cell["name"] + ".json")) as f:
        data = json.load(f)
    assert data["config"] == cell["config"] and data["chips"] == cell["chips"]
    assert os.path.exists(os.path.join(B.BENCH, "drivers", data["driver"] + ".py"))
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]]) for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_reader(metric):
    """Each per-layer metric has a reader file of its own that declares what
    BENCHMARK.json says of it, and moves a metric its cells report."""
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    mod = _module(os.path.join(B.BENCH, "metrics", metric["name"] + ".py"))
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES, mod.WORKLOADS) == (
        metric["layer"], metric["unit"], metric["better"], metric["source"],
        metric["moves"], metric["workloads"])
    assert callable(mod.read)
    moves = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
    assert set(metric["workloads"]) <= set(moves.get("workloads", metric["workloads"]))
    if metric["name"].endswith("_roofline") or "_roofline." in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_layer_names_are_one_line_each():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)
