"""A copy of the benchmark with cells at smoke widths, for CPU tests: the
cells, configuration and metrics are added as files, as a later change
would add them."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_CONFIG = {
    "name": "qwen2-smoke", "source": "https://arxiv.org/abs/2407.10671",
    "program": {"arch": "qwen2-0.5b", "smoke": True},
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "hidden_act": "silu", "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-06, "qkv_bias": True, "tie_word_embeddings": False,
    "torch_dtype": "float32",
}
TRAIN_CELL = {
    "config": "qwen2-smoke", "driver": "train", "chips": 1, "tuning_db": None,
    "train": {"batch": 4, "seq_len": 64, "mode": "auto", "microbatches": 1,
              "remat": "none", "loss_chunk": 32, "warm_steps": 3},
    "optimizer": {"lr": 0.0003, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                  "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 100,
                  "total_steps": 1000000, "min_lr_frac": 0.1, "master_fp32": True},
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3, "grad_rel_l2": 1e-3},
}
SERVE_CELL = {
    "config": "qwen2-smoke", "driver": "serve", "chips": 1, "tuning_db": None,
    "engine": {"max_batch": 4, "max_seq": 128, "mode": "auto"},
    "traffic": {"wave": 6,
                "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8, "min": 8, "max": 60},
                "output": {"dist": "uniform", "min": 4, "max": 12}},
    "check": {"tokens": 24},
    "limits": {"served_token_gap": 1e-3},
}


def make_copy(tmp):
    """A checkout-shaped copy under ``tmp`` (BENCHMARK.json and bench/) with
    the cells smoke.train and smoke.serve added; returns its bench dir."""
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = os.path.join(root, "bench")
    _write(os.path.join(bench, "configs", "qwen2-smoke.json"), SMOKE_CONFIG)
    cells = {"smoke.train": TRAIN_CELL, "smoke.serve": SERVE_CELL}
    for name, cell in cells.items():
        _write(os.path.join(bench, "workloads", name + ".json"), cell)
        spec["workloads"].append({"name": name, "config": "qwen2-smoke",
                                  "traffic": name.split(".")[1], "chips": 1, "why": "smoke"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "train" if any("train" in w for w in m["workloads"]) else "serve"
            m["workloads"].append("smoke." + kind)
    spec["configs"].append({"name": "qwen2-smoke", "source": SMOKE_CONFIG["source"],
                            "file": "bench/configs/qwen2-smoke.json", "reduced": [],
                            "why": "smoke"})
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return bench


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def run_cell(bench, cell, seed=123, seconds=1.0, trace=0):
    """One run in this process, past the look for a chip; returns (exit code,
    the result's JSON object or None)."""
    import contextlib
    import io

    import run as harness

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                           "--trace", str(trace)],
                          bench=bench, program_root=REPO, chip_check=False, compile_cache=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
