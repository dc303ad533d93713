#!/usr/bin/env python3
"""The readings each cell's limits are set from, for many seeds in one
process: the program's, the control's and the planted faults'.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out file.jsonl]

For each seed, one JSON line. Training: the gaps of the program's warm steps,
of the control (the reference computed in the next lower precision, put in
the program's place) and of a planted fault (the reference on half of each
batch), each against the float32 reference. Serving: one wave at the cell's
load, then the widest served-token gap of the program and of the control
(the token the lower-precision reference puts first at each served
position), and the smallest gap of a served token altered to the next id.
A benchmark run never runs this; the limits in the cells' files come from
its readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402


def train_seed(run, drv, seed):
    import reference as R

    t = run.cell["train"]
    trainer = drv.build(run, seed)
    prog = drv.warm(trainer, t["warm_steps"])
    del trainer
    gc.collect()
    batches = drv.reference_batches(run, seed, t["warm_steps"])
    opt = run.cell["optimizer"]
    ref = R.train_readings(run.cfg, seed, batches, opt)
    ctl = R.train_readings(run.cfg, seed, batches, opt, quant=R.control_quant(run.cfg))
    half = R.train_readings(run.cfg, seed, batches, opt, rows=t["batch"] // 2)
    return {"program": drv.compare(prog, ref), "control": drv.compare(ctl, ref)[0],
            "half_batch": drv.compare(half, ref)[0],
            "loss": {"program": prog["loss"], "reference": ref["loss"]}}


def serve_seed(run, drv, engine, init, seed):
    import jax
    import numpy as np

    import reference as R

    V = run.cfg["vocab_size"]
    engine.params = init(jax.random.PRNGKey(seed))
    done = drv.serve_wave(engine, run.cell["traffic"], V, seed, 0)
    picked = drv.sample(done, run.cell["check"]["tokens"], seed)
    engine.params = None
    gc.collect()
    params = R.make_params(run.cfg, seed)
    altered = []
    for r in picked:
        r2 = type(r)(prompt=r.prompt)
        r2.output = np.asarray(r.output).copy()
        r2.output[0] = (r2.output[0] + 1) % V
        altered.append(r2)
    with jax.default_matmul_precision("highest"):
        prog = drv.gaps(run.cfg, params, picked)
        ctl = drv.gaps(run.cfg, params, picked, quant=R.control_quant(run.cfg))
        alt = drv.gaps(run.cfg, params, altered)
    del params
    gc.collect()
    return {"program": max(float(g.max()) for g in prog),
            "control": max(float(g.max()) for g in ctl),
            "altered_token": min(float(g[0]) for g in alt),
            "requests": len(picked), "tokens": sum(len(r.output) for r in picked)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    cell = harness.load_json(os.path.join(BENCH, "workloads", args.workload + ".json"))
    cfg = harness.load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    import jax

    if jax.devices()[0].platform != "tpu":
        harness.log("calibrate: needs a TPU")
        return 3
    sys.path.insert(0, os.path.join(root, "src"))
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    drv = harness.load_module(os.path.join(BENCH, "drivers", cell["driver"] + ".py"), "drv")
    seeds = [int(s) for s in args.seeds.split(",")]
    run = harness.Run(BENCH, args.workload, cell, cfg, seeds[0], 0, False, None)
    if cell["driver"] == "serve":
        from repro.models import lm

        engine, _ = drv.build(run, seeds[0])
        drv.warm_up(engine, cell["traffic"])
        init = jax.jit(lambda k: lm.init_params(k, engine.cfg)[0])
        one = lambda s: serve_seed(run, drv, engine, init, s)
    else:
        one = lambda s: train_seed(run, drv, s)
    out = open(args.out, "a") if args.out else None
    for s in seeds:
        line = json.dumps({"workload": args.workload, "seed": s, **one(s)})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
