"""Training cells: ``Trainer.run_one_step`` as ``repro.launch.train.build``
builds it, on the cell's batch, with the tuning database the cell names
(none in these cells).

Set-up builds one trainer from the seed, feeds it the seed's batches
(``traffic.TrainFeed``) and drives its first ``warm_steps`` steps through the
same call and feed the window uses; those steps compile everything and are
what the reference follows. The window then runs whole steps until
``--seconds`` have passed: ``train_tokens_per_s`` is every token trained in
the window over the window.

Compared with the reference, after the window: each warm step's loss; the
first gradient as the optimizer got it (Adam's first moment after step 1,
leaf by leaf); and the change of the float32 master weights over the warm
steps, leaf by leaf. A leaf's gap is |program norm - reference norm| over
the larger of the reference's norm of that leaf and of the median leaf. The
first gradient is also compared element by element: per leaf, the norm of
the difference over the reference's norm (norms and means average a lower
precision's rounding away; this does not). Leaves whose reference gradient
is under a thousandth of the median leaf's (moved by round-off alone, like a
key bias under softmax) are left out of the leaf comparisons.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import time

import program
import reference as R
import traffic

KEEP_FRACTION = 1e-3


def build(run, seed):
    """The trainer for ``seed``, fed by the seed's batches."""
    import repro
    from repro.launch import train

    cfg, t = run.cfg, run.cell["train"]
    args = train.parser().parse_args([
        "--arch", cfg["program"]["arch"], "--mesh", "1x1",
        "--batch", str(t["batch"]), "--seq-len", str(t["seq_len"]),
        "--steps", str(run.cell["optimizer"]["total_steps"]), "--mode", t["mode"],
        "--seed", str(seed), "--ckpt-dir", tempfile.mkdtemp(prefix="bench-ckpt-"),
        "--ckpt-every", str(10 ** 9),
    ] + (["--smoke"] if cfg["program"].get("smoke") else []))
    rt = repro.runtime(db=program.tuning_db(run), mode=t["mode"], name="bench-train")
    trainer, _ = train.build(args, runtime=rt)
    _check_matches(trainer, cfg, run.cell)
    trainer.data = traffic.TrainFeed(seed, t["batch"], t["seq_len"], cfg["vocab_size"])
    return trainer


def _check_matches(trainer, cfg, cell):
    """The program runs what the configuration and the cell state."""
    program.check_model(trainer.cfg, cfg)
    r, o, t = trainer.run, trainer.opt_cfg, cell["train"]
    bad = {k: (getattr(r, k), t[k]) for k in ("microbatches", "remat", "loss_chunk")
           if getattr(r, k) != t[k]}
    bad.update({"optimizer." + k: (getattr(o, k), v) for k, v in cell["optimizer"].items()
                if getattr(o, k) != v})
    if bad:
        raise RuntimeError(f"the program departs from the cell (program, file): {bad}")


def warm(trainer, steps):
    """Run the first ``steps`` steps; returns the program's readings. The
    update is the change of the float32 master weights from their own start
    (on a TPU the master starts from the unrounded draw, not from the
    bfloat16 weights); that start waits on the host, so the readings take no
    device memory the steps need."""
    import jax
    import jax.numpy as jnp

    p0 = jax.device_get(trainer.opt_state["master"])
    out = {"loss": []}
    for i in range(1, steps + 1):
        out["loss"].append(trainer.run_one_step()["loss"])
        if i == 1:
            out["m1"] = R.leaf_norms(trainer.opt_state["m"])
            out["m1_host"] = jax.device_get(trainer.opt_state["m"])
    diff = jax.jit(lambda a, b: jnp.linalg.norm(a - b))
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(trainer.opt_state["master"])[0])
    out["update"] = {jax.tree_util.keystr(k): float(diff(a, b)) for k, a, b in
                     zip(paths, leaves, jax.tree_util.tree_leaves(p0))}
    return out


def compare(prog, ref):
    """The gaps compared: loss (relative, worst warm step); gradient and
    update (worst leaf, gap of norms); and the first gradient's worst-leaf
    relative L2 distance, |program - reference| / |reference| of the first
    moment, which sees a lower precision that the norms average away. Also,
    for each leaf number, (leaf, program, reference) of its worst leaf."""
    import jax
    import numpy as np

    gaps = {"loss_gap": max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))}
    worst = {}
    med = statistics.median(ref["m1"].values())
    keep = [k for k, v in ref["m1"].items() if v >= KEEP_FRACTION * med]
    for name, key in (("grad_gap", "m1"), ("update_gap", "update")):
        if set(prog[key]) != set(ref[key]):
            raise RuntimeError(f"{key}: leaves differ from the reference's")
        floor = statistics.median(ref[key][k] for k in keep)
        gap = {k: abs(prog[key][k] - ref[key][k]) / max(ref[key][k], floor) for k in keep}
        leaf = max(gap, key=gap.get)
        gaps[name], worst[name] = gap[leaf], (leaf, prog[key][leaf], ref[key][leaf])
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                      for k, v in jax.tree_util.tree_flatten_with_path(t)[0]}
    a, b = flat(prog["m1_host"]), flat(ref["m1_host"])
    rel = {k: float(np.linalg.norm(a[k] - b[k]) / np.linalg.norm(b[k])) for k in keep}
    leaf = max(rel, key=rel.get)
    gaps["grad_rel_l2"], worst["grad_rel_l2"] = rel[leaf], (leaf, rel[leaf], ref["m1"][leaf])
    return gaps, worst


def reference_batches(run, seed, steps):
    t = run.cell["train"]
    return [(b["tokens"], b["labels"]) for b in (
        traffic.train_batch(seed, i, t["batch"], t["seq_len"], run.cfg["vocab_size"])
        for i in range(steps))]


def run(run):
    import jax

    t = run.cell["train"]
    with run.phase("build"):
        trainer = build(run, run.seed)
    with run.phase("warm_steps"):
        prog = warm(trainer, t["warm_steps"])
    compile_s = run.compiles.seconds
    steps = 0
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            with jax.profiler.TraceAnnotation("bench.step"):
                trainer.run_one_step()
            steps += 1
        elapsed = time.perf_counter() - t0
    tokens = steps * t["batch"] * t["seq_len"]
    run.counters.update(rows=steps * t["batch"], seq_len=t["seq_len"])
    tier = program.tiers(trainer.runtime)
    failed = steps if tier.get("reference") else 0
    if run.trace:
        import roofline

        batch = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), trainer.data.next_batch(), trainer._b_sh)
        with trainer._scope():
            run.kernels = roofline.pallas_kernels(trainer._train_step.trace(
                trainer.params, trainer.opt_state, trainer.ef_state, batch).jaxpr.jaxpr)
        del batch
    run.read_memory_peak()
    shutil.rmtree(trainer.tcfg.checkpoint_dir, ignore_errors=True)
    del trainer
    gc.collect()

    ref = R.train_readings(run.cfg, run.seed, reference_batches(run, run.seed, t["warm_steps"]),
                           run.cell["optimizer"])
    gaps, worst = compare(prog, ref)
    limits = run.cell["limits"]
    return {
        "metrics": {"train_tokens_per_s": tokens / elapsed, "setup_s": run.setup_s},
        "attempted": steps, "failed": failed,
        "setup_compile_s": compile_s,
        "notes": [f"dispatch tiers at trace time: {tier}",
                  f"window: {steps} steps of {t['batch']} x {t['seq_len']} tokens in {elapsed!r} s",
                  f"warm losses: program {prog['loss']}, reference {ref['loss']}",
                  f"worst leaves (leaf, program norm, reference norm): {worst}",
                  f"leaves left out (reference gradient under {KEEP_FRACTION} of the median): "
                  f"{sorted(k for k, v in ref['m1'].items() if v < KEEP_FRACTION * statistics.median(ref['m1'].values()))}"],
        "checks": {k: (gaps[k], limits[k]) for k in ("loss_gap", "grad_gap", "update_gap",
                                                     "grad_rel_l2")},
    }
