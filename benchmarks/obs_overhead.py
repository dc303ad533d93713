"""Observability-overhead benchmark: the obs plane's no-cost contract.

`repro.obs` instruments the hot paths (dispatch resolution, the per-step
span + metrics the trainer records, serving ticks). The contract is that a
**disabled** collector — the process default — costs one predicate branch
per site, and an **enabled** default-sampled collector stays in noise for a
kernel-mode step whose real work is jitted compute. This benchmark bounds
both:

* ``step.*`` — a jitted kernel-mode fwd+bwd step (matmul + rmsnorm through
  ``repro.dispatch``, gradients included) vs the per-step cost of exactly
  the obs calls the trainer adds around it (span + counter),
  measured in isolation where microsecond precision is possible; overhead
  is their ratio (see :func:`bench_step` for why not A+B-vs-B timing).
* ``resolve.*`` — the eager dispatch-resolution hot path (where the obs
  calls run per-call, not per-trace): warm cached resolves with the
  collector disabled vs enabled.

Assertion mode (``--assert-overhead``, the CI obs leg) enforces the
acceptance bars: disabled < 2% step overhead, enabled < 5%.

Run:  PYTHONPATH=src python -m benchmarks.obs_overhead [--quick] [--out J]
or as the ``obs.*`` rows of ``python -m benchmarks.run``.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict


def _min_round_us(fn, rounds: int, steps: int) -> float:
    """Median-free, drift-robust timing: per-round mean, min across rounds."""
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(steps):
            fn()
        best = min(best, (time.perf_counter() - t0) / steps)
    return best * 1e6


def bench_step(quick: bool = False) -> Dict:
    """Kernel-mode fwd+bwd step overhead, bounded by isolated instrumentation cost.

    A jitted CPU step's wall time is noisy at the ±5% level, so timing
    (step + obs) against (step) cannot resolve a 2% bound in CI. Instead we
    measure the two quantities whose ratio *is* the overhead, each where it
    can be measured precisely: the kernel-mode step time (min-of-rounds over
    the jitted fwd+bwd), and the per-step cost of exactly the obs calls the
    trainer adds around it (span + counter, timed in isolation
    over thousands of iterations). ``overhead = instr_cost / step_time`` is
    an upper bound on the added fraction — the obs calls do the same work
    whether or not a jitted call sits inside the span.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    import repro.obs as obs
    from repro.obs.collect import current_collector
    from repro.obs.trace import span

    rt = repro.runtime(mode="kernel", name="obs-bench")
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(128, 256), jnp.float32)
    w = jnp.asarray(rs.randn(256, 256), jnp.float32)
    g = jnp.asarray(rs.randn(256), jnp.float32)

    def loss(x, w, g):
        h = repro.dispatch("matmul", x, w)
        h = repro.dispatch("rmsnorm", h, g)
        return jnp.sum(h * h)

    with rt:
        step = jax.jit(jax.grad(loss, argnums=(1, 2)))
        jax.block_until_ready(step(x, w, g))     # trace + compile once

    def raw():
        jax.block_until_ready(step(x, w, g))

    def instr_only():
        # exactly what Trainer.run_one_step wraps around the jitted step,
        # with the step itself removed
        with span("train.step"):
            pass
        col = current_collector()
        if col.enabled:
            col.counter("train.tokens", x.shape[0])

    rounds, steps = (3, 10) if quick else (5, 30)
    step_us = _min_round_us(raw, rounds, steps)
    n = 2000 if quick else 10000
    # no collector entered: the ambient one is the disabled process default
    instr_disabled_us = _min_round_us(instr_only, 3, n)
    with obs.collect(name="obs-bench"):
        instr_enabled_us = _min_round_us(instr_only, 3, n)
    return {
        "step_us": step_us,
        "instr_disabled_us": instr_disabled_us,
        "instr_enabled_us": instr_enabled_us,
        "overhead_disabled_pct": 100.0 * instr_disabled_us / step_us,
        "overhead_enabled_pct": 100.0 * instr_enabled_us / step_us,
    }


def bench_resolve(quick: bool = False) -> Dict:
    """Warm cached dispatch resolution, collector disabled vs enabled.

    This is the path where obs code runs per *call* (resolve happens at
    trace time under jit, but eager callers and retraces pay it live).
    """
    import jax.numpy as jnp

    import repro.obs as obs
    from repro.core import TunedRuntime
    from repro.kernels.matmul import matmul as matmul_tunable

    rt = TunedRuntime(mode="kernel", name="obs-resolve-bench")
    args_list = [
        (jnp.zeros((64 << i, 128), jnp.float32),
         jnp.zeros((128, 64), jnp.float32))
        for i in range(4)
    ]
    for a in args_list:                          # warm the resolution cache
        rt.resolve(matmul_tunable, a)

    def loop():
        for a in args_list:
            rt.resolve(matmul_tunable, a)

    rounds, steps = (3, 20) if quick else (5, 100)
    disabled_us = _min_round_us(loop, rounds, steps) / len(args_list)
    with obs.collect(name="obs-resolve-bench"):
        enabled_us = _min_round_us(loop, rounds, steps) / len(args_list)
    return {
        "disabled_us": disabled_us,
        "enabled_us": enabled_us,
        "overhead_enabled_pct": max(
            0.0, 100.0 * (enabled_us - disabled_us) / disabled_us
        ),
    }


def bench(quick: bool = False) -> Dict:
    return {"step": bench_step(quick=quick), "resolve": bench_resolve(quick=quick)}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write the result dict as JSON (the committed "
                         "benchmarks/results/BENCH_obs.json baseline)")
    ap.add_argument("--assert-overhead", action="store_true",
                    help="fail (exit 1) unless disabled < 2%% and "
                         "enabled < 5%% step overhead — the CI gate")
    args = ap.parse_args()
    r = bench(quick=args.quick)
    s = r["step"]
    print(f"kernel-mode step: {s['step_us']:.0f} us; per-step obs cost "
          f"disabled {s['instr_disabled_us']:.2f} us "
          f"(+{s['overhead_disabled_pct']:.3f}%), "
          f"enabled {s['instr_enabled_us']:.2f} us "
          f"(+{s['overhead_enabled_pct']:.3f}%)")
    rv = r["resolve"]
    print(f"warm resolve: obs-disabled {rv['disabled_us']:.2f} us/call, "
          f"obs-enabled {rv['enabled_us']:.2f} us/call "
          f"(+{rv['overhead_enabled_pct']:.1f}%)")
    if args.out:
        import json

        with open(args.out, "w") as f:
            json.dump(r, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")
    if args.assert_overhead:
        ok = (s["overhead_disabled_pct"] < 2.0
              and s["overhead_enabled_pct"] < 5.0)
        print(f"overhead contract: "
              f"{'OK' if ok else 'VIOLATED'} "
              f"(disabled < 2%, enabled-default-sampled < 5%)")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
