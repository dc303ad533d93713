"""Production serving launcher: continuous-batching engine on the chosen mesh.

The engine gets its own scoped dispatch runtime (`repro.runtime`): pass a
campaign-exported per-platform database via ``--db`` and every kernel the
model traces resolves against it — no process-global state — and the run
ends with the runtime's telemetry report (which resolution tier served each
kernel×bucket: the sustained-performance accounting).

    # one chip, full widths, kernel path, prompts of 16..300 tokens:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \\
        --mesh 1x1 --mode kernel --max-seq 512 --max-prompt 300
    # pod, with a campaign artifact:
    python -m repro.launch.serve --arch qwen2.5-3b --requests 64 \\
        --db tpu-v5e.json --warmup
    # dev smoke:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
from typing import List, Optional

import jax
import numpy as np

import repro
from ..configs.base import SHAPES, get_config
from ..core.database import TuningDatabase
from ..models import lm
from ..serving.engine import EngineConfig, Request, ServingEngine
from . import defaults
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_mesh_from_spec, make_production_mesh


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-prompt", type=int, default=16,
                    help="prompt lengths spread from 16 up to this many tokens")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the prompts")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="explicit mesh spec DATAxMODEL (e.g. 1x1) over the "
                         "available devices; overrides the smoke/production "
                         "mesh choice")
    ap.add_argument("--db", default=None,
                    help="campaign-exported tuning database for this platform")
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "kernel", "reference"),
                    help="dispatch mode for the engine's runtime")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-resolve every slot-pool bucket before serving")
    ap.add_argument("--platform", default=None,
                    help="override the fingerprinted platform key (db namespace)")
    ap.add_argument("--telemetry-out", default=None,
                    help="write the runtime telemetry snapshot JSON here "
                         "(feed to `campaign status --telemetry` / "
                         "benchmarks/campaign_report.py)")
    ap.add_argument("--metrics-out", default=None,
                    help="enable the obs collector for the run and write its "
                         "snapshot JSON here (render with "
                         "`python -m repro.obs report --metrics <file>`)")
    return ap


def build(args, runtime: Optional[repro.TunedRuntime] = None):
    """(cfg, engine, runtime) for parsed launcher ``args``.

    The engine runs under ``runtime`` when one is given, else under a
    runtime made from ``--db``/``--mode``. Weights are random, drawn from
    ``--seed``.
    """
    cfg = get_config(args.arch)
    shape = SHAPES["decode_32k"]
    if args.smoke:
        cfg = cfg.reduced()
        if cfg.num_experts:
            cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    if args.mesh:
        mesh = make_mesh_from_spec(args.mesh)
    elif args.smoke:
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    layout = defaults.default_layout(cfg, args.multi_pod)
    run = defaults.default_run(cfg, shape)
    if args.smoke:
        run = dataclasses.replace(
            run, q_chunk=32, k_chunk=max(32, args.max_seq), loss_chunk=32
        )

    params = jax.jit(lambda k: lm.init_params(k, cfg)[0])(
        jax.random.PRNGKey(args.seed)
    )
    rt = runtime or repro.runtime(
        db=TuningDatabase(args.db) if args.db else None,
        mode=args.mode, name="serve",
    )
    engine = ServingEngine(
        cfg, run, params, mesh, layout,
        EngineConfig(max_batch=8, max_seq=args.max_seq),
        runtime=rt,
    )
    return cfg, engine, rt


def make_requests(cfg, args) -> List[Request]:
    """``--requests`` staggered requests (arrival every tick, exercising
    in-flight admission), alternating greedy and sampled decoding, with
    prompt lengths log-uniform in [16, ``--max-prompt``]."""
    rs = np.random.RandomState(args.seed)
    hi = max(16, args.max_prompt)
    lens = np.exp(rs.uniform(np.log(16), np.log(hi), args.requests)).astype(int)
    lens[:1] = 16
    lens[-1:] = hi
    return [
        Request(
            prompt=rs.randint(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=args.new_tokens,
            temperature=0.7 if i % 2 else 0.0,
            seed=i,
            arrival_time=float(i),
        )
        for i, n in enumerate(lens)
    ]


def main(argv=None):
    ap = parser()
    args = ap.parse_args(argv)
    if args.platform:
        from ..core.platform import set_platform_override

        set_platform_override(args.platform)
    if args.db and not os.path.exists(args.db):
        # A typo'd path would otherwise open as an EMPTY database and every
        # bucket would silently resolve at the heuristic tier — the exact
        # wasted-artifact failure warmup exists to prevent.
        ap.error(f"--db {args.db}: no such file")
    enable_compile_cache()
    cfg, engine, rt = build(args)

    import repro.obs as obs
    from ..obs.metrics import percentile_row

    col = (
        obs.collect(name="serve")
        if args.metrics_out else contextlib.nullcontext()
    )
    with col:
        if args.warmup:
            resolved = engine.warmup()
            print(f"warmup resolved {len(resolved)} kernel buckets")
        for req in make_requests(cfg, args):
            engine.submit(req)
        done = engine.serve()
    toks = sum(len(r.output) for r in done)
    st = engine.stats
    ttft = [r.first_token_s - r.submitted_s for r in done]
    print(f"served {len(done)} requests / {toks} tokens; "
          f"p50 latency {sorted(r.latency_s for r in done)[len(done)//2]:.2f}s "
          f"({sorted(r.latency_steps for r in done)[len(done)//2]} ticks); "
          f"TTFT p50 {np.percentile(ttft, 50):.2f}s p95 {np.percentile(ttft, 95):.2f}s; "
          f"{st['decode_steps']} pool decode steps, "
          f"{st['tokens_out']/max(1, st['decode_steps']):.2f} tok/step")
    if args.metrics_out:
        snap = col.snapshot()
        for name, label in (("span.serve.admit", "admission"),
                            ("serve.per_token_s", "per-token"),
                            ("serve.latency_s", "request latency")):
            row = percentile_row(snap, name)
            if row:
                print(f"{label}: p50 {row['p50']*1e3:.2f}ms  "
                      f"p95 {row['p95']*1e3:.2f}ms  p99 {row['p99']*1e3:.2f}ms "
                      f"(n={row['count']})")
        col.write(args.metrics_out)
        print(f"wrote metrics -> {args.metrics_out}")
    print(rt.telemetry.report())
    if args.telemetry_out:
        rt.telemetry.write(args.telemetry_out)
        print(f"wrote telemetry -> {args.telemetry_out}")


if __name__ == "__main__":
    main()
