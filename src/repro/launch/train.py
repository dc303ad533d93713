"""Production training launcher.

On a TPU pod this builds the production mesh and the full-size model; on a
dev host it degrades to the 1-device mesh + reduced config (--smoke). The
same Trainer/steps path the multi-pod dry-run compiled is what runs here —
build_cell is shared, so dry-run success is launch success.

Training runs under a *pinned dispatch runtime* (mirroring launch/serve):
``--db`` points every kernel the step traces at a campaign-exported
per-platform database, ``--mode`` picks kernel/reference/auto dispatch, and
the run ends with the runtime's telemetry report — which resolution tier
(exact / cover / heuristic / reference) served each kernel×bucket. Because
the trainer traces under its mesh context, those buckets are keyed on
per-device *local* shard shapes: the shapes ``campaign plan --train-mesh``
pre-tunes.

    # one chip, full widths, kernel path, 8 x 1024 tokens per step:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \\
        --mesh 1x1 --batch 8 --seq-len 1024 --steps 3 --mode kernel
    # pod (256 chips), with a campaign artifact:
    python -m repro.launch.train --arch mixtral-8x7b --shape train_4k \\
        --steps 1000 --db tpu-v5e.json --mode kernel
    # dev smoke:
    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b --smoke --steps 5
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import logging
import os
from typing import Optional

import jax

import repro
from ..configs.base import SHAPES, get_config
from ..core.database import TuningDatabase
from ..core.platform import set_platform_override
from ..data.pipeline import DataConfig
from ..optim import adamw
from ..train.trainer import Trainer, TrainerConfig
from . import defaults
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_mesh_from_spec, make_production_mesh



def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (sequences per step); default: the "
                         "shape's")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="tokens per sequence; default: the shape's")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + host mesh (CPU dev box)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="explicit mesh spec DATAxMODEL (e.g. 2x4) over the "
                         "available devices; overrides the smoke/production "
                         "mesh choice")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8_ef"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--db", default=None,
                    help="campaign-exported tuning database for this platform")
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "kernel", "reference"),
                    help="dispatch mode for the trainer's runtime")
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
    ap.add_argument("--metrics-sample", type=float, default=1.0,
                    help="obs sample rate for high-frequency sites (1.0 = all)")
    return ap


def build(args, runtime: Optional[repro.TunedRuntime] = None):
    """(trainer, runtime) for parsed launcher ``args``; the trainer runs
    under ``runtime`` when one is given, else under one made from
    ``--db``/``--mode``."""
    cfg = get_config(args.arch)
    shape = SHAPES["train_smoke" if args.smoke else args.shape]
    if args.smoke:
        cfg = cfg.reduced()
    if args.mesh:
        mesh = make_mesh_from_spec(args.mesh)
    elif args.smoke:
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    shape = dataclasses.replace(
        shape,
        global_batch=args.batch or shape.global_batch,
        seq_len=args.seq_len or shape.seq_len,
    )
    run = defaults.default_run(cfg, shape)
    layout = defaults.default_layout(cfg, args.multi_pod)

    rt = runtime or repro.runtime(
        db=TuningDatabase(args.db) if args.db else None,
        mode=args.mode, name="train",
    )
    trainer = Trainer(
        cfg, run, mesh, layout,
        DataConfig(seed=args.seed, batch_size=shape.global_batch,
                   seq_len=shape.seq_len,
                   host_index=jax.process_index(),
                   host_count=jax.process_count()),
        adamw.AdamWConfig(total_steps=args.steps),
        TrainerConfig(
            total_steps=args.steps,
            checkpoint_every=args.ckpt_every,
            checkpoint_dir=args.ckpt_dir,
            grad_compression=args.compression,
            seed=args.seed,
        ),
        runtime=rt,
    )
    return trainer, rt


def main(argv=None):
    logging.basicConfig(level=logging.INFO, format="%(name)s: %(message)s")
    ap = parser()
    args = ap.parse_args(argv)
    if args.db and not os.path.exists(args.db):
        # A typo'd path would otherwise open as an EMPTY database and every
        # bucket would silently resolve at the heuristic tier.
        ap.error(f"--db {args.db}: no such file")
    if args.platform:
        set_platform_override(args.platform)
    enable_compile_cache()

    # Observability is opt-in: without --metrics-out the ambient collector
    # stays the disabled process default and instrumentation costs one
    # branch per site (the overhead contract).
    import repro.obs as obs

    col = (
        obs.collect(name="train", sample_rate=args.metrics_sample)
        if args.metrics_out else contextlib.nullcontext()
    )
    with col:
        trainer, rt = build(args)
        # resume if a checkpoint exists
        if trainer.ckpt.latest_step() is not None:
            trainer.restore_checkpoint()
        metrics = trainer.train()
    print(f"done at step {trainer.step}: {metrics}")
    print(rt.telemetry.report())
    if args.telemetry_out:
        rt.telemetry.write(args.telemetry_out)
        print(f"wrote telemetry -> {args.telemetry_out}")
    if args.metrics_out:
        col.write(args.metrics_out)
        print(f"wrote metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
