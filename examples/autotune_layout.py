import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# ^ must precede any jax import: this example tunes the DISTRIBUTION layout,
# so it needs a small fake mesh (2 data x 4 model) on the CPU host.

"""Layout autotuning — the paper's technique applied to sharding.

The knob space here is not a tile shape but the distribution layout
(head-aware TP, FSDP, microbatch count, grad wire format). Variants are
scored by the CostModelEvaluator: each candidate is lowered + compiled for
the mesh and its dominant roofline term (from compiled HLO, trip-aware
collective parse) is the objective — exactly the loop behind the §Perf
hillclimbs, shrunk to run in ~2 minutes on CPU.

    PYTHONPATH=src python examples/autotune_layout.py
"""
import dataclasses

import jax

from repro.configs import get_config
from repro.core import (
    BoolParam,
    CostModelEvaluator,
    EnumParam,
    IntParam,
    ParamSpace,
    tunable,
)
from repro.core.search import ExhaustiveSearch
from repro.core.search.base import Trial
from repro.distributed.sharding import Layout
from repro.launch import steps
from repro.launch.defaults import default_run
from repro.configs.base import SHAPES, ShapeSpec
from repro.models.transformer import RunConfig


LAYOUT_SPACE = ParamSpace(
    [
        BoolParam("head_aware"),
        BoolParam("fsdp"),
        IntParam("microbatches", [1, 2]),
        EnumParam("grad_compression", ["none", "bf16"]),
    ]
)


def main():
    cfg = get_config("qwen2-0.5b").reduced()
    # a small but real shape so compiles stay ~seconds
    shape = ShapeSpec("mini_train", seq_len=128, global_batch=8, kind="train")
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    evaluator = CostModelEvaluator(chips=8)

    def lower_variant(**knobs):
        layout = Layout(
            fsdp=knobs["fsdp"],
            head_aware=knobs["head_aware"],
            counts=(("heads", cfg.num_heads), ("kv_heads", cfg.num_kv_heads)),
        )
        run = RunConfig(
            remat="none", q_chunk=64, k_chunk=64, loss_chunk=64,
            microbatches=knobs["microbatches"],
            grad_compression=knobs["grad_compression"],
        )
        cell = steps.build_cell(cfg, shape, mesh, layout, run)
        lowered = steps.lower_cell(cell, mesh)
        return lowered.compile()

    def objective(config):
        m = evaluator.evaluate(lambda: lower_variant(**config))
        r = m.meta.get("roofline", {})
        print(
            f"  {config} -> "
            + (
                f"step bound {m.objective*1e3:.2f}ms (dominant: {r.get('dominant')})"
                if m.ok
                else f"INVALID: {m.error}"
            )
        )
        return Trial(config=config, objective=m.objective, ok=m.ok,
                     meta=m.meta)

    print(f"searching {LAYOUT_SPACE.cardinality} layout variants "
          f"(compile-and-analyse each):")
    res = ExhaustiveSearch(budget=16).run(LAYOUT_SPACE, objective)
    print(f"\nbest layout: {res.best_config}")
    print(f"step-time bound: {res.best_objective*1e3:.2f}ms")
    best_roofline = res.best.meta["roofline"]
    print(f"terms: compute {best_roofline['compute_s']*1e3:.2f}ms | "
          f"memory {best_roofline['memory_s']*1e3:.2f}ms | "
          f"collective {best_roofline['collective_s']*1e3:.2f}ms")


if __name__ == "__main__":
    main()
