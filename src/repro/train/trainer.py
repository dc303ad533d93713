"""The training loop: sharded init, step execution, checkpoint/restart,
straggler monitoring, gradient compression — assembled from the substrate.

Single-host usage (examples, tests) and pod usage share this class; the
difference is the mesh handed in. The Trainer never constructs device state
outside the mesh's shardings, so the same code drives 1 CPU or 512 chips.

Dispatch: the model's kernel sites (projection/FFN gemms, rmsnorm, the fused
loss, flash attention) resolve through the dispatch runtime — forward AND
backward: in kernel mode the gradients are dispatch sites too (transposed
matmul gemms, the ``*_bwd`` tunables), resolved under the same scope with
``bwd``-tagged telemetry, so a planned campaign (``campaign plan
--train-mesh``) pre-tunes everything a train step executes. Pass
``runtime=repro.runtime(db=..., mode=...)`` to pin a campaign database for
the whole run — every trace the trainer builds executes under that scope
*and* under the trainer's ``mesh_context``, so database keys use per-device
local shard shapes (what a campaign tuned), and ``runtime.telemetry``
reports which tier served each kernel×bucket per phase. With
``runtime=None`` the ambient/default runtime applies, as before
(``runtime=repro.runtime(..., bwd_dispatch=False)`` restores the old
reference-VJP backward recompute).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.runtime import dispatch_phase
from ..data.pipeline import DataConfig, SyntheticPipeline
from ..distributed import sharding as shd
from ..distributed.collectives import compress_grads, ef_init
from ..models import lm
from ..models.transformer import RunConfig
from ..obs.collect import current_collector as _obs_collector
from ..obs.trace import span as _obs_span
from ..testing.faults import fault_point as _fault_point
from ..optim import adamw
from . import checkpoint as ckpt_mod
from .resilience import RestartPolicy, StragglerMonitor, run_with_recovery

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 25
    checkpoint_dir: str = "checkpoints"
    checkpoint_keep: int = 3
    async_checkpoint: bool = True
    log_every: int = 10
    seed: int = 0
    grad_compression: str = "none"      # none | bf16 | int8_ef
    max_failures: int = 10


class Trainer:
    def __init__(
        self,
        cfg: ArchConfig,
        run: RunConfig,
        mesh: jax.sharding.Mesh,
        layout: shd.Layout,
        data_cfg: DataConfig,
        opt_cfg: Optional[adamw.AdamWConfig] = None,
        tcfg: Optional[TrainerConfig] = None,
        runtime: Optional[Any] = None,
    ):
        self.cfg = cfg
        self.run = run
        self.mesh = mesh
        self.layout = layout
        self.opt_cfg = opt_cfg or adamw.AdamWConfig()
        self.tcfg = tcfg or TrainerConfig()
        self.runtime = runtime          # a TunedRuntime, or None = ambient
        # The degree the step's batch dim is sharded at — drives local-shape
        # database keying. Computed ONCE from the per-microbatch batch dim
        # (what every kernel site actually sees), mirroring the campaign
        # planner's plan_training_jobs; never re-derived per argument.
        # Known approximation: when microbatching shrinks the batch below
        # the input sharding's full multi-axis degree (b/k not divisible by
        # the axes that divide b), XLA's reshape propagation decides the
        # true per-device shape — keys then state the b/k-derived degree,
        # which planner and dispatch still agree on (see ROADMAP).
        sizes = shd.mesh_axis_sizes(mesh)
        self._dp_degree = shd.data_parallel_degree(
            sizes, layout,
            max(1, data_cfg.batch_size // max(1, run.microbatches)),
        )
        # When the microbatch divides the mesh differently from the full
        # input batch, the degree above is an approximation of XLA's actual
        # shard choice — flagged so the keying layer emits a one-time
        # structured warning naming the affected key.
        self._dp_approx = (
            run.microbatches > 1
            and self._dp_degree
            != shd.data_parallel_degree(sizes, layout, data_cfg.batch_size)
        )
        self.data = SyntheticPipeline(cfg, data_cfg)
        self.ckpt = ckpt_mod.Checkpointer(
            self.tcfg.checkpoint_dir, keep=self.tcfg.checkpoint_keep
        )
        self.monitor = StragglerMonitor()
        self.step = 0
        self._build()

    def _scope(self):
        """The trainer's execution scope: pinned runtime (if any) + ambient
        mesh/layout context.

        Entered around every call that may *trace* model code (init, the
        train step): jax.jit traces lazily, so the scope must be live at
        call time, not construction time. The mesh context is what switches
        dispatch keying to per-device local shard shapes.
        """
        stack = contextlib.ExitStack()
        if self.runtime is not None:
            stack.enter_context(self.runtime)
        stack.enter_context(
            shd.mesh_context(self.mesh, self.layout, dp_degree=self._dp_degree,
                             dp_approx=self._dp_approx)
        )
        return stack

    # ------------------------------------------------------------------ build
    def _build(self) -> None:
        cfg, mesh, layout = self.cfg, self.mesh, self.layout
        params_abs, axes = lm.abstract_params(cfg)
        self.p_sh = shd.param_shardings(axes, params_abs, mesh, layout)
        rep = shd.replicated(mesh)
        self.o_sh = adamw.state_shardings(self.p_sh, self.opt_cfg.master_fp32, rep)

        def init_all(rng):
            params, _ = lm.init_params(rng, cfg)
            opt_state = adamw.init(self.opt_cfg, params)
            return params, opt_state

        init_jit = jax.jit(init_all, out_shardings=(self.p_sh, self.o_sh))
        with self._scope():
            self.params, self.opt_state = init_jit(
                jax.random.PRNGKey(self.tcfg.seed)
            )
            if self.tcfg.grad_compression == "int8_ef":
                self.ef_state = jax.jit(ef_init, out_shardings=self.p_sh)(self.params)
            else:
                self.ef_state = None

        comp_mode = self.tcfg.grad_compression
        run, opt_cfg = self.run, self.opt_cfg

        def loss_fn(params, batch):
            return lm.loss_fn(params, batch, cfg, run)

        def train_step(params, opt_state, ef_state, batch):
            if run.microbatches > 1:
                k = run.microbatches
                mbs = jax.tree_util.tree_map(
                    lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch
                )

                def body(carry, mb):
                    g_acc, l_acc = carry
                    (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                    return (
                        jax.tree_util.tree_map(lambda a, b: a + b.astype(a.dtype), g_acc, g),
                        l_acc + l,
                    ), None

                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (grads, loss), _ = jax.lax.scan(
                    body, (g0, jnp.zeros((), jnp.float32)), mbs
                )
                grads = jax.tree_util.tree_map(lambda g: g / k, grads)
                loss = loss / k
            else:
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch
                )
            grads, ef_state = compress_grads(grads, ef_state, comp_mode)
            # Phase-tag the optimizer update: any dispatch resolved while
            # tracing it carries phase="opt" in telemetry/obs. adamw itself
            # contains no dispatch sites today, so existing fwd/bwd-only
            # accounting is unchanged — the tag is the hook.
            with dispatch_phase("opt"):
                params, opt_state, om = adamw.update(
                    opt_cfg, grads, opt_state, params
                )
            return params, opt_state, ef_state, {"loss": loss, **om}

        b_abs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.data.next_batch(),
        )
        self.data.step -= 1  # peek, don't consume
        b_sh = shd.data_specs(b_abs, mesh, layout)
        ef_sh = self.p_sh if self.ef_state is not None else None
        self._train_step = jax.jit(
            train_step,
            in_shardings=(self.p_sh, self.o_sh, ef_sh, b_sh),
            out_shardings=(self.p_sh, self.o_sh, ef_sh, None),
            donate_argnums=(0, 1, 2),
        )
        self._b_sh = b_sh

    # ------------------------------------------------------------------- state
    def _state_tree(self):
        t = {
            "params": self.params,
            "opt": self.opt_state,
            "data": {"step": jnp.asarray(self.data.step, jnp.int32)},
            "trainer_step": jnp.asarray(self.step, jnp.int32),
        }
        if self.ef_state is not None:
            t["ef"] = self.ef_state
        return t

    def save_checkpoint(self) -> None:
        tree = self._state_tree()
        if self.tcfg.async_checkpoint:
            self.ckpt.save_async(self.step, tree)
        else:
            self.ckpt.save(self.step, tree)

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        self.ckpt.wait()
        step = step if step is not None else self.ckpt.latest_step()
        if step is None:
            log.warning("no checkpoint to restore; restarting from scratch")
            self._build()
            self.step = 0
            self.data.step = 0
            return 0
        target = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self._state_tree()
        )
        shardings = {
            "params": self.p_sh,
            "opt": self.o_sh,
            "data": {"step": shd.replicated(self.mesh)},
            "trainer_step": shd.replicated(self.mesh),
        }
        if self.ef_state is not None:
            shardings["ef"] = self.p_sh
        tree = self.ckpt.restore(step, target, shardings)
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        if self.ef_state is not None:
            self.ef_state = tree["ef"]
        self.data.step = int(tree["data"]["step"])
        self.step = int(tree["trainer_step"])
        log.info("restored checkpoint at step %d", self.step)
        return self.step

    # -------------------------------------------------------------------- run
    def run_one_step(self) -> Dict[str, float]:
        with _obs_span("train.data"):
            batch_np = self.data.next_batch()
        batch = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), batch_np, self._b_sh
        )
        t0 = time.perf_counter()
        # The span closes after the metrics are read back, so it times the
        # step itself, not its enqueue.
        with _obs_span("train.step", step=self.step):
            with self._scope():
                self.params, self.opt_state, self.ef_state, metrics = (
                    self._train_step(
                        self.params, self.opt_state, self.ef_state, batch
                    )
                )
            metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.perf_counter() - t0
        self.monitor.record(self.step, dt)
        self.step += 1
        metrics["step_time_s"] = dt
        col = _obs_collector()
        if col.enabled:
            leaves = jax.tree_util.tree_leaves(batch_np)
            tokens = (
                int(np.prod(leaves[0].shape[:2]))
                if leaves and getattr(leaves[0], "ndim", 0) >= 2 else 0
            )
            if tokens and dt > 0:
                col.counter("train.tokens", tokens)
                col.gauge("train.tokens_per_s", tokens / dt)
        if self.step % self.tcfg.checkpoint_every == 0:
            self.save_checkpoint()
        if self.step % self.tcfg.log_every == 0:
            log.info(
                "step %d loss %.4f (%.2fs)", self.step, metrics["loss"], dt
            )
        return metrics

    def train(self, fail_hook: Optional[Callable[[int], None]] = None) -> Dict:
        """Run to total_steps with recovery. `fail_hook(step)` (tests) may
        raise to simulate node failure at a given step."""

        def step_fn(step: int) -> Dict:
            if fail_hook is not None:
                fail_hook(step)
            # Named chaos site: a FaultPlan can fail chosen steps without the
            # caller wiring a fail_hook (recovery drills exercise the same
            # run_with_recovery path either way).
            _fault_point(f"train.step:{step}", step=step)
            return self.run_one_step()

        def restore_fn() -> int:
            return self.restore_checkpoint()

        policy = RestartPolicy(max_failures=self.tcfg.max_failures)
        metrics = run_with_recovery(
            step_fn,
            restore_fn,
            total_steps=self.tcfg.total_steps,
            start_step=self.step,
            policy=policy,
            sleep=lambda s: None,
        )
        self.ckpt.wait()
        return metrics
