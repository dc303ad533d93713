"""Tuner orchestration: space × search × evaluator → best correct variant.

This is the paper's §2 loop end-to-end:

  1. the reference implementation runs once to produce reference outputs;
  2. the search strategy proposes configs;
  3. each config is bound to a variant, compiled, executed and measured;
  4. outputs are compared with the reference (gate), failures pruned;
  5. the best surviving variant is recorded in the per-platform database.

`tune_or_lookup` is the legacy deployment helper (the dispatch runtime's
policy pipeline supersedes it): database hit ⇒ zero-cost specialization
(performance portability); miss ⇒ either tune now (`allow_tune=True`) or
fall back to the shape heuristic.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax

from .annotate import Tunable
from .database import Record, TuningDatabase, default_db, make_key, now
from .evaluate import Evaluator, WallClockEvaluator
from .params import Config, ParamSpace
from .platform import detect_platform
from .search import SearchAlgorithm, SearchResult, Trial, CoordinateDescent
from .search.base import INVALID

log = logging.getLogger("repro.tuner")


@dataclasses.dataclass
class TuningResult:
    best_config: Config
    best_objective: float
    default_objective: float          # the untuned baseline (paper's '-O3')
    evaluations: int
    search: SearchResult
    from_database: bool = False

    @property
    def speedup(self) -> float:
        if self.best_objective <= 0:
            return 1.0
        return self.default_objective / self.best_objective


def promoted_dtype(dtypes: Sequence[Any]) -> str:
    """Order-independent key dtype: the JAX type promotion of all array dtypes.

    Keying on any *single* argument's dtype makes mixed-dtype calls (bf16
    activations × f32 weights) produce argument-order-dependent database
    keys. The promoted dtype is symmetric in the arguments and names the
    precision the call actually computes in.

    Migration note: keys for mixed-dtype calls recorded before this change
    (which used the dtype of the *last* array argument — e.g. ``int32`` for
    softmax_xent's labels) will no longer hit; a campaign re-run or re-tune
    rebuilds them under the promoted-dtype key.
    """
    if not dtypes:
        return "f32"
    try:
        return _promote_cached(tuple(dtypes))
    except TypeError:          # unhashable dtype-likes: promote uncached
        import jax.numpy as jnp

        return str(jnp.result_type(*dtypes))


@functools.lru_cache(maxsize=512)
def _promote_cached(dtypes: tuple) -> str:
    # jnp.result_type costs ~25us; dispatch pays this per call, so memoize
    # on the (hashable) dtype tuple.
    import jax.numpy as jnp

    return str(jnp.result_type(*dtypes))


def _args_key(tunable: Tunable, args: Sequence[Any], platform: str, extra: str = "",
              dp_dims: Optional[Dict[int, int]] = None) -> str:
    """Database key for (tunable, concrete-or-traced args) on `platform`.

    Sharding-aware: inside a ``mesh_context`` that carries a ``dp_degree``
    (the Trainer's scope) the batch-leading args declared by the tunable's
    ``DispatchSpec.data_parallel_args`` are keyed on their per-device *local*
    shard shape (leading dim ÷ degree) — a jit trace carries global shapes,
    but each device executes the local shard, which is what a campaign
    tuned. Outside such a scope (serving warmup, campaign evaluation,
    tests, dry-run lowering) keys are unchanged.

    ``dp_dims`` (``{arg index: dim index}``) overrides the spec's
    leading-dim convention for THIS call: backward dispatch sites pass it
    when a transposed operand carries the token dim somewhere other than
    dim 0 (matmul's dL/dw keys ``x.T`` on dim 1).
    """
    shapes = []
    dtypes = []
    arg_dims: Dict[int, int] = {}
    spec = tunable.dispatch
    if dp_dims is None:
        dp_args = spec.data_parallel_args if spec is not None else (0,)
        dp_dims = {i: 0 for i in dp_args}
    for i, a in enumerate(args):
        if hasattr(a, "shape"):
            if i in dp_dims:
                arg_dims[len(shapes)] = dp_dims[i]
            shapes.append(tuple(a.shape))
            dtypes.append(getattr(a, "dtype", "float32"))
    shapes = _localize(shapes, arg_dims)
    key = make_key(tunable.name, platform, shapes, promoted_dtype(dtypes), extra)
    _warn_if_dp_approx(key)
    return key


def _warn_if_dp_approx(key: str) -> None:
    # ROADMAP-carried hazard, surfaced structurally: when the scope owner
    # flagged its dp_degree as approximate (microbatch batch dim divides the
    # mesh differently from the full input batch), the local-shape key we
    # just built may not match the shard XLA actually materializes. One
    # obs warning per key — recorded in the event buffer (and logged) even
    # when metric collection is disabled, never warnings.warn spam.
    from ..distributed.sharding import current_dp_approx

    if not current_dp_approx():
        return
    from ..obs.collect import warn_once

    warn_once(
        "dispatch.local_key_approx",
        key=key,
        detail=(
            "microbatch batch dim divides the mesh differently from the "
            "full input batch; local-shape key approximates XLA's shard"
        ),
    )


def _localize(shapes, arg_dims):
    # Late import: distributed is a higher layer; the ambient-context check
    # is a single contextvar read, so unsharded dispatch stays cheap.
    from ..distributed.sharding import localize_shapes

    return localize_shapes(shapes, batch_arg_dims=arg_dims)


def autotune(
    tunable: Tunable,
    args: Sequence[Any],
    search: Optional[SearchAlgorithm] = None,
    evaluator: Optional[Evaluator] = None,
    db: Optional[TuningDatabase] = None,
    key_extra: str = "",
    save: bool = True,
    seed_configs: Optional[Sequence[Config]] = None,
) -> TuningResult:
    """Full tuning pass for `tunable` on concrete `args`.

    `seed_configs` warm-start the search (transfer tuning): configs that won
    on a neighbouring shape bucket or sibling platform are evaluated first,
    so local strategies converge in far fewer evaluations than a cold start.
    Invalid seeds are silently dropped by the strategy.
    """
    search = search or CoordinateDescent(budget=48)
    evaluator = evaluator or WallClockEvaluator()
    platform = detect_platform().name

    # 1. Reference outputs (the correctness oracle).
    reference = None
    if tunable.reference is not None:
        reference = jax.jit(tunable.reference)(*args)
        jax.block_until_ready(reference)

    # Static legality pre-pass: configs whose abstract grid model is
    # infeasible on this platform (lane misalignment, OOB index map, racy
    # output ref) never reach compile+run — the Petrovič et al. 2019
    # "filter before measurement" step. Fail-open: a model-building error
    # must never block tuning, only skip the pruning.
    illegal: Dict[str, str] = {}
    try:
        from .gridmodel import space_illegal

        arrays = [a for a in args if hasattr(a, "shape")]
        shapes = tuple(tuple(a.shape) for a in arrays)
        # Tiles are laid out, and VMEM is spent, at the leading operand's type.
        dtype = str(arrays[0].dtype) if arrays else None
        for ck, (cat, reason) in space_illegal(
            tunable.name, platform, shapes or None, dtype
        ).items():
            illegal[ck] = f"{cat}: {reason}"
    except Exception:                                 # pragma: no cover
        log.debug("legality pre-pass failed for %s", tunable.name, exc_info=True)

    # 2-4. Search with compile+run+gate per proposed config.
    def objective(config: Config) -> Trial:
        pruned = illegal.get(ParamSpace.config_key(config))
        if pruned is not None:
            log.debug("variant %s statically pruned: %s", config, pruned)
            return Trial(
                config=config, objective=INVALID, ok=False,
                meta={"pruned": pruned},
            )
        variant = tunable.variant(**config)
        m = evaluator.evaluate(variant, args, reference=reference)
        if not m.ok:
            log.debug("variant %s pruned: %s", config, m.error)
        return Trial(config=config, objective=m.objective, ok=m.ok, meta=m.meta)

    t0 = time.perf_counter()
    result = search.run(tunable.space, objective, seeds=tuple(seed_configs or ()))
    elapsed = time.perf_counter() - t0
    if result.best is None:
        raise RuntimeError(
            f"autotuning {tunable.name}: no valid variant found "
            f"({result.evaluations} evaluations)"
        )

    # Baseline: the default (heuristic) config = the 'unannotated' program.
    default_cfg = tunable.default_config(*args)
    base = evaluator.evaluate(tunable.variant(**default_cfg), args, reference=reference)
    default_obj = base.objective if base.ok else INVALID

    # The tuner must never regress (claim C3): a budget too small to rediscover
    # the baseline keeps the measured default as the winner.
    best_config, best_objective = result.best_config, result.best_objective
    if base.ok and tunable.space.is_valid(default_cfg) and default_obj < best_objective:
        best_config, best_objective = dict(default_cfg), default_obj

    # 5. Persist.
    if db is None:
        db = default_db()
    key = _args_key(tunable, args, platform, key_extra)
    db.put(
        Record(
            key=key,
            config=best_config,
            objective=best_objective,
            evaluator=evaluator.name,
            evaluations=result.evaluations,
            timestamp=now(),
            meta={
                "search": search.name,
                "default_objective": default_obj,
                "search_seconds": elapsed,
            },
        ),
        save=save,
    )
    log.info(
        "tuned %s: %.3gs -> %.3gs (%.2fx) in %d evals",
        key, default_obj, best_objective,
        (default_obj / best_objective if best_objective else 1.0),
        result.evaluations,
    )
    return TuningResult(
        best_config=best_config,
        best_objective=best_objective,
        default_objective=default_obj,
        evaluations=result.evaluations,
        search=result,
    )


def tune_or_lookup(
    tunable: Tunable,
    args: Sequence[Any],
    db: Optional[TuningDatabase] = None,
    allow_tune: bool = False,
    key_extra: str = "",
    allow_cover: bool = True,
    **tune_kwargs,
) -> Config:
    """Deployment-time config resolution.

    Precedence: exact DB hit > tune-now (`allow_tune`) > cover-set entry for
    the nearest tuned shape ('a few fit most': a small set of campaign
    winners covers most unseen buckets) > the shape heuristic default.
    """
    db = db or default_db()
    platform = detect_platform().name
    key = _args_key(tunable, args, platform, key_extra)
    rec = db.lookup(key)
    if rec is not None and tunable.space.is_valid(rec.config):
        return dict(rec.config)
    if allow_tune:
        return autotune(tunable, args, db=db, key_extra=key_extra, **tune_kwargs).best_config
    if allow_cover:
        shapes = [tuple(a.shape) for a in args if hasattr(a, "shape")]
        for entry in db.lookup_cover(tunable.name, platform, shapes):
            cfg = entry.get("config")
            if cfg is not None and tunable.space.is_valid(cfg):
                return dict(cfg)
    return tunable.default_config(*args)
