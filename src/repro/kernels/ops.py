"""Migration guide: the old deployment surfaces and where they went.

This module used to *be* the deployment surface: a hand-written wrapper per
kernel, a process-global ``_STATE`` mode dict, and a hard-coded
exact→cover→heuristic chain inside each wrapper. All of that lives in the
dispatch runtime now (:mod:`repro.core.runtime`), and the deprecated shims
(``ops.set_kernel_mode`` / ``ops.kernels_enabled`` / ``ops.<kernel>``,
DeprecationWarning since the runtime redesign) have completed their cycle
and are **removed**. What remains here is the migration guide plus the
registry-populating imports (``from repro.kernels import ops`` keeps working
as a one-stop import for the kernel tunables).

Old API (removed)                            New API
-----------------------------------------    ----------------------------------
``ops.set_kernel_mode(True)``                ``with repro.runtime(mode="kernel"): ...``
``ops.kernels_enabled()``                    ``repro.current_runtime().kernel_mode_active``
``set_default_db(db); ops.matmul(x, w)``     ``with repro.runtime(db=db): repro.dispatch("matmul", x, w)``
``ops.matmul(x, w, config={...})``           ``repro.dispatch("matmul", x, w, config={...})``
hand-written wrapper per new kernel          none: ``@tunable(..., dispatch=DispatchSpec(...))``
                                             auto-generates the entry point

Database-key semantics (what a record must look like to hit):

* **Platform namespace** — keys carry the *detected* platform
  (``tpu-v4`` / ``tpu-v5e`` / ``cpu-host``, fingerprinted from
  ``jax.devices()``). Override with ``REPRO_PLATFORM``,
  ``repro.core.set_platform_override(...)``, or a per-runtime
  ``repro.runtime(platform=...)`` — an unknown name clones the fingerprinted
  profile under the new name, fully isolating the namespace.
* **Promoted dtype** — the dtype field is the JAX promotion of *all* array
  args (order-independent). Records for mixed-dtype calls keyed on a single
  argument's dtype (notably softmax_xent, once keyed ``int32``) no longer
  exact-hit; they still warm-start re-tunes as transfer neighbours.
* **Local shard shapes** — inside an active ``mesh_context`` (training, any
  jit-sharded trace), batch-sharded args (``DispatchSpec.data_parallel_args``,
  or a per-call ``dp_dims`` override for transposed backward operands) are
  keyed on their per-device *local* shard shape: a record tuned at
  ``(batch/dp, seq, d)`` is the record dispatch finds. Unsharded call sites
  are unchanged. Records tuned for sharded sites *before* local-shape
  keying were keyed on global shapes — they only warm-start; re-plan with
  ``campaign plan --train-mesh ...`` and re-run the campaign.
* **Backward keys** — gradients are dispatch sites too (``DispatchSpec.bwd``
  + ``vjp="dispatch"``): matmul's dL/dx and dL/dw resolve as
  transposed-operand ``matmul`` keys, and flash attention / rmsnorm /
  softmax-xent resolve dedicated ``flash_attention_bwd`` / ``rmsnorm_bwd``
  / ``softmax_xent_bwd`` tunables with their own records. The training
  planner (``plan_training_jobs``) emits this backward roster at local
  shard shapes, so ``campaign plan --train-mesh`` pre-tunes it.
  **Migration hazard**: campaigns exported before the tuned backward plane
  have NO backward records — a kernel-mode train step against such a
  database resolves its gradient sites at warm-start/cover/heuristic tiers,
  never ExactHit. Re-plan and re-run the campaign to bank them; or pin
  ``repro.runtime(bwd_dispatch=False)`` to restore the old reference-VJP
  recompute (fwd-only tuning) while you do.

Residual contract (``DispatchSpec.residuals``)
----------------------------------------------

Forward tunables may return auxiliary outputs alongside the primal —
forward intermediates the backward pass would otherwise recompute:

===================  ==============================  =======================
tunable              residual                        consumed by
===================  ==============================  =======================
``flash_attention``  per-query logsumexp             ``flash_attention_bwd``
                     ``[b, h, s_q]`` f32             (with the primal ``o``
                                                     for delta rows)
``rmsnorm``          per-row inverse rms ``[rows]``  ``rmsnorm_bwd``
``softmax_xent``     per-row logsumexp ``[rows]``    ``softmax_xent_bwd``
===================  ==============================  =======================

With ``residuals=N`` the bound variant (and the *tuning* reference — the
``ref.*_res`` oracles) returns ``(primal, *aux)``; dispatch saves the
canonical args, the primal, and the aux into the ``custom_vjp`` residuals
and calls the backward plan as ``bwd(ct, *args, primal, *aux, **kwargs)``.
Callers only ever see the primal; the *deployment* reference stays
primal-only. The payoff is structural: ``flash_attention_bwd`` dropped its
(o, lse) recompute pass — two Pallas calls instead of three — and the
rmsnorm/xent backward kernels consume their residual instead of a
re-reduction over the inputs.

**Migration hazard (residual keys)**: the residual args are *part of the
backward db key* (an extra shape, and f32 residuals promote the key dtype
of a bf16 site). ``*_bwd`` records banked before the residual contract are
keyed on the old pre-residual signature — they never ExactHit a
residual-threaded gradient site, only warm-start re-tunes.
``python -m repro.campaign check`` flags such records as warm-start-only;
re-plan (``campaign plan --train-mesh ...``) and re-run to bank current
keys.

Fusion opt-in (``runtime.fusion_wins``)
---------------------------------------

The fused-epilogue tunables (``matmul_bias_act``, ``rmsnorm_matmul``)
extend the database-key story with a *resolution-policy hook*: model sites
call ``repro.core.runtime.fusion_wins("matmul_bias_act", x, w, b, ...)``
and route through the fused kernel only when kernel mode is active AND the
database holds a valid record for that exact fused key — i.e. a campaign
measured the fusion and banked it. No record, no fusion: the site keeps
its unfused ``matmul``/``rmsnorm`` dispatches, so exact-hit coverage is
invariant under the routing and fusion can never *introduce* a
heuristic-tier site. Their gradients decompose onto plain ``matmul`` /
``rmsnorm`` / ``rmsnorm_bwd`` records (``DispatchSpec.bwd_via`` declares
the decomposition; the contracts pass verifies it).

Arch coverage — which tunables each model family dispatches
------------------------------------------------------------

Every registered arch family now routes its hot contractions through the
registry; the planners (``plan_train_jobs`` / ``plan_training_jobs`` /
``plan_serving_jobs``) emit roster rows for every cell below, so a planned
campaign can take ANY config to 100% ExactHit, fwd and bwd:

===========  =================================================================
family       dispatch sites (beyond the shared matmul/rmsnorm/softmax_xent)
===========  =================================================================
attention    ``flash_attention`` (+ ``flash_attention_bwd``); QKV/out/FFN
             projections as ``matmul``
fused        ``matmul_bias_act`` (dense-with-bias; ffn gelu/silu epilogues)
             and ``rmsnorm_matmul`` (final-norm → unembed) — *opt-in* per
             site via ``fusion_wins`` (tuned record required); gradients
             decompose onto matmul/rmsnorm/rmsnorm_bwd records (bwd_via)
mamba (SSM)  ``ssm_scan`` chunked selective scan for train/prefill
             (+ ``ssm_scan_bwd``), ``ssm_update`` fused single-step state
             update for decode (+ ``ssm_update_bwd``); in/x/dt/out
             projections as ``matmul`` (dt_proj and out_proj run f32)
moe          ``expert_gemm`` grouped (experts × capacity × hidden) gemm for
             all three expert-FFN contractions; backward resolves
             transposed-operand ``expert_gemm`` keys (dL/dx, dL/dw). The
             router matmul stays plain jnp (below the tile floor).
mlstm        q/k/v/in/out projections and the post-cell gemms as ``matmul``;
             the inner score matmuls carry fused decay masks and are NOT
             substitutable by plain matmul records (kept in-model)
slstm        input projection + the three GeGLU MLP gemms as ``matmul``
===========  =================================================================

Hybrid configs (jamba = attention + mamba + moe, arctic = attention + moe)
compose rows per segment. SSM jobs key dt/A-conditioned arguments (see
``campaign.runner.materialize_args``); expert_gemm jobs are not
batch-sharded (capacity derives from the *global* traced token count).

Semantics are otherwise unchanged: dispatch resolves through the *active*
runtime, whose default policy reproduces the old precedence exactly —
stored best variant for (platform, kernel, shape-bucket, dtype), else the
campaign's 'few fit most' cover entry, else the shape heuristic, with the
pure-jnp reference path when kernels are disabled (``mode="reference"``,
or ``mode="auto"`` off-TPU without ``REPRO_USE_PALLAS=1``).

Observability (``repro.obs``)
-----------------------------

The dispatch plane is instrumented: every resolve/dispatch site, trainer
step phase, serving tick, and campaign job reports into the *ambient
collector* — ``repro.obs.collect(...)`` scoped the same contextvar way as
``repro.runtime`` (thread/async isolated, nestable).

* **Spans** — ``with obs.span("train.step", step=i): ...`` builds a
  contextvar-scoped span tree; each span lands as a structured event in a
  bounded ring buffer and as a ``span.<name>`` latency histogram. Pass
  ``xla_annotations=True`` to ``collect`` to mirror spans into
  ``jax.profiler.TraceAnnotation`` so they show up in XLA profiles.
* **Metrics** — counters / gauges / log-bucketed histograms (p50/p95/p99
  in bounded memory). Built-in hot-path series: ``dispatch.resolve_s``
  (per-tier, cache hit/miss), ``dispatch.calls``, ``span.train.step`` /
  ``train.tokens_per_s``, ``span.serve.admit`` / ``span.serve.tick`` /
  ``serve.per_token_s``, ``campaign.job_s`` / ``campaign.speedup``.
* **Drift** — ``python -m repro.obs report --drift --db <db>`` (or
  ``python -m repro.campaign drift``) replays each stored record's winning
  config, attributes live seconds to %-of-tuned-best and %-of-roofline
  (``tools/analytic.site_roofline_seconds``), and ranks regressions — the
  re-tune queue.
* **Export** — ``--metrics-out`` on ``launch.train`` / ``launch.serve`` /
  ``campaign run`` writes a snapshot JSON; render with
  ``python -m repro.obs report --metrics``, compare runs with
  ``python -m repro.obs diff``; ``write_prom`` emits a Prometheus textfile
  and ``write_jsonl`` an event log.

**Overhead guarantee**: the process-default collector is *disabled*; every
instrumentation site starts with one ``if not collector.enabled`` branch,
so a tuned kernel-mode step pays no measurable cost (<2%, asserted by
``benchmarks/obs_overhead.py`` in CI; <5% with default sampling enabled).

Static analysis (``repro.analysis``)
------------------------------------

The dispatch contract is now *machine-checked* without compiling anything
— ``python -m repro.analysis check --strict`` runs in CI and fails the
build on violations:

* **Dispatch-completeness lint** — raw FLOP sites in ``repro.models``
  (``jnp.einsum`` / ``@`` / ``jax.nn.softmax`` / ``jax.lax.scan``) must
  either route through the registry or carry an explicit pragma::

      # repro: allow-raw(<reason — single line, no parentheses>)

  Same-line covers that line; a pragma on its own line covers the whole
  statement that starts below it (so one above a ``def`` blesses the
  function body). Adding a new model? Either ``repro.dispatch(...)`` the
  contraction or annotate *why* it stays raw — the lint makes "forgot to
  dispatch" a CI failure instead of a silent heuristic-tier fallback.
* **Kernel legality** — every Pallas tunable registers an abstract grid
  model (``repro.core.gridmodel.register_grid_model``): grid shape,
  BlockSpec blocks, index maps, and dimension semantics as pure functions
  of the config. The checker abstractly evaluates the FULL config space
  per platform fingerprint for write-write races across parallel grid
  axes, index-map out-of-bounds, and TPU sublane/lane tiling (dtype-aware:
  8 rows f32, 16 bf16, 128 lanes). Adding a new kernel without a model is
  a contracts warning; adding one WITH a model gets static pruning for
  free: ``ParamSpace.legal_configs(platform)`` feeds the tuner's pre-pass
  (illegal configs marked pruned, zero measurement budget spent) and
  ``campaign plan`` stamps per-kernel pruned counts into the manifest
  (``campaign status`` prints them).
* **Registry contracts + artifact checks** — ``vjp="dispatch"`` tunables
  must dispatch a registered ``*_bwd`` sibling (or the forward kernel for
  transposed-operand gradients) with an oracle — or declare their
  decomposition via ``DispatchSpec.bwd_via``, verified against the plan's
  source; planner rosters must be registry-covered. ``python -m repro.campaign check --db ... --manifest
  ...`` extends this to shipped artifacts: the stale single-arg-dtype keys
  and pre-backward-plane manifests described above are now *detected*, not
  just documented (stale ``int32`` softmax_xent keys are an error; missing
  backward rosters and expert-capacity bucket drift are flagged).

Fault isolation (guarded dispatch, ``BackgroundTune``)
------------------------------------------------------

The ops-era wrappers executed the chosen variant bare: a record that
miscompiled on a new driver, or a kernel that faulted on one host,
raised straight through the train/serve step. Kernel-mode dispatch is
now **guarded by default** — a variant that throws (at trace time or
concretely) quarantines its database key in the runtime's
:class:`~repro.core.runtime.HealthBook` and the call falls through the
remaining tiers (heuristic config if it differs from the faulting one,
reference terminally), so one poisoned record degrades one bucket
instead of taking down the run. Quarantine has two levels: ``record``
(the stored config is bad — db tiers are skipped for that key) and
``kernel`` (the kernel itself cannot execute — straight to reference);
entries back off exponentially and re-probe when the backoff lapses, so
a fixed driver heals without a restart. Observability:
``dispatch.quarantine`` counter + a ``warn_once`` event per (key, level),
both exercised by ``tests/test_chaos.py``.

Migration notes:

* ``repro.runtime(guard=False)`` restores the old raise-through
  behavior (real tracebacks — debugging, benchmarks). An explicit
  ``config=`` override is always unguarded: the caller pinned a variant
  by hand and wants the traceback.
* ``repro.runtime(guard_nonfinite=True)`` additionally validates each
  bucket's FIRST resolution for NaN/Inf output (then caches a plain
  resolution) — the poisoned-record drill for silent corruption.
* The old "miss tunes inline" serving posture
  (``allow_tune=True`` + TuneNow) blocks a request on a full search.
  Use :func:`repro.core.background_policy` instead: misses answer with
  the heuristic config immediately (tier ``"bgtune"``, uncached) while
  a :class:`~repro.core.BackgroundTuner` worker tunes off-path and
  ``db.put``s the winner under the request's own key — the next resolve
  ExactHits, converging live traffic to 100% ExactHit with zero
  request-path stalls (ROADMAP item 2; ``tests/test_bgtune.py`` gates
  the convergence and the never-blocks latency bound).
* Deterministic failure drills live in :mod:`repro.testing.faults`
  (``FaultPlan`` / ``fault_point``) — the named sites
  (``dispatch.kernel:*``, ``bgtune.worker:*``, ``campaign.job:*``,
  ``db.load:*``, ``checkpoint.write:*``, ``train.step:*``) are compiled
  into the shipped library so staging environments can run the same
  seeded chaos scenarios CI does.
"""
from __future__ import annotations

# Importing the kernel modules is what populates the tunable registry —
# `from repro.kernels import ops` must keep working as a one-stop import.
from . import ref  # noqa: F401  (re-exported: the reference oracles)
from .attention import flash_attention as _flash_tunable  # noqa: F401
from .attention import flash_attention_bwd as _flash_bwd_tunable  # noqa: F401
from .fused import matmul_bias_act as _mba_tunable  # noqa: F401
from .fused import rmsnorm_matmul as _rmm_tunable  # noqa: F401
from .matmul import matmul as _matmul_tunable  # noqa: F401
from .moe_gemm import expert_gemm as _expert_gemm_tunable  # noqa: F401
from .rmsnorm import rmsnorm as _rmsnorm_tunable  # noqa: F401
from .ssm_scan import ssm_scan as _ssm_scan_tunable  # noqa: F401
from .ssm_scan import ssm_scan_bwd as _ssm_scan_bwd_tunable  # noqa: F401
from .ssm_scan import ssm_update as _ssm_update_tunable  # noqa: F401
from .ssm_scan import ssm_update_bwd as _ssm_update_bwd_tunable  # noqa: F401
from .rmsnorm import rmsnorm_bwd as _rmsnorm_bwd_tunable  # noqa: F401
from .xent import softmax_xent as _xent_tunable  # noqa: F401
from .xent import softmax_xent_bwd as _xent_bwd_tunable  # noqa: F401
