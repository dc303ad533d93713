"""The annotation layer — `@tunable` is our "pragma".

In the paper, a single-line comment annotation turns a plain loop into a
tuning site without changing program semantics. Here, decorating a function
with :func:`tunable` declares its knob space and default config; the function
itself *is* the transformation: it must accept the knobs as keyword-only
arguments and produce the same math for every valid config. Undecorated
callers see the default config, so — exactly as in the paper — the annotated
program still runs as the reference implementation.

    @tunable("matmul", space=ParamSpace([...]), reference=ref.matmul)
    def matmul(x, w, *, bm, bn, bk): ...

    matmul(x, w)                  # default config (the 'unannotated' program)
    matmul.variant(bm=128, ...)   # one concrete variant (a transformed code)
    matmul.tune(x, w)             # run the autotuner -> best variant

Deployment is declared here too: the optional ``dispatch=DispatchSpec(...)``
argument tells the dispatch runtime (:mod:`repro.core.runtime`) everything it
needs to auto-generate a deployment entry point — which reference fn backs
the kernel, how to derive the database ``key_extra`` from call kwargs, and
how to canonicalize arguments (e.g. rmsnorm's flatten-to-2D/reshape-back).
A new kernel therefore needs exactly one decorator: no hand-written wrapper
in ``kernels/ops.py``, no planner or serving edits.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from .params import Config, ParamSpace

_REGISTRY: Dict[str, "Tunable"] = {}


@dataclasses.dataclass(frozen=True)
class DispatchSpec:
    """Declarative deployment spec for one tunable.

    The dispatch runtime consumes this to build the kernel's deployment
    entry point; every field is optional:

    * ``reference`` — the fallback / reference-mode implementation, called as
      ``reference(*args, **call_kwargs)``. Defaults to the tunable's tuning
      reference (``Tunable.reference``). Always primal-only: a tunable with
      ``residuals > 0`` has a residual-emitting *tuning* reference (so the
      correctness gate compares like structure), and must set this field to
      the plain oracle explicitly.
    * ``key_extra`` — maps the *call kwargs* to the database key suffix
      (e.g. flash attention's ``f"c{causal}w{window}"``), so semantically
      different calls with identical shapes get distinct records.
    * ``canonicalize`` — ``(*args) -> (canon_args, restore)``: rewrites the
      positional args into the layout the kernel (and its db keys) expect,
      plus a function applied to the kernel output to undo the rewrite.
      rmsnorm uses this to flatten ``[..., d] -> [rows, d]`` and reshape
      back. The reference path always sees the *original* args.
    * ``example`` — ``() -> (args, kwargs)``: small representative arguments
      (interpret-mode friendly) used by the registry parity tests and the
      dispatch-overhead benchmark, so coverage of a new kernel is automatic.
    * ``data_parallel_args`` — indices of the *canonical* positional args
      whose leading dim is batch/token-like. Under an active sharded
      ``mesh_context`` the runtime keys the database on the per-device
      *local* shard of those dims (global dim ÷ data-parallel degree), so
      campaign records tuned at local shard shapes exact-hit inside
      jit-sharded traces. Default ``(0,)`` (the row-major convention);
      ``()`` disables localization for a kernel. The same dims are what
      kernel-mode dispatch shards the kernel over (``jax.shard_map``), so
      each device runs exactly the local shapes its key names.
    * ``reduced_outputs`` — indices of the kernel's outputs (in its flat
      output tuple) that sum over the sharded token dim, such as a weight
      gradient: under a sharded mesh each device's partial result is summed
      over the data axes. A call whose ``dp_dims`` moves the sharded dim
      into the contraction reduces every output.
    * ``vjp`` — how dispatch differentiates the kernel path.

      * ``"dispatch"`` — the backward pass is itself a set of dispatch
        sites: the bound variant is wrapped in a ``jax.custom_vjp`` whose
        backward calls ``bwd(ct, *canonical_args, **call_kwargs)``, and the
        ``bwd`` callable routes each gradient through the runtime
        (``dispatch(...)`` on the same or a sibling tunable). Every
        backward call then resolves through the policy pipeline with its
        own database key and telemetry rows (phase-tagged ``bwd``), so a
        campaign can pre-tune gradients exactly like forwards. Falls back
        to ``"reference"`` behaviour when the runtime disables backward
        dispatch (``bwd_dispatch=False``) or no ``bwd`` is declared.
      * ``"reference"`` — wraps the bound variant in a ``jax.custom_vjp``
        whose backward pass is the VJP of the reference implementation, so
        tuned kernels are trainable even when the Pallas kernel itself has
        no transpose rule (forward stays the tuned kernel; backward
        recomputes through the reference math).
      * ``"none"`` — leaves the variant bare (for tunables that are never
        differentiated at all). Backward-plane tunables use ``"reference"``
        instead, so ``jax.grad``-of-``jax.grad`` can differentiate *through*
        a dispatched gradient site; the runtime additionally routes any
        dispatch under second-order JVP nesting straight to the reference
        implementation (``custom_vjp`` has no forward-mode rule).
    * ``bwd`` — the backward dispatch plan for ``vjp="dispatch"``: called
      as ``bwd(ct, *canonical_args, **call_kwargs)`` — or, with
      ``residuals > 0``, as ``bwd(ct, *canonical_args, primal_out,
      *aux, **call_kwargs)`` — returns one cotangent per canonical
      positional arg (``None`` for non-differentiable args — integer
      labels and the like).
    * ``residuals`` — the *residual contract*: when > 0, the bound variant
      (and the tuning reference) returns ``(primal, *aux)`` with exactly
      this many auxiliary outputs — forward intermediates the backward
      pass would otherwise recompute (flash attention's lse, rmsnorm's
      inv-rms, softmax-xent's lse). Dispatch saves them into the
      ``custom_vjp`` residuals alongside the canonical args and the
      primal output, and hands all three to the backward plan; callers
      only ever see the primal. Residuals stay *canonical* (the
      ``canonicalize`` restore applies to the primal alone).
    * ``bwd_via`` — the registered tunable names the backward plan
      dispatches, for plans that decompose into *other* kernels' sites
      (the fused-epilogue tunables lower their gradients onto plain
      ``matmul`` / ``rmsnorm_bwd`` records rather than a dedicated
      ``*_bwd`` sibling). The analysis contracts pass verifies these
      against the plan's source instead of requiring a same-name sibling.
    """

    reference: Optional[Callable] = None
    key_extra: Optional[Callable[[Dict[str, Any]], str]] = None
    canonicalize: Optional[Callable[..., Tuple[tuple, Callable]]] = None
    example: Optional[Callable[[], Tuple[tuple, Dict[str, Any]]]] = None
    data_parallel_args: Tuple[int, ...] = (0,)
    reduced_outputs: Tuple[int, ...] = ()
    vjp: str = "reference"
    bwd: Optional[Callable] = None
    residuals: int = 0
    bwd_via: Tuple[str, ...] = ()

    def reference_for(self, tunable: "Tunable") -> Optional[Callable]:
        return self.reference if self.reference is not None else tunable.reference

    def extra_for(self, call_kwargs: Dict[str, Any]) -> str:
        return self.key_extra(call_kwargs) if self.key_extra else ""

    def canon(self, args: tuple) -> Tuple[tuple, Callable]:
        if self.canonicalize is None:
            return args, lambda out: out
        return self.canonicalize(*args)


class Tunable:
    def __init__(
        self,
        name: str,
        fn: Callable,
        space: ParamSpace,
        reference: Optional[Callable] = None,
        default: Optional[Config] = None,
        heuristic: Optional[Callable[..., Config]] = None,
        dispatch: Optional[DispatchSpec] = None,
    ):
        self.name = name
        self.fn = fn
        self.space = space
        self.reference = reference
        self._default = default
        # Shape-aware default: maps concrete args -> a good starting config
        # (the 'vendor library' baseline the tuner must beat).
        self.heuristic = heuristic
        # Deployment declaration consumed by repro.core.runtime (None means
        # dispatch with defaults: tuning reference, no key_extra, identity
        # canonicalization).
        self.dispatch = dispatch
        functools.update_wrapper(self, fn)

    # -- variants -------------------------------------------------------------
    def default_config(self, *args) -> Config:
        if self.heuristic is not None and args:
            cfg = self.heuristic(*args)
            if self.space.is_valid(cfg):
                return cfg
        if self._default is not None:
            return dict(self._default)
        return self.space.default()

    def variant(self, **config) -> Callable:
        """Bind one concrete config — a 'code variant' in the paper's terms."""
        why = self.space.why_invalid(config)
        if why is not None:
            raise ValueError(f"invalid config for {self.name}: {why}")
        return functools.partial(self.fn, **config)

    def __call__(self, *args, **overrides):
        """Run with the default config, plus validated knob overrides.

        Knob overrides (keys in the space) are merged into the default config
        and the result is validated via ``space.why_invalid`` — an off-domain
        or constraint-violating override raises with the reason, matching
        :meth:`variant`. Non-knob kwargs (``eps``, ``causal``, ``interpret``,
        ...) pass through to the implementation untouched.
        """
        cfg = self.default_config(*args)
        knobs = set(self.space.names)
        passthrough = {k: v for k, v in overrides.items() if k not in knobs}
        cfg.update({k: v for k, v in overrides.items() if k in knobs})
        why = self.space.why_invalid(cfg)
        if why is not None:
            raise ValueError(f"invalid config for {self.name}: {why}")
        return self.fn(*args, **cfg, **passthrough)

    # -- tuning ----------------------------------------------------------------
    def tune(self, *args, **kwargs):
        from .tuner import autotune  # late import: tuner imports annotate

        return autotune(self, args, **kwargs)

    def __repr__(self) -> str:
        return f"<tunable {self.name} over {self.space!r}>"


def tunable(
    name: str,
    space: ParamSpace,
    reference: Optional[Callable] = None,
    default: Optional[Config] = None,
    heuristic: Optional[Callable[..., Config]] = None,
    dispatch: Optional[DispatchSpec] = None,
) -> Callable[[Callable], Tunable]:
    def deco(fn: Callable) -> Tunable:
        t = Tunable(name, fn, space, reference, default, heuristic, dispatch)
        _REGISTRY[name] = t
        return t

    return deco


def get_tunable(name: str) -> Tunable:
    return _REGISTRY[name]


def registered() -> Dict[str, Tunable]:
    return dict(_REGISTRY)
