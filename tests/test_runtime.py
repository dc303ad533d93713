"""Dispatch-runtime tests: registry parity, scoped contexts, policies.

Registry parity is the zero-boilerplate guarantee: for EVERY tunable that
declares a dispatch example, the auto-generated entry point must match the
reference implementation in both modes (kernel mode runs the Pallas kernels
in interpret mode on CPU) — a new kernel gets this coverage by adding one
``DispatchSpec(example=...)`` field, with no test edits.

Everything here pins mode/db via `repro.runtime(...)` scopes, so the file
is environment-agnostic (the CI dispatch-parity leg re-runs it with
``REPRO_USE_PALLAS=1``).
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import (
    CoverSet,
    ExactHit,
    Heuristic,
    Record,
    Reference,
    TunedRuntime,
    TuningDatabase,
    make_key,
    registered,
)
from repro.core.platform import detect_platform
from repro.core.runtime import dispatch, entry_point
from repro.core.tuner import _args_key, promoted_dtype

# Populate the registry (kernels + model-level tunables) for parametrize.
import repro.kernels  # noqa: F401
import repro.models.tunables  # noqa: F401

DISPATCHABLE = sorted(
    name
    for name, t in registered().items()
    if t.dispatch is not None and t.dispatch.example is not None
)


def _fresh(mode):
    """A pinned scope: given mode, empty in-memory db (no env leakage)."""
    return repro.runtime(mode=mode, db=TuningDatabase(None))


# ---------------------------------------------------------------------------
# Registry parity: auto-generated dispatch ≡ reference, both modes
# ---------------------------------------------------------------------------


def test_registry_covers_all_pallas_kernels():
    # The Pallas kernels (forward AND backward plane) + the model-level
    # chunked attention must all be deployable through the registry with
    # example args.
    assert {"matmul", "flash_attention", "rmsnorm", "softmax_xent",
            "attn_chunks", "flash_attention_bwd", "rmsnorm_bwd",
            "softmax_xent_bwd"} <= set(DISPATCHABLE)


def _assert_trees_close(out, expected, rtol=0.0, atol=0.0):
    """Leaf-wise allclose: backward tunables return tuples of gradients
    with heterogeneous shapes, so a bare np.asarray comparison cannot work."""
    import jax

    o_leaves = jax.tree_util.tree_leaves(out)
    e_leaves = jax.tree_util.tree_leaves(expected)
    assert len(o_leaves) == len(e_leaves)
    for o, e in zip(o_leaves, e_leaves):
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(e, np.float32),
            rtol=rtol, atol=atol,
        )


@pytest.mark.parametrize("name", DISPATCHABLE)
def test_parity_reference_mode(name):
    t = registered()[name]
    args, kwargs = t.dispatch.example()
    expected = t.dispatch.reference_for(t)(*args, **kwargs)
    with _fresh("reference") as rt:
        out = dispatch(name, *args, **kwargs)
    _assert_trees_close(out, expected)
    assert rt.telemetry.snapshot()["tiers"] == {"reference": 1}


@pytest.mark.parametrize("name", DISPATCHABLE)
def test_parity_kernel_mode(name):
    t = registered()[name]
    args, kwargs = t.dispatch.example()
    expected = t.dispatch.reference_for(t)(*args, **kwargs)
    with _fresh("kernel"):
        out = dispatch(name, *args, **kwargs)
    _assert_trees_close(out, expected, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", DISPATCHABLE)
def test_parity_entry_point_matches_dispatch(name):
    t = registered()[name]
    args, kwargs = t.dispatch.example()
    fn = entry_point(name)
    with _fresh("kernel"):
        _assert_trees_close(
            fn(*args, **kwargs), dispatch(name, *args, **kwargs)
        )


# ---------------------------------------------------------------------------
# Scoped contexts: nesting, inheritance, thread isolation
# ---------------------------------------------------------------------------


def _matmul_args():
    x = jnp.ones((64, 128), jnp.float32)
    w = jnp.ones((128, 64), jnp.float32)
    return x, w


def _matmul_db(bm):
    db = TuningDatabase(None)
    key = make_key(
        "matmul", detect_platform().name, [(64, 128), (128, 64)], "float32"
    )
    db.put(Record(key, {"bm": bm, "bn": 128, "bk": 128}, 1e-6, "wallclock", 1, 0.0))
    return db


def test_nested_runtime_scoping():
    from repro.kernels.matmul import matmul as matmul_tunable

    x, w = _matmul_args()
    outer_db, inner_db = _matmul_db(bm=8), _matmul_db(bm=64)
    root = repro.current_runtime()
    with repro.runtime(db=outer_db, mode="kernel") as outer:
        assert repro.current_runtime() is outer
        assert outer.resolve(matmul_tunable, (x, w)).config["bm"] == 8
        with repro.runtime(db=inner_db) as inner:
            assert repro.current_runtime() is inner
            assert inner.mode == "kernel"          # inherited from outer
            assert inner.resolve(matmul_tunable, (x, w)).config["bm"] == 64
        # inner popped: outer's db (and its resolution cache) are back
        assert repro.current_runtime() is outer
        assert outer.resolve(matmul_tunable, (x, w)).config["bm"] == 8
    assert repro.current_runtime() is root


def test_nested_override_mode_keeps_db():
    with repro.runtime(db=_matmul_db(bm=8), mode="kernel") as outer:
        with repro.runtime(mode="reference") as inner:
            assert inner.db is outer.db
            assert not inner.kernel_mode_active
        assert outer.kernel_mode_active


def test_thread_isolation_fresh_thread_sees_no_scope():
    seen = {}

    def worker():
        seen["rt"] = repro.current_runtime()

    with repro.runtime(mode="kernel") as rt:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert repro.current_runtime() is rt
    # A fresh thread starts at the process default, not inside our scope.
    assert seen["rt"] is not rt


def test_thread_isolation_no_cross_talk():
    barrier = threading.Barrier(2, timeout=10)
    seen = {}

    def worker(tag):
        with repro.runtime(mode="kernel", name=tag) as rt:
            barrier.wait()              # both threads are inside their scope
            seen[tag] = repro.current_runtime() is rt
            barrier.wait()

    ts = [threading.Thread(target=worker, args=(f"t{i}",)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert seen == {"t0": True, "t1": True}


# ---------------------------------------------------------------------------
# Policy pipeline + telemetry + resolution cache
# ---------------------------------------------------------------------------


def test_exact_or_reference_policy(rs):
    """Trimmed pipeline: measured configs or reference — never heuristic."""
    from repro.kernels import ref

    x = jnp.asarray(rs.randn(64, 128), jnp.float32)
    w = jnp.asarray(rs.randn(128, 64), jnp.float32)
    db = TuningDatabase(None)
    with repro.runtime(
        db=db, mode="kernel", policy=(ExactHit(), Reference())
    ) as rt:
        out = dispatch("matmul", x, w)      # no record -> reference executes
        np.testing.assert_allclose(out, ref.matmul(x, w))
        assert rt.telemetry.snapshot()["tiers"] == {"reference": 1}

        key = make_key(
            "matmul", detect_platform().name, [(64, 128), (128, 64)], "float32"
        )
        db.put(Record(key, {"bm": 8, "bn": 128, "bk": 128}, 1e-6, "w", 1, 0.0))
        rt.clear_cache()
        dispatch("matmul", x, w)            # now the record serves it
        assert rt.telemetry.snapshot()["tiers"]["exact"] == 1


def test_telemetry_tier_accounting():
    """exact vs cover vs heuristic per kernel×bucket — the paper's
    sustained-performance accounting."""
    from repro.kernels.rmsnorm import rmsnorm as rmsnorm_tunable

    platform = detect_platform().name
    db = TuningDatabase(None)
    w = jnp.ones((32,), jnp.float32)
    x_exact = jnp.ones((64, 32), jnp.float32)
    x_cover = jnp.ones((256, 32), jnp.float32)
    key = make_key("rmsnorm", platform, [(64, 32), (32,)], "float32")
    db.put(Record(key, {"block_rows": 8}, 1e-6, "wallclock", 1, 0.0))
    db.put_cover(
        "rmsnorm", platform,
        [{"config": {"block_rows": 16}, "support": [[[128, 32], [32]]],
          "share": 1.0}],
    )
    with repro.runtime(db=db, mode="kernel") as rt:
        assert rt.resolve(rmsnorm_tunable, (x_exact, w)).tier == "exact"
        assert rt.resolve(rmsnorm_tunable, (x_cover, w)).tier == "cover"
        # empty-db kernel: heuristic tier
        from repro.kernels.matmul import matmul as matmul_tunable

        assert rt.resolve(matmul_tunable, _matmul_args()).tier == "heuristic"
    snap = rt.telemetry.snapshot()
    assert snap["tiers"] == {"exact": 1, "cover": 1, "heuristic": 1}
    assert any(k.startswith("rmsnorm|") for k in snap["by_key"])


def test_resolution_cache_hits_and_invalidation():
    from repro.kernels.matmul import matmul as matmul_tunable

    x, w = _matmul_args()
    db = _matmul_db(bm=8)
    with repro.runtime(db=db, mode="kernel") as rt:
        r1 = rt.resolve(matmul_tunable, (x, w))
        r2 = rt.resolve(matmul_tunable, (x, w))
        assert r1.config == r2.config
        assert rt.cache_size == 1
        snap = rt.telemetry.snapshot()
        assert snap["calls"] == 2 and snap["cache_hits"] == 1

        # A db update is invisible until the cache is cleared (documented).
        key = make_key(
            "matmul", detect_platform().name, [(64, 128), (128, 64)], "float32"
        )
        db.put(Record(key, {"bm": 64, "bn": 128, "bk": 128}, 1e-9, "w", 1, 1.0))
        assert rt.resolve(matmul_tunable, (x, w)).config["bm"] == 8
        rt.clear_cache()
        assert rt.resolve(matmul_tunable, (x, w)).config["bm"] == 64


def test_runtime_rejects_bad_mode():
    with pytest.raises(ValueError):
        TunedRuntime(mode="turbo")


def test_reference_mode_wins_over_explicit_config(rs):
    """config= must not force a kernel in reference mode (the multi-pod
    dry-run escape hatch, same precedence as the old ops.* wrappers)."""
    from repro.kernels import ref

    x = jnp.asarray(rs.randn(16, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32, 8), jnp.float32)
    with repro.runtime(mode="reference") as rt:
        out = dispatch("matmul", x, w, config={"bm": 8, "bn": 128, "bk": 128})
    np.testing.assert_allclose(out, ref.matmul(x, w))
    assert rt.telemetry.snapshot()["tiers"] == {"reference": 1}


def test_default_db_swap_invalidates_cached_resolution():
    """A db=None runtime resolves against default_db() *at call time*:
    set_default_db mid-session must not be shadowed by the cache."""
    from repro.core import set_default_db
    from repro.core.database import default_db
    from repro.kernels.matmul import matmul as matmul_tunable

    x, w = _matmul_args()
    prev = default_db()
    try:
        set_default_db(TuningDatabase(None))
        with repro.runtime(mode="kernel") as rt:
            assert rt.db is None                   # inherited ambient default
            assert rt.resolve(matmul_tunable, (x, w)).tier == "heuristic"
            set_default_db(_matmul_db(bm=8))       # campaign artifact arrives
            res = rt.resolve(matmul_tunable, (x, w))
            assert res.tier == "exact" and res.config["bm"] == 8
    finally:
        set_default_db(prev)


def test_shared_runtime_interleaved_asyncio_tasks():
    """Two tasks on ONE thread entering the same runtime, exits interleaved
    (A enters, B enters, A exits while B is still inside): context-local
    stacks must not cross."""
    import asyncio

    rt = TunedRuntime(mode="kernel", name="shared-async")

    async def task(entered, may_exit):
        with rt:
            assert repro.current_runtime() is rt
            entered.set()
            await may_exit.wait()
            assert repro.current_runtime() is rt

    async def main():
        a_in, a_out = asyncio.Event(), asyncio.Event()
        b_in, b_out = asyncio.Event(), asyncio.Event()
        ta = asyncio.create_task(task(a_in, a_out))
        tb = asyncio.create_task(task(b_in, b_out))
        await a_in.wait()
        await b_in.wait()
        a_out.set()               # A exits first, B still inside its scope
        await ta
        b_out.set()
        await tb

    asyncio.run(main())


def test_warmup_resolves_against_passed_db_without_install():
    """warmup(db, install=False) must consult the passed artifact (old
    tune_or_lookup semantics), not the ambient default database."""
    import jax
    from repro.configs import get_config
    from repro.core.database import default_db
    from repro.distributed.sharding import Layout
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.models.transformer import RunConfig
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = get_config("qwen2_0_5b").reduced()
    params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(
        cfg, RunConfig(remat="none", loss_chunk=16, q_chunk=16, k_chunk=16),
        params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=2, max_seq=32),     # no pinned runtime
    )
    platform = detect_platform().name
    # decode-pool rmsnorm bucket: x=[max_batch, d_model], w=[d_model]
    key = make_key("rmsnorm", platform,
                   [(2, cfg.d_model), (cfg.d_model,)], "float32")
    art = TuningDatabase(None)
    art.put(Record(key, {"block_rows": 8}, 1e-6, "wallclock", 1, 0.0))

    prev_default = default_db()
    resolved = eng.warmup(db=art, install=False, max_tokens=2048)
    assert default_db() is prev_default            # nothing installed
    assert resolved[key] == {"block_rows": 8}      # artifact WAS consulted


def test_shared_runtime_entered_from_two_threads():
    """One engine-pinned runtime may wrap calls on several serving threads:
    entry tokens are per-thread, so interleaved enter/exit must not blow up."""
    rt = TunedRuntime(mode="kernel", name="shared")
    barrier = threading.Barrier(2, timeout=10)
    errors = []

    def worker():
        try:
            for _ in range(50):
                with rt:
                    barrier.wait()
                    assert repro.current_runtime() is rt
                    barrier.wait()
        except Exception as e:  # noqa: BLE001 - surface any token mismatch
            errors.append(e)
            try:
                barrier.abort()
            except Exception:
                pass

    ts = [threading.Thread(target=worker) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors


# ---------------------------------------------------------------------------
# Warmed engine: per-tier accounting end-to-end (acceptance criterion)
# ---------------------------------------------------------------------------


def test_warmed_serving_engine_reports_tiers():
    """warmup() resolves every slot-pool bucket through the engine's runtime;
    serve-time dispatch runs under the same scope — telemetry shows per-tier
    hit counts for the whole run."""
    import jax
    from repro.configs import get_config
    from repro.distributed.sharding import Layout
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.models.transformer import RunConfig
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    cfg = get_config("qwen2_0_5b").reduced()
    params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
    # Pinned mode keeps this env-agnostic (reference path on the CPU host).
    rt = repro.runtime(mode="reference", db=TuningDatabase(None), name="test-engine")
    eng = ServingEngine(
        cfg, RunConfig(remat="none", loss_chunk=16, q_chunk=16, k_chunk=16),
        params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=2, max_seq=64), runtime=rt,
    )
    resolved = eng.warmup(max_tokens=2048)
    assert resolved and all(cfg_ is not None for cfg_ in resolved.values())
    assert rt.cache_size > 0                      # warm resolution cache

    prompt = np.arange(1, 9, dtype=np.int32) % cfg.vocab_size
    eng.submit(Request(prompt=prompt, max_new_tokens=3))
    eng.submit(Request(prompt=prompt[:5], max_new_tokens=3))
    done = eng.serve()
    assert len(done) == 2

    snap = rt.telemetry.snapshot()
    # warmup resolutions landed on config tiers (all-heuristic: empty db)...
    assert snap["tiers"].get("heuristic", 0) > 0
    # ...and the serve-time traces dispatched under the engine's scope.
    assert snap["tiers"].get("reference", 0) > 0
    # per-bucket accounting: warmed serving buckets appear as db keys
    assert any(k.startswith("rmsnorm|") for k in snap["by_key"])


# ---------------------------------------------------------------------------
# Bounded resolution cache: LRU capacity + TTL + eviction telemetry
# ---------------------------------------------------------------------------


def _resolve_rows(rt, rows):
    from repro.kernels.rmsnorm import rmsnorm as rmsnorm_tunable

    w = jnp.ones((32,), jnp.float32)
    return rt.resolve(rmsnorm_tunable, (jnp.ones((rows, 32), jnp.float32), w))


def test_cache_lru_capacity_bounds_growth():
    """A long-lived server cycling through many buckets must not grow the
    resolution cache without limit (ROADMAP follow-up)."""
    with repro.runtime(mode="kernel", db=TuningDatabase(None),
                       cache_capacity=2) as rt:
        for rows in (16, 64, 256, 1024):       # 4 distinct buckets
            _resolve_rows(rt, rows)
        assert rt.cache_size == 2
        snap = rt.telemetry.snapshot()
        assert snap["cache_evictions"] == 2
        # LRU order: the two most recent buckets are still warm
        _resolve_rows(rt, 1024)
        assert rt.telemetry.snapshot()["cache_hits"] == 1


def test_cache_lru_touch_on_hit():
    with repro.runtime(mode="kernel", db=TuningDatabase(None),
                       cache_capacity=2) as rt:
        _resolve_rows(rt, 16)
        _resolve_rows(rt, 64)
        _resolve_rows(rt, 16)                  # touch: 16 becomes most-recent
        _resolve_rows(rt, 256)                 # evicts 64, not 16
        _resolve_rows(rt, 16)
        snap = rt.telemetry.snapshot()
        assert snap["cache_hits"] == 2         # the touch + the final re-use


def test_cache_ttl_expires_entries(monkeypatch):
    import repro.core.runtime as rtmod

    t = {"now": 1000.0}
    monkeypatch.setattr(rtmod.time, "monotonic", lambda: t["now"])
    with repro.runtime(mode="kernel", db=TuningDatabase(None),
                       cache_ttl=10.0) as rt:
        _resolve_rows(rt, 16)
        t["now"] += 5.0
        _resolve_rows(rt, 16)                  # within TTL: cache hit
        assert rt.telemetry.snapshot()["cache_hits"] == 1
        t["now"] += 11.0
        _resolve_rows(rt, 16)                  # expired: re-resolved
        snap = rt.telemetry.snapshot()
        assert snap["cache_hits"] == 1
        assert snap["cache_evictions"] == 1


def test_cache_params_inherit():
    with repro.runtime(cache_capacity=7, cache_ttl=3.0):
        inner = repro.runtime()
        assert inner.cache_capacity == 7 and inner.cache_ttl == 3.0
        assert repro.runtime(cache_capacity=9).cache_capacity == 9


# ---------------------------------------------------------------------------
# Satellite regressions: key dtype promotion + __call__ validation
# ---------------------------------------------------------------------------


def test_args_key_uses_promoted_dtype():
    from repro.kernels.matmul import matmul as matmul_tunable

    bf = jnp.ones((8, 16), jnp.bfloat16)
    f = jnp.ones((16, 4), jnp.float32)
    k1 = _args_key(matmul_tunable, (bf, f), "p")
    k2 = _args_key(matmul_tunable, (f, bf), "p")
    # dtype field is order-independent and is the promotion, not the last arg
    assert k1.split("|")[3] == k2.split("|")[3] == "float32"
    # int labels never dominate the key (softmax_xent's old bug)
    assert promoted_dtype(["float32", "int32"]) == "float32"
    assert promoted_dtype([]) == "f32"


def test_call_validates_knob_overrides(rs):
    from repro.kernels.matmul import matmul as matmul_tunable

    x = jnp.asarray(rs.randn(16, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32, 8), jnp.float32)
    with pytest.raises(ValueError, match="not in domain"):
        matmul_tunable(x, w, bm=999)
    # valid knob override + non-knob passthrough kwarg both still work
    out = matmul_tunable(x, w, bm=8, interpret=True)
    from repro.kernels import ref

    np.testing.assert_allclose(out, ref.matmul(x, w), rtol=1e-4, atol=1e-4)


def test_call_rejects_constraint_violation():
    from repro.core import Constraint, ParamSpace, PowerOfTwoParam, tunable

    space = ParamSpace(
        [PowerOfTwoParam("a", 8, 64), PowerOfTwoParam("b", 8, 64)],
        [Constraint(lambda c: c["a"] <= c["b"], "a must not exceed b")],
    )

    @tunable("toy_constrained_rt", space=space, default={"a": 8, "b": 8})
    def toy(x, *, a, b):
        return x

    with pytest.raises(ValueError, match="a must not exceed b"):
        toy(jnp.ones(4), a=64, b=8)
    assert toy(jnp.ones(4), a=8, b=64) is not None


def test_auto_mode_takes_the_kernel_path_on_tpu(monkeypatch):
    """``mode="auto"`` dispatches kernels on a TPU backend; elsewhere it
    needs ``REPRO_USE_PALLAS=1``."""
    import repro.core.runtime as rt_mod

    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    auto = repro.runtime(mode="auto")
    monkeypatch.setattr(rt_mod, "_backend_tpu", False)
    assert not auto.kernel_mode_active
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert auto.kernel_mode_active
    monkeypatch.delenv("REPRO_USE_PALLAS")
    monkeypatch.setattr(rt_mod, "_backend_tpu", True)
    assert auto.kernel_mode_active
