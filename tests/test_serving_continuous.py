"""Continuous-batching correctness: any arrival pattern + per-request
max_new_tokens yields token-for-token the outputs of running each request
alone (greedy, seeded), and a freed slot's cache never leaks into the next
occupant.

Property-based via hypothesis when installed; a seeded-random fallback
sweep runs the same check otherwise, so the equivalence property is always
exercised.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import Layout
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.models.transformer import RunConfig
from repro.serving.engine import EngineConfig, Request, ServingEngine

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

RUN = RunConfig(remat="none", loss_chunk=16, q_chunk=16, k_chunk=16)
MAX_SEQ = 64
PROMPT_LENS = (3, 9, 12, 17)   # few distinct lengths: solo refs jit per length


@pytest.fixture(scope="module")
def served():
    cfg = get_config("qwen2_0_5b").reduced()
    params, _ = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=3, max_seq=MAX_SEQ),
    )
    return cfg, params, eng


def _prompt(cfg, length: int, seed: int) -> np.ndarray:
    rs = np.random.RandomState(10_000 + 17 * length + seed)
    return rs.randint(0, cfg.vocab_size, length).astype(np.int32)


_SOLO_CACHE = {}


def _solo_greedy(cfg, params, prompt: np.ndarray, max_new: int) -> np.ndarray:
    """Reference: exact-length prefill + scalar-pos greedy decode, alone."""
    key = (prompt.tobytes(), max_new)
    if key in _SOLO_CACHE:
        return _SOLO_CACHE[key]
    L = len(prompt)
    logits, caches = lm.prefill(
        params, {"tokens": jnp.asarray(prompt)[None]}, cfg, RUN, cache_len=MAX_SEQ
    )
    out = [int(jnp.argmax(logits[0]))]
    for step in range(min(max_new, MAX_SEQ - L) - 1):
        logits, caches = lm.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), caches,
            jnp.asarray(L + step, jnp.int32), cfg, RUN,
        )
        out.append(int(jnp.argmax(logits[0])))
    ref = np.asarray(out, np.int32)
    _SOLO_CACHE[key] = ref
    return ref


def _check_schedule(cfg, params, eng, schedule):
    """schedule: list of (arrival_gap, prompt_len, max_new, prompt_seed)."""
    reqs = []
    t = 0.0
    for gap, length, max_new, seed in schedule:
        t += gap
        reqs.append(Request(
            prompt=_prompt(cfg, length, seed), max_new_tokens=max_new,
            arrival_time=t,
        ))
    for r in reqs:
        eng.submit(r)
    done = eng.serve()
    assert len(done) == len(reqs)
    assert all(s is None for s in eng._slots), "slots must drain"
    for r in done:
        ref = _solo_greedy(cfg, params, r.prompt, r.max_new_tokens)
        np.testing.assert_array_equal(
            r.output, ref,
            err_msg=f"arrival={r.arrival_time} len={len(r.prompt)} "
                    f"max_new={r.max_new_tokens} slot={r.slot}",
        )


if HAS_HYPOTHESIS:
    @settings(max_examples=15, deadline=None, derandomize=True)
    @given(
        st.lists(
            st.tuples(
                st.integers(0, 6),                  # arrival gap (ticks)
                st.sampled_from(PROMPT_LENS),       # prompt length
                st.integers(1, 6),                  # max_new_tokens
                st.integers(0, 3),                  # prompt content seed
            ),
            min_size=1, max_size=6,
        )
    )
    def test_any_arrival_pattern_matches_solo(served, schedule):
        cfg, params, eng = served
        _check_schedule(cfg, params, eng, schedule)
else:
    @pytest.mark.parametrize("case_seed", range(12))
    def test_any_arrival_pattern_matches_solo(served, case_seed):
        cfg, params, eng = served
        rs = np.random.RandomState(500 + case_seed)
        n = rs.randint(1, 7)
        schedule = [
            (int(rs.randint(0, 7)),
             int(PROMPT_LENS[rs.randint(len(PROMPT_LENS))]),
             int(rs.randint(1, 7)),
             int(rs.randint(0, 4)))
            for _ in range(n)
        ]
        _check_schedule(cfg, params, eng, schedule)


def test_invalid_requests_rejected_at_submit(served):
    cfg, params, eng = served
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=_prompt(cfg, 9, 0), max_new_tokens=0))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=np.zeros((0,), np.int32), max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=_prompt(cfg, MAX_SEQ, 0), max_new_tokens=4))


def test_single_token_requests_admit_through_one_slot(served):
    """max_new=1 completes at admission and recycles the slot immediately."""
    cfg, params, eng = served
    eng.reset_stats()
    for i in range(5):
        eng.submit(Request(prompt=_prompt(cfg, 9, i % 4), max_new_tokens=1))
    done = eng.serve()
    assert all(len(r.output) == 1 for r in done)
    assert eng.stats["decode_steps"] == 0          # prefill logits only
    assert eng.stats["prefill_calls"] == 5
    for r in done:
        ref = _solo_greedy(cfg, params, r.prompt, 1)
        np.testing.assert_array_equal(r.output, ref)


def test_freed_slot_cache_never_leaks(served):
    """A long occupant then a fresh request in the SAME slot: the second's
    output equals its solo run — the insert overwrites the whole region."""
    cfg, params, eng = served
    # single-slot engine forces reuse of slot 0
    one = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=1, max_seq=MAX_SEQ),
    )
    a = Request(prompt=_prompt(cfg, 17, 0), max_new_tokens=12)
    b = Request(prompt=_prompt(cfg, 3, 1), max_new_tokens=8)
    one.submit(a)
    one.submit(b)
    da, db = one.serve()
    assert da.slot == db.slot == 0
    np.testing.assert_array_equal(db.output, _solo_greedy(cfg, params, b.prompt, 8))
    # and the occupant that ran first was itself correct
    np.testing.assert_array_equal(da.output, _solo_greedy(cfg, params, a.prompt, 12))


def test_slot_reuse_matches_fresh_engine(served):
    """Output from a reused slot is bit-identical to a never-used engine."""
    cfg, params, eng = served
    req = lambda: Request(prompt=_prompt(cfg, 12, 2), max_new_tokens=10)
    # dirty the pool with varied traffic, then serve the probe
    for i in range(4):
        eng.submit(Request(prompt=_prompt(cfg, 17, i % 4), max_new_tokens=6))
    eng.serve()
    eng.submit(req())
    (dirty,) = eng.serve()
    fresh_eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=3, max_seq=MAX_SEQ),
    )
    fresh_eng.submit(req())
    (fresh,) = fresh_eng.serve()
    np.testing.assert_array_equal(dirty.output, fresh.output)


def test_seeded_temperature_matches_solo_timing_independent(served):
    """Same seed + temperature gives the same draws regardless of when the
    request is admitted or which slot it lands in."""
    cfg, params, eng = served
    mk = lambda arrival: Request(
        prompt=_prompt(cfg, 9, 3), max_new_tokens=8, temperature=1.0, seed=7,
        arrival_time=arrival,
    )
    filler = [Request(prompt=_prompt(cfg, 12, i), max_new_tokens=5 + i)
              for i in range(3)]
    eng.submit(mk(0.0))
    early = eng.serve()[0]
    for f in filler:
        eng.submit(f)
    eng.submit(mk(4.0))                    # admitted mid-flight, different slot mix
    late = [r for r in eng.serve() if r.temperature > 0][0]
    np.testing.assert_array_equal(early.output, late.output)


def test_ssm_arch_exact_length_prefill_matches_solo():
    """SSM mixers can't mask pad tokens out of their state: the engine
    prefills them at exact length and must still match solo decode."""
    cfg = get_config("jamba_1_5_large").reduced()
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params, _ = lm.init_params(jax.random.PRNGKey(1), cfg)
    eng = ServingEngine(
        cfg, RUN, params, make_host_mesh(), Layout(),
        EngineConfig(max_batch=2, max_seq=32),
    )
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int32) for n in (5, 11)]
    eng.submit(Request(prompt=prompts[0], max_new_tokens=6))
    eng.submit(Request(prompt=prompts[1], max_new_tokens=3))
    done = eng.serve()
    for r, prompt in zip(done, prompts):
        L = len(prompt)
        logits, caches = lm.prefill(
            params, {"tokens": jnp.asarray(prompt)[None]}, cfg, RUN, cache_len=32
        )
        ref = [int(jnp.argmax(logits[0]))]
        for step in range(r.max_new_tokens - 1):
            logits, caches = lm.decode_step(
                params, jnp.asarray([[ref[-1]]], jnp.int32), caches,
                jnp.asarray(L + step, jnp.int32), cfg, RUN,
            )
            ref.append(int(jnp.argmax(logits[0])))
        np.testing.assert_array_equal(r.output, np.asarray(ref, np.int32))


# --------------------------------------------------------------------------
# Greedy picks made on the device
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_device_pick_equals_host_pick_with_planted_ties(dtype):
    """The jits' argmax picks what the host's widened-float32 argmax picks,
    the first index of the maximum on a tie included."""
    from repro.serving.engine import _sample_one, _with_ids

    rs = np.random.RandomState(3)
    x = rs.randn(6, 1000).astype(np.float32)
    for row, (a, b) in enumerate([(5, 900), (0, 1), (998, 999), (17, 400),
                                  (250, 251), (3, 600)]):
        x[row, [a, b]] = x[row].max() + 1.0          # a tie at the maximum
    logits = jnp.asarray(x, dtype)
    _, _, ids = jax.jit(_with_ids)((logits, None))
    host = np.asarray(logits, np.float32)
    assert ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ids), np.argmax(host, -1))
    np.testing.assert_array_equal(np.asarray(ids), [5, 0, 998, 17, 250, 3])
    greedy = Request(prompt=np.zeros(1, np.int32))
    assert [_sample_one(r, greedy, None) for r in host] == list(np.asarray(ids))


def test_greedy_pool_copies_ids_only_and_matches_solo(served):
    cfg, params, eng = served
    eng.reset_stats()
    _check_schedule(cfg, params, eng, [(0, 17, 9, 0), (0, 3, 4, 1), (0, 12, 7, 2),
                                       (2, 9, 6, 3), (1, 12, 5, 1)])
    assert eng.stats["decode_steps"] > 0
    assert eng.stats["device_pick_ticks"] == eng.stats["decode_steps"]


def test_mixed_pool_copies_logits_only_on_sampling_ticks(served):
    """Greedy and temperature > 0 requests in one pool: each gets the output
    it gets alone, and only the ticks with no sampling slot copy ids only."""
    cfg, params, eng = served
    mk = lambda length, n, temp, seed, arrival: Request(
        prompt=_prompt(cfg, length, seed), max_new_tokens=n, temperature=temp,
        seed=seed, arrival_time=arrival)
    spec = [(17, 10, 0.0, 0, 0.0), (9, 4, 1.0, 5, 0.0), (12, 8, 0.0, 2, 1.0),
            (3, 5, 0.7, 9, 6.0), (9, 3, 0.0, 1, 7.0)]
    solo = {}
    for s in spec:
        if s[2] > 0:
            eng.reset_stats()
            eng.submit(mk(*s[:4], 0.0))
            (r,) = eng.serve()
            assert eng.stats["device_pick_ticks"] == 0
            solo[s] = r.output
    eng.reset_stats()
    reqs = [mk(*s) for s in spec]
    for r in reqs:
        eng.submit(r)
    done = eng.serve()
    sampling_ticks = set()
    for s, r in zip(spec, done):
        if r.temperature > 0:
            np.testing.assert_array_equal(r.output, solo[s])
            sampling_ticks.update(range(r.admitted_step, r.finished_step))
        else:
            np.testing.assert_array_equal(
                r.output, _solo_greedy(cfg, params, r.prompt, r.max_new_tokens))
    st = eng.stats
    assert 0 < st["device_pick_ticks"] < st["decode_steps"]
    assert st["device_pick_ticks"] == st["decode_steps"] - len(sampling_ticks)
