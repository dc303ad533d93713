"""correctness_gate edge cases: NaN-in-reference, zero-size leaves,
mismatched tree structure, and dtype-aware (bf16) tolerance scaling."""
import jax.numpy as jnp
import numpy as np

from repro.core.evaluate import correctness_gate, tolerance_for


def test_nan_in_reference_matching_positions_pass():
    ref = np.array([1.0, np.nan, 3.0], np.float32)
    out = np.array([1.0, np.nan, 3.0], np.float32)
    assert correctness_gate(out, ref)


def test_nan_in_output_where_reference_finite_fails():
    ref = np.array([1.0, 2.0, 3.0], np.float32)
    out = np.array([1.0, np.nan, 3.0], np.float32)
    assert not correctness_gate(out, ref)


def test_nan_positions_must_align():
    ref = np.array([np.nan, 2.0], np.float32)
    out = np.array([2.0, np.nan], np.float32)
    assert not correctness_gate(out, ref)


def test_all_nan_reference_does_not_blow_up_scale():
    ref = np.full((4,), np.nan, np.float32)
    assert correctness_gate(np.full((4,), np.nan, np.float32), ref)
    assert not correctness_gate(np.zeros((4,), np.float32), ref)


def test_zero_size_leaves_pass():
    ref = {"a": np.zeros((0, 8), np.float32), "b": np.ones((2,), np.float32)}
    out = {"a": np.zeros((0, 8), np.float32), "b": np.ones((2,), np.float32)}
    assert correctness_gate(out, ref)


def test_zero_size_vs_nonzero_shape_fails():
    assert not correctness_gate(np.zeros((0,), np.float32), np.zeros((1,), np.float32))


def test_mismatched_tree_structure_same_leaf_count_fails():
    x = np.ones((2,), np.float32)
    y = np.zeros((2,), np.float32)
    assert not correctness_gate({"a": x, "b": y}, [x, y])
    assert not correctness_gate((x, (y,)), ((x,), y))
    # same structure still passes
    assert correctness_gate({"a": x, "b": y}, {"a": x, "b": y})


def test_bf16_tolerance_scales_with_dtype():
    ref = jnp.ones((8,), jnp.float32)
    drift = 5e-3  # within bf16 tolerance, far outside f32 tolerance
    out_bf16 = (jnp.ones((8,)) + drift).astype(jnp.bfloat16)
    out_f32 = jnp.ones((8,), jnp.float32) + drift
    assert correctness_gate(out_bf16, ref)       # coarser dtype decides
    assert not correctness_gate(out_f32, ref)    # f32 variant held to f32 tol
    # explicit tolerances override the dtype rule
    assert correctness_gate(out_f32, ref, rtol=1e-2, atol=1e-2)
    assert not correctness_gate(out_bf16, ref, rtol=1e-6, atol=1e-6)


def test_bf16_tolerance_applies_before_f32_upcast():
    """The upcast-to-f32 used for comparison must not reset the tolerance."""
    rt, at = tolerance_for(jnp.bfloat16)
    assert rt >= 1e-2
    ref = jnp.asarray(np.linspace(0.5, 2.0, 16), jnp.bfloat16)
    out = ref + ref * 1e-2                      # 1% off: bf16-ok, f32-not
    assert correctness_gate(out, ref)


def test_tolerance_scale_uses_finite_reference_magnitude():
    ref = np.array([np.inf, 100.0, -100.0], np.float32)
    out = np.array([np.inf, 100.0, -100.0], np.float32)
    assert correctness_gate(out, ref)
    # the finite magnitude (100) scales atol; a 2e-3 absolute error passes f32
    out2 = np.array([np.inf, 100.0005, -100.0], np.float32)
    assert correctness_gate(out2, ref)


def test_strict_evaluator_raises_where_lenient_scores_inf():
    import math

    import pytest

    from repro.core.evaluate import WallClockEvaluator

    x = jnp.ones((4, 4), jnp.float32)

    def broken(a):
        raise ValueError("refused tile")

    wrong = lambda a: a + 1.0
    lenient = WallClockEvaluator(repeats=1, warmup=0)
    strict = WallClockEvaluator(repeats=1, warmup=0, strict=True)
    for fn, ref in ((broken, None), (wrong, x)):
        m = lenient.evaluate(fn, (x,), reference=ref)
        assert not m.ok and math.isinf(m.objective)
    with pytest.raises(ValueError, match="refused tile"):
        strict.evaluate(broken, (x,))
    with pytest.raises(AssertionError, match="correctness gate"):
        strict.evaluate(wrong, (x,), reference=x)
    assert strict.evaluate(lambda a: a * 1.0, (x,), reference=x).ok
