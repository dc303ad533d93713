"""The comparison that decides ``correct`` fails what it must: the control
(the reference in the next lower precision, in the program's place) and each
fault the cells can have, planted under the timed path of a whole run at
smoke widths on the CPU."""
from __future__ import annotations

import pytest

import bench_smoke as B


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return B.make_copy(tmp_path_factory.mktemp("bench"))


def _state_unchanged(monkeypatch):
    from repro.optim import adamw

    monkeypatch.setattr(adamw, "update", lambda cfg, grads, state, params: (
        params, state, {"grad_norm": 0.0 * state["step"], "lr": 0.0 * state["step"]}))


def _half_batch(monkeypatch):
    from repro.models import lm

    real = lm.loss_fn

    def half(params, batch, *a, **k):
        n = batch["tokens"].shape[0] // 2
        return real(params, {key: v[:n] for key, v in batch.items()}, *a, **k)

    monkeypatch.setattr(lm, "loss_fn", half)


def _token_altered(monkeypatch):
    from repro.serving import engine

    real = engine._sample_one
    monkeypatch.setattr(engine, "_sample_one",
                        lambda logits, req, rng: (real(logits, req, rng) + 1) % len(logits))


@pytest.mark.parametrize("cell,plant", [
    ("smoke.train", _state_unchanged),
    ("smoke.train", _half_batch),
    ("smoke.serve", _token_altered),
])
def test_planted_fault_is_not_correct(bench, monkeypatch, cell, plant):
    plant(monkeypatch)
    rc, res = B.run_cell(bench, cell, seed=2**31 + 3)
    assert rc == 0
    assert res["correct"] is False, res["checks"]


# The training faults again, as source run before a run in a process of its
# own on four host devices; and the one only a mesh can have.
MESH_PLANTS = {
    "state_unchanged": (
        "from repro.optim import adamw\n"
        "adamw.update = lambda cfg, grads, state, params: (params, state, "
        "{'grad_norm': 0.0 * state['step'], 'lr': 0.0 * state['step']})\n"),
    "half_batch": (
        "from repro.models import lm\n"
        "_real = lm.loss_fn\n"
        "def _half(params, batch, *a, **k):\n"
        "    n = batch['tokens'].shape[0] // 2\n"
        "    return _real(params, {key: v[:n] for key, v in batch.items()}, *a, **k)\n"
        "lm.loss_fn = _half\n"),
    # each device keeps its own rows' part of every weight gradient
    "exchange_left_out": "import jax\njax.lax.psum = lambda x, axis_name, **k: x\n",
}


@pytest.mark.parametrize("plant", sorted(MESH_PLANTS))
def test_planted_fault_on_a_4x1_mesh_is_not_correct(tmp_path, plant):
    """Each fault a data-parallel training cell can have, planted under a
    4x1 mesh of host devices (kernel-mode dispatch, as on a TPU), turns
    ``correct`` false."""
    bench = B.make_copy(tmp_path)
    B.add_dp4_cell(bench)
    rc, res, err = B.run_on_host_devices(bench, "smoke.train_dp4", seed=2**31 + 5,
                                         plant=MESH_PLANTS[plant])
    assert rc == 0, err[-4000:]
    assert res["device"]["count"] == 4
    assert res["correct"] is False, res["checks"]


def test_training_control_is_not_correct(bench):
    """The reference in bfloat16 (the smoke configuration states float32)
    fails one of the training cell's numbers against the float32 reference."""
    import run as harness

    drv = harness.load_module(f"{bench}/drivers/train.py", "smoke_train_driver")
    run = harness.Run(bench, "smoke.train", B.TRAIN_CELL, B.SMOKE_CONFIG, 5, 0, False, None)
    R = run.family
    batches = drv.reference_batches(run, 5, 3)
    opt = B.TRAIN_CELL["optimizer"]
    ref = R.train_readings(B.SMOKE_CONFIG, 5, batches, opt)
    ctl = R.train_readings(B.SMOKE_CONFIG, 5, batches, opt, quant=R.control_quant(B.SMOKE_CONFIG))
    gaps, _ = drv.compare(ctl, ref)
    assert any(gaps[k] > B.TRAIN_CELL["limits"][k] for k in gaps), gaps


def test_serving_control_is_not_correct(bench):
    """At each served position of 30 smoke waves, the token the float8
    reference (the control of the bfloat16 serving cells) puts first lies
    below the float32 reference's best by more than the limit somewhere. (A
    bfloat16 control flips no token of a 256-row vocabulary at these widths:
    its near ties are too rare.)"""
    import numpy as np

    import run as harness

    drv = harness.load_module(f"{bench}/drivers/serve.py", "smoke_serve_driver")
    run = harness.Run(bench, "smoke.serve", B.SERVE_CELL, B.SMOKE_CONFIG, 9, 0, False, None)
    R = run.family
    engine, _ = drv.build(run, 9)
    picked = [r for w in range(30) for r in drv.serve_wave(engine, B.SERVE_CELL["traffic"], 256, 9, w)]
    params = R.make_params(B.SMOKE_CONFIG, 9)
    prog = max(float(g.max()) for g in drv.gaps(run, params, picked))
    ctl = max(float(g.max()) for g in drv.gaps(
        run, params, picked, quant=R.control_quant(B.SMOKE_CONFIG)))
    limit = B.SERVE_CELL["limits"]["served_token_gap"]
    assert prog <= limit < ctl, (prog, limit, ctl)
    assert np.isfinite(ctl)
