"""Static kernel-legality plane (repro.core.gridmodel): race/OOB/alignment
checks on abstract grid models, space-level pruning on TPU fingerprints, and
the tuner's filter-before-measurement pre-pass."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.evaluate import Evaluator, Measurement
from repro.core.gridmodel import (
    GridModel,
    RefModel,
    check_alignment,
    check_oob,
    check_races,
    config_verdict,
    registered_models,
    space_illegal,
    space_report,
    sublanes_for,
)
from repro.core.platform import PROFILES, set_platform_override

TPU = PROFILES["tpu-v5e"]
CPU = PROFILES["cpu-host"]


def _register_all():
    from repro.core.runtime import ensure_registered

    ensure_registered()


# ---------------------------------------------------------------------------
# Race detector
# ---------------------------------------------------------------------------


def _dw_model(semantics):
    """An rmsnorm_bwd-shaped model: dw accumulator invariant along the row
    axis. Legal only when that axis is sequential ("arbitrary")."""
    return GridModel(
        kernel="synthetic_rmsnorm_bwd",
        grid=(8,),
        semantics=semantics,
        refs=(
            RefModel("dx", (128, 4096), lambda i: (i, 0), (1024, 4096), role="out"),
            RefModel("dw", (1, 4096), lambda i: (0, 0), (1, 4096), role="out"),
        ),
    )


def test_race_detector_flags_parallelized_accumulator():
    reason = check_races(_dw_model(("parallel",)))
    assert reason is not None
    assert "dw" in reason and "race" in reason


def test_race_detector_accepts_sequential_accumulator():
    assert check_races(_dw_model(("arbitrary",))) is None


def test_race_detector_ignores_input_refs():
    m = GridModel(
        kernel="k",
        grid=(4,),
        semantics=("parallel",),
        refs=(RefModel("w", (1, 128), lambda i: (0, 0), (1, 128), role="in"),),
    )
    assert check_races(m) is None


def test_shipped_sequential_kernels_are_race_free():
    """The shipped rmsnorm_bwd dw accumulator and ssm_scan chunk carry ride
    'arbitrary' axes — the detector must not flag them (ground truth)."""
    _register_all()
    for kernel in registered_models():
        for platform in ("tpu-v5e", "tpu-v4", "cpu-host"):
            r = space_report(kernel, platform)
            assert r["by_category"].get("race", 0) == 0, (kernel, platform, r)
            assert r["by_category"].get("oob", 0) == 0, (kernel, platform, r)


# ---------------------------------------------------------------------------
# OOB + alignment
# ---------------------------------------------------------------------------


def test_oob_detector_flags_overrunning_index_map():
    m = GridModel(
        kernel="k",
        grid=(2,),
        semantics=("parallel",),
        # block row i of size 8 over a dim of 8: i=1 spans [8, 16) — OOB.
        refs=(RefModel("x", (8, 128), lambda i: (i, 0), (8, 128), role="out"),),
    )
    reason = check_oob(m)
    assert reason is not None and "outside padded dim" in reason


def test_alignment_lane_rule_and_full_dim_exemption():
    bad = GridModel(
        kernel="k", grid=(2,), semantics=("parallel",),
        refs=(RefModel("x", (8, 64), lambda i: (0, i), (8, 4096)),),
    )
    assert "lanes" in check_alignment(bad, TPU)
    full = GridModel(
        kernel="k", grid=(1,), semantics=("parallel",),
        refs=(RefModel("x", (8, 4096), lambda i: (0, 0), (8, 4096)),),
    )
    assert check_alignment(full, TPU) is None
    # Off-TPU nothing is pruned.
    assert check_alignment(bad, CPU) is None


def test_alignment_sublane_rule_is_dtype_aware():
    assert sublanes_for(TPU, "float32") == 8
    assert sublanes_for(TPU, "bfloat16") == 16
    m = GridModel(
        kernel="k", grid=(8,), semantics=("parallel",),
        refs=(RefModel("x", (4, 128), lambda i: (i, 0), (64, 128)),),
    )
    assert "sublanes" in check_alignment(m, TPU, "float32")
    # A single-row (1, N) block that tiles its axis is refused too — the
    # TPU compiler rejects it (flash attention keeps lse as [bq, 1] columns
    # and [1, bq] rows of 3-D arrays instead).
    row = GridModel(
        kernel="k", grid=(8,), semantics=("parallel",),
        refs=(RefModel("lse", (1, 128), lambda i: (i, 0), (8, 4096)),),
    )
    assert "sublanes" in check_alignment(row, TPU)


# ---------------------------------------------------------------------------
# Space-level verdicts on the shipped kernels
# ---------------------------------------------------------------------------

EXPECTED_TPU_V5E = {
    "matmul": (144, 144),
    "expert_gemm": (144, 144),
    "rmsnorm": (10, 4),
    "rmsnorm_bwd": (10, 4),
    "softmax_xent": (34, 34),
    "softmax_xent_bwd": (34, 34),
    "flash_attention": (17, 17),
    "flash_attention_bwd": (17, 17),
    "ssm_scan": (46, 18),
    "ssm_update": (46, 18),
}


def test_space_reports_on_tpu_v5e_match_ground_truth():
    _register_all()
    got = {
        k: (space_report(k, "tpu-v5e")["total"], space_report(k, "tpu-v5e")["legal"])
        for k in EXPECTED_TPU_V5E
    }
    assert got == EXPECTED_TPU_V5E


def test_ssm_pruning_is_exactly_the_sub_lane_tiles():
    """On tpu-v5e the ssm spaces lose exactly the block_d < 128 tiles (a
    block_d that tiles d_inner must span full lanes); everything pruned is
    'align', never race/oob."""
    _register_all()
    illegal = space_illegal("ssm_scan", "tpu-v5e")
    assert len(illegal) == 28
    assert all(cat == "align" for cat, _ in illegal.values())
    assert all("block_d=" in key for key in illegal)
    for key in illegal:
        bd = int(dict(kv.split("=") for kv in key.split(","))["block_d"])
        assert bd < 128


def test_cpu_host_prunes_nothing():
    _register_all()
    from repro.kernels.ssm_scan import SSM_SCAN_SPACE

    full = list(SSM_SCAN_SPACE.enumerate())
    assert SSM_SCAN_SPACE.legal_configs("cpu-host") == full
    assert space_illegal("ssm_scan", "cpu-host") == {}


def test_legal_configs_shrinks_on_tpu_and_keeps_aligned_tiles():
    _register_all()
    from repro.kernels.ssm_scan import SSM_SCAN_SPACE

    full = list(SSM_SCAN_SPACE.enumerate())
    legal = SSM_SCAN_SPACE.legal_configs("tpu-v5e")
    assert len(legal) == 18 < len(full) == 46
    assert all(cfg["block_d"] >= 128 for cfg in legal)
    pruned = [c for c in full if c not in legal]
    assert all(cfg["block_d"] < 128 for cfg in pruned)


def test_space_without_grid_model_enumerates_fully():
    _register_all()
    from repro.kernels.ssm_scan import SSM_SCAN_BWD_SPACE

    full = list(SSM_SCAN_BWD_SPACE.enumerate())
    assert SSM_SCAN_BWD_SPACE.legal_configs("tpu-v5e") == full
    assert len(full) == 7


def test_pruned_configs_are_infeasible_not_wrong():
    """Acceptance: pruning must be conservative — a config pruned on the TPU
    fingerprint still computes the right answer under interpret mode (it is
    merely unlowerable/mispadded on real hardware, not incorrect)."""
    _register_all()
    from repro.kernels.ssm_scan import (
        _ssm_scan_example, ssm_scan_chunked, ssm_scan_pallas,
    )

    (xc, dt, B, C, A, h0), _ = _ssm_scan_example()
    y_ref, h_ref = ssm_scan_chunked(xc, dt, B, C, A, h0)
    illegal = space_illegal("ssm_scan", "tpu-v5e")
    sampled = sorted(illegal)[:2]
    for key in sampled:
        cfg = {k: int(v) for k, v in (kv.split("=") for kv in key.split(","))}
        y, hn = ssm_scan_pallas(xc, dt, B, C, A, h0, interpret=True, **cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(hn), np.asarray(h_ref),
                                   rtol=2e-4, atol=2e-4)


def test_best_interpret_config_survives_pruning():
    """Acceptance: pruning changes nothing about the best-found config on
    interpret platforms — cpu-host legality is the full space, so any winner
    found there is by construction un-pruned."""
    _register_all()
    from repro.kernels.ssm_scan import SSM_SCAN_SPACE

    legal_keys = {
        SSM_SCAN_SPACE.config_key(c)
        for c in SSM_SCAN_SPACE.legal_configs("cpu-host")
    }
    assert {SSM_SCAN_SPACE.config_key(c) for c in SSM_SCAN_SPACE.enumerate()} \
        == legal_keys


# ---------------------------------------------------------------------------
# Tuner integration: the static pre-pass
# ---------------------------------------------------------------------------


class SmallestTileEvaluator(Evaluator):
    """Deterministic objective preferring the smallest tiles: without the
    legality pre-pass, block_d=8 would always win."""

    name = "smallest-tile"

    def evaluate(self, fn, args, reference=None):
        cfg = getattr(fn, "keywords", {})
        score = sum(float(v) for v in cfg.values() if isinstance(v, int))
        return Measurement(objective=score or 1.0, ok=True)


def test_autotune_prunes_statically_illegal_configs_on_tpu_fingerprint():
    _register_all()
    from repro.core.annotate import get_tunable
    from repro.core.database import TuningDatabase
    from repro.core.search import ExhaustiveSearch
    from repro.core.tuner import autotune

    rs = np.random.RandomState(0)
    b, s, di, ds = 2, 64, 256, 16
    args = (
        jnp.asarray(rs.randn(b, s, di) * 0.5, jnp.float32),
        jnp.asarray(np.abs(rs.randn(b, s, di)) * 0.1 + 0.01, jnp.float32),
        jnp.asarray(rs.randn(b, s, ds) * 0.5, jnp.float32),
        jnp.asarray(rs.randn(b, s, ds) * 0.5, jnp.float32),
        jnp.asarray(-np.abs(rs.randn(di, ds)) - 0.1, jnp.float32),
        jnp.asarray(rs.randn(b, di, ds) * 0.3, jnp.float32),
    )
    set_platform_override("tpu-v5e")
    try:
        result = autotune(
            get_tunable("ssm_scan"), args,
            search=ExhaustiveSearch(),
            evaluator=SmallestTileEvaluator(),
            db=TuningDatabase(None), save=False,
        )
    finally:
        set_platform_override(None)
    # The surrogate prefers block_d=8, but every block_d < 128 tile is
    # statically illegal at di=256 on tpu-v5e — the winner must be aligned.
    assert result.best_config["block_d"] >= 128
    pruned = [
        t for t in result.search.trials
        if not t.ok and t.meta.get("pruned", "").startswith("align")
    ]
    assert pruned, "no trial carries the static-prune marker"
    assert all(t.config["block_d"] < 128 for t in pruned)


def test_autotune_pre_pass_is_inert_on_cpu():
    _register_all()
    from repro.core.annotate import get_tunable
    from repro.core.database import TuningDatabase
    from repro.core.search import ExhaustiveSearch
    from repro.core.tuner import autotune

    rs = np.random.RandomState(1)
    b, s, di, ds = 2, 12, 8, 4
    args = (
        jnp.asarray(rs.randn(b, s, di) * 0.5, jnp.float32),
        jnp.asarray(np.abs(rs.randn(b, s, di)) * 0.1 + 0.01, jnp.float32),
        jnp.asarray(rs.randn(b, s, ds) * 0.5, jnp.float32),
        jnp.asarray(rs.randn(b, s, ds) * 0.5, jnp.float32),
        jnp.asarray(-np.abs(rs.randn(di, ds)) - 0.1, jnp.float32),
        jnp.asarray(rs.randn(b, di, ds) * 0.3, jnp.float32),
    )
    result = autotune(
        get_tunable("ssm_scan"), args,
        search=ExhaustiveSearch(),
        evaluator=SmallestTileEvaluator(),
        db=TuningDatabase(None), save=False,
    )
    assert not any(t.meta.get("pruned") for t in result.search.trials)
    assert result.best_config == {"chunk": 8, "block_d": 8}


# ---------------------------------------------------------------------------
# Scheduler integration: legality stamped into the manifest
# ---------------------------------------------------------------------------


def test_build_manifest_stamps_legality_counts(tmp_path):
    _register_all()
    from repro.campaign.planner import TuningJob
    from repro.campaign.scheduler import CampaignManifest, build_manifest

    job = TuningJob(
        kernel="ssm_scan",
        arg_shapes=((2, 64, 256), (2, 64, 256), (2, 64, 16), (2, 64, 16),
                    (256, 16), (2, 256, 16)),
        arg_dtypes=("float32",) * 6,
        scenarios=("jamba/train_4k",),
    )
    path = str(tmp_path / "m.json")
    m = build_manifest([job], 24, path=path, platform="tpu-v5e",
                       profile=PROFILES["tpu-v5e"])
    assert m.meta["legality"]["ssm_scan"] == {
        "total": 46, "legal": 18, "pruned": 28,
    }
    assert m.summary()["configs_pruned"] == 28
    # survives the JSON round trip `campaign status` reads
    loaded = CampaignManifest.load(path)
    assert loaded.meta["legality"]["ssm_scan"]["pruned"] == 28
    assert loaded.summary()["configs_pruned"] == 28


def test_config_verdict_unknown_kernel_is_legal():
    assert config_verdict("no_such_kernel", {"a": 1}, "tpu-v5e") is None
