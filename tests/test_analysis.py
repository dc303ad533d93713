"""repro.analysis: dispatch-completeness lint + pragma grammar, registry
contract verification, db/manifest checks, and the CLI exit-code gates."""
import json

import pytest

from repro.analysis import Report, run_checks
from repro.analysis.lint import default_models_dir, lint_paths, lint_source
from repro.core.database import make_key

# ---------------------------------------------------------------------------
# Pass 1: lint + pragma grammar
# ---------------------------------------------------------------------------

RAW = """
import jax
import jax.numpy as jnp

def f(x, w):
    return jnp.einsum("ij,jk->ik", x, w)
"""

RAW_ALLOWED_SAME_LINE = """
import jax.numpy as jnp

def f(x, w):
    return jnp.einsum("ij,jk->ik", x, w)  # repro: allow-raw(tiny gate matmul)
"""

RAW_ALLOWED_STATEMENT = """
import jax
import jax.numpy as jnp

# repro: allow-raw(whole function is the tunable reference body)
def f(x, w):
    y = x @ w
    z = jax.nn.softmax(y)
    return jax.lax.scan(lambda c, t: (c + t, c), 0.0, z)
"""

CLEAN = """
import jax.numpy as jnp
from repro.core.runtime import dispatch

def f(x, w):
    return dispatch("matmul", x, w) + jnp.sum(x)
"""


def _lint_str(src):
    report = Report()
    lint_source(src, "synthetic.py", report)
    return report


def test_lint_flags_raw_einsum_and_gate_bites():
    report = _lint_str(RAW)
    assert len(report.errors()) == 1
    assert "einsum" in report.errors()[0].message
    assert report.exit_code() == 1          # the CI gate fails on this


def test_lint_same_line_pragma_downgrades_to_info():
    report = _lint_str(RAW_ALLOWED_SAME_LINE)
    assert report.errors() == []
    infos = report.by_severity("info")
    assert len(infos) == 1 and "tiny gate matmul" in infos[0].message
    assert report.exit_code(strict=True) == 0


def test_lint_statement_pragma_covers_whole_def():
    """One own-line pragma above a def covers every raw site inside it —
    the @, the softmax, and the scan."""
    report = _lint_str(RAW_ALLOWED_STATEMENT)
    assert report.errors() == []
    assert len(report.by_severity("info")) == 3


def test_lint_pragma_does_not_leak_past_the_statement():
    src = RAW_ALLOWED_SAME_LINE + "\n\ndef g(a, b):\n    return a @ b\n"
    report = _lint_str(src)
    assert len(report.errors()) == 1        # g's @ is not covered


def test_lint_clean_file_has_no_findings():
    report = _lint_str(CLEAN)
    assert report.findings == []


def test_lint_directory_walk_and_seeded_violation(tmp_path):
    """End-to-end gate proof: a seeded synthetic violation in a fresh tree
    makes `check --strict` (and plain `check`) exit non-zero."""
    (tmp_path / "bad.py").write_text(RAW)
    (tmp_path / "good.py").write_text(CLEAN)
    report = run_checks(models_dir=str(tmp_path), passes=["lint"])
    assert report.exit_code() == 1
    assert report.stats["lint_files"] == 2
    (tmp_path / "bad.py").write_text(RAW_ALLOWED_SAME_LINE)
    report = run_checks(models_dir=str(tmp_path), passes=["lint"])
    assert report.exit_code(strict=True) == 0


def test_repo_models_lint_clean_strict():
    """Satellite acceptance: the shipped model layer carries a pragma with a
    reason at every intentional raw site — zero errors, zero warnings."""
    report = lint_paths([default_models_dir()])
    assert report.errors() == []
    assert report.exit_code(strict=True) == 0
    # the known-intentional sites are documented, not silenced
    assert report.stats.get("lint_allowed", 0) >= 20


# ---------------------------------------------------------------------------
# Pass 2 + 3 on the repo itself
# ---------------------------------------------------------------------------


def test_repo_full_check_strict_is_clean():
    report = run_checks()
    assert report.errors() == []
    assert report.warnings() == []
    assert report.exit_code(strict=True) == 0
    # legality stats carried for both TPU fingerprints
    assert report.stats["legality"]["ssm_scan@tpu-v5e"]["illegal"] == 28


def test_contracts_flag_missing_reference_oracle():
    from repro.analysis.contracts import check_contracts
    from repro.core.annotate import _REGISTRY, Tunable
    from repro.core.params import ParamSpace, PowerOfTwoParam

    fake = Tunable(
        "zz_fake_no_oracle", lambda x: x,
        space=ParamSpace([PowerOfTwoParam("a", 8, 16)]), reference=None,
    )
    _REGISTRY[fake.name] = fake
    try:
        report = check_contracts()
        locs = [f.location for f in report.errors()]
        assert fake.name in locs
    finally:
        del _REGISTRY[fake.name]


def test_contracts_verify_bwd_dispatch_targets():
    report = Report()
    from repro.analysis.contracts import check_contracts

    check_contracts(report)
    assert report.errors() == []
    # every dispatch-vjp tunable was actually inspected
    assert report.stats["contracts"]["dispatch_vjp"] >= 6


# ---------------------------------------------------------------------------
# db / manifest checks (the `campaign check` body)
# ---------------------------------------------------------------------------


def _write_db(path, records, schema=2):
    path.write_text(json.dumps({"schema": schema, "records": records}))


def test_db_check_flags_stale_int_dtype_key(tmp_path):
    from repro.analysis.db_check import check_db

    stale = make_key("softmax_xent", "tpu-v5e", ((2048, 65536), (2048,)), "int32")
    good = make_key("softmax_xent", "tpu-v5e", ((2048, 65536), (2048,)), "float32")
    db = tmp_path / "db.json"
    _write_db(db, {stale: {"objective": 1.0}, good: {"objective": 1.0}})
    report = check_db(str(db))
    errs = [f for f in report.errors() if f.location == stale]
    assert len(errs) == 1 and "stale integer-dtype key" in errs[0].message
    assert not [f for f in report.errors() if f.location == good]


def test_db_check_flags_unknown_platform_and_schema(tmp_path):
    from repro.analysis.db_check import check_db

    key = make_key("matmul", "rocm-mi300", ((512, 512), (512, 512)), "float32")
    db = tmp_path / "db.json"
    _write_db(db, {key: {"objective": 1.0}}, schema=1)
    report = check_db(str(db))
    msgs = " | ".join(f.message for f in report.warnings())
    assert "schema 1" in msgs
    assert "rocm-mi300" in msgs


def test_db_check_flags_invalid_stored_config(tmp_path):
    from repro.analysis.db_check import check_db

    key = make_key("matmul", "tpu-v5e", ((512, 512), (512, 512)), "float32")
    db = tmp_path / "db.json"
    _write_db(db, {key: {"objective": 1.0, "config": {"bogus_knob": 3}}})
    report = check_db(str(db))
    assert any("no longer valid" in f.message for f in report.warnings())


def test_db_check_flags_pre_residual_bwd_key(tmp_path):
    """A *_bwd record keyed before the residual contract (fewer operands
    than the tunable's current example call) is warm-start-only: flagged."""
    from repro.analysis.db_check import check_db

    q, kv = (2, 4, 128, 16), (2, 2, 128, 16)
    stale = make_key(
        "flash_attention_bwd", "tpu-v5e", (q, q, kv, kv), "float32", "cTruew0"
    )
    good = make_key(
        "flash_attention_bwd", "tpu-v5e",
        (q, q, kv, kv, q, (2, 4, 128)), "float32", "cTruew0",
    )
    db = tmp_path / "db.json"
    _write_db(db, {stale: {"objective": 1.0}, good: {"objective": 1.0}})
    report = check_db(str(db))
    flagged = [f for f in report.warnings() if f.location == stale]
    assert len(flagged) == 1 and "pre-residual" in flagged[0].message
    assert not [f for f in report.warnings() if f.location == good]


def _capacity_manifest(tmp_path, capacity=1024, scenarios=("mixtral/train_4k@dp16",)):
    from repro.campaign.planner import TuningJob
    from repro.campaign.scheduler import CampaignManifest

    job = TuningJob(
        kernel="expert_gemm",
        arg_shapes=((4, capacity, 512), (4, 512, 256)),
        arg_dtypes=("float32", "float32"),
        scenarios=scenarios,
    )
    path = str(tmp_path / "manifest.json")
    CampaignManifest(path=path, platform="tpu-v5e", jobs=[job]).save()
    return path


def test_db_check_flags_expert_capacity_drift_and_missing_bwd(tmp_path):
    from repro.analysis.db_check import check_db

    drifted = make_key(
        "expert_gemm", "tpu-v5e", ((4, 2048, 512), (4, 512, 256)), "float32"
    )
    matching = make_key(
        "expert_gemm", "tpu-v5e", ((4, 1024, 512), (4, 512, 256)), "float32"
    )
    db = tmp_path / "db.json"
    _write_db(db, {drifted: {"objective": 1.0}, matching: {"objective": 1.0}})
    manifest = _capacity_manifest(tmp_path, capacity=1024)
    report = check_db(str(db), manifest_path=manifest)
    # capacity drift: warn on the 2048-capacity record only
    drift = [f for f in report.warnings() if f.location == drifted]
    assert len(drift) == 1 and "capacity bucket 2048" in drift[0].message
    assert not [f for f in report.warnings() if f.location == matching]
    # @dp training manifest without a backward roster is the pre-bwd hazard
    assert any("backward roster" in f.message for f in report.errors())
    # ... and the drift also landed in the obs event buffer via warn_once
    from repro.obs.collect import warn_once

    assert not warn_once("analysis.expert_gemm_capacity", key=drifted)


def test_db_check_clean_without_manifest_is_info_only(tmp_path):
    from repro.analysis.db_check import check_db

    key = make_key("matmul", "tpu-v5e", ((512, 512), (512, 512)), "float32")
    db = tmp_path / "db.json"
    _write_db(db, {key: {"objective": 1.0}})
    report = check_db(str(db))
    assert report.errors() == [] and report.warnings() == []
    assert any("skipped" in f.message for f in report.by_severity("info"))


# ---------------------------------------------------------------------------
# CLIs: python -m repro.analysis check / python -m repro.campaign check
# ---------------------------------------------------------------------------


def test_analysis_cli_strict_clean_on_repo(capsys):
    from repro.analysis.cli import main

    rc = main(["check", "--strict", "--passes", "lint,contracts"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 error(s), 0 warning(s)" in out


def test_analysis_cli_fails_on_seeded_violation(tmp_path, capsys):
    from repro.analysis.cli import main

    (tmp_path / "bad.py").write_text(RAW)
    rc = main(["check", "--models-dir", str(tmp_path), "--passes", "lint"])
    assert rc == 1
    assert "not routed through a registry tunable" in capsys.readouterr().out


def test_analysis_cli_json_output(tmp_path, capsys):
    from repro.analysis.cli import main

    (tmp_path / "bad.py").write_text(RAW)
    rc = main(["check", "--models-dir", str(tmp_path), "--passes", "lint",
               "--json"])
    assert rc == 1
    blob = json.loads(capsys.readouterr().out)
    assert blob["counts"]["error"] == 1
    assert blob["findings"][0]["pass_name"] == "lint"


def test_campaign_check_cli(tmp_path, capsys):
    from repro.campaign.cli import main as campaign_main

    stale = make_key("softmax_xent", "tpu-v5e", ((2048, 65536), (2048,)), "int32")
    db = tmp_path / "db.json"
    _write_db(db, {stale: {"objective": 1.0}})
    manifest = _capacity_manifest(
        tmp_path, capacity=1024, scenarios=("mixtral/train_4k",)
    )
    rc = campaign_main(["check", "--db", str(db), "--manifest", manifest])
    out = capsys.readouterr().out
    assert rc == 1
    assert "stale integer-dtype key" in out

    clean = tmp_path / "clean.json"
    _write_db(clean, {})
    rc = campaign_main(["check", "--db", str(clean), "--manifest", manifest])
    assert rc == 0


def test_campaign_status_prints_pruned_counts(tmp_path, capsys):
    from repro.campaign.cli import main as campaign_main
    from repro.campaign.planner import TuningJob
    from repro.campaign.scheduler import build_manifest
    from repro.core.platform import PROFILES
    from repro.core.runtime import ensure_registered

    ensure_registered()
    job = TuningJob(
        kernel="ssm_scan",
        arg_shapes=((2, 64, 256), (2, 64, 256), (2, 64, 16), (2, 64, 16),
                    (256, 16), (2, 256, 16)),
        arg_dtypes=("float32",) * 6,
        scenarios=("jamba/train_4k",),
    )
    path = str(tmp_path / "m.json")
    build_manifest([job], 24, path=path, platform="tpu-v5e",
                   profile=PROFILES["tpu-v5e"])
    rc = campaign_main(["status", "--manifest", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert '"configs_pruned": 28' in out
    assert "pruned 28 of 46 configs (18 legal) on tpu-v5e" in out


# ---------------------------------------------------------------------------
# Report mechanics
# ---------------------------------------------------------------------------


def test_report_exit_code_strictness():
    r = Report()
    r.add("db", "warn", "k", "drift")
    assert r.exit_code() == 0
    assert r.exit_code(strict=True) == 1
    r.add("lint", "error", "f.py:1", "raw")
    assert r.exit_code() == 1


def test_report_rejects_bad_severity():
    r = Report()
    with pytest.raises(ValueError):
        r.add("lint", "fatal", "x", "y")
