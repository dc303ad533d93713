"""Platform fingerprinting + the override escape hatch.

The dispatch runtime and campaign tools namespace databases under the
*detected* platform; these tests pin the override precedence (explicit arg >
set_platform_override > $REPRO_PLATFORM > fingerprint) and that a runtime
can pin a foreign namespace without touching process state.
"""
import jax.numpy as jnp
import pytest

import repro
from repro.core import TuningDatabase
from repro.core.platform import (
    CPU_HOST,
    PROFILES,
    detect_platform,
    platform_override,
    set_platform_override,
)


@pytest.fixture(autouse=True)
def clean_override(monkeypatch):
    monkeypatch.delenv("REPRO_PLATFORM", raising=False)
    set_platform_override(None)
    yield
    set_platform_override(None)


def test_fingerprint_on_this_host_is_cpu():
    assert detect_platform().name == "cpu-host"
    assert detect_platform() is CPU_HOST


def test_known_override_selects_profile():
    set_platform_override("tpu-v4")
    assert platform_override() == "tpu-v4"
    assert detect_platform() is PROFILES["tpu-v4"]


def test_unknown_override_clones_fingerprint():
    """A new namespace (e.g. an unreleased TPU generation) isolates records
    while keeping sensible roofline peaks from the fingerprinted profile."""
    set_platform_override("tpu-v6e-preview")
    prof = detect_platform()
    assert prof.name == "tpu-v6e-preview"
    assert prof.peak_flops_bf16 == CPU_HOST.peak_flops_bf16
    assert "tpu-v6e-preview" not in PROFILES   # no registry pollution


def test_env_override_and_precedence(monkeypatch):
    monkeypatch.setenv("REPRO_PLATFORM", "tpu-v5e")
    assert detect_platform().name == "tpu-v5e"
    set_platform_override("tpu-v4")            # explicit call wins over env
    assert detect_platform().name == "tpu-v4"
    assert detect_platform(override="cpu-host").name == "cpu-host"


def test_override_changes_runtime_db_namespace():
    """Dispatch keys follow the override — records stored under the
    overridden namespace hit; the fingerprinted namespace does not leak."""
    from repro.kernels.matmul import matmul as matmul_tunable

    x = jnp.ones((16, 32), jnp.float32)
    w = jnp.ones((32, 8), jnp.float32)
    set_platform_override("tpu-v6e-preview")
    with repro.runtime(mode="kernel", db=TuningDatabase(None)) as rt:
        rt.resolve(matmul_tunable, (x, w))
    keys = list(rt.telemetry.snapshot()["by_key"])
    assert keys and all("|tpu-v6e-preview|" in k for k in keys)


def test_runtime_platform_param_pins_namespace():
    """A per-runtime platform pin (inspecting a foreign artifact from a dev
    host) needs no process-global state."""
    from repro.core import Record, make_key
    from repro.kernels.matmul import matmul as matmul_tunable

    x = jnp.ones((16, 32), jnp.float32)
    w = jnp.ones((32, 8), jnp.float32)
    db = TuningDatabase(None)
    key = make_key("matmul", "tpu-v5e", [(16, 32), (32, 8)], "float32")
    db.put(Record(key, {"bm": 8, "bn": 128, "bk": 128}, 1e-6, "w", 1, 0.0))

    with repro.runtime(mode="kernel", db=db, platform="tpu-v5e") as rt:
        res = rt.resolve(matmul_tunable, (x, w))
    assert res.tier == "exact"
    # the same db under the detected (cpu-host) namespace misses
    with repro.runtime(mode="kernel", db=db) as rt2:
        assert rt2.resolve(matmul_tunable, (x, w)).tier == "heuristic"
    # nested runtimes inherit the pinned platform
    with repro.runtime(platform="tpu-v5e"):
        assert repro.runtime().platform == "tpu-v5e"


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", "tpu-v5e"),
    ("TPU v4", "tpu-v4"),
    ("TPU v6 lite", None),
])
def test_fingerprint_keys_profiles_by_device_kind(monkeypatch, kind, want):
    """TPU profiles come from the device_kind table; a kind without peaks
    is an error, never another chip's numbers."""
    import types

    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    if want is None:
        with pytest.raises(ValueError, match="TPU v6 lite"):
            detect_platform()
    else:
        assert detect_platform() is PROFILES[want]
