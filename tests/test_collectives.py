"""Gradient compression + ring all-reduce. Multi-device cases run in a
subprocess with 8 fake host devices (the parent process stays 1-device)."""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.collectives import compress_grads, ef_init


def test_bf16_compression_lossy_but_close():
    g = {"w": jnp.asarray(np.random.RandomState(0).randn(128), jnp.float32)}
    out, _ = compress_grads(g, None, "bf16")
    err = float(jnp.max(jnp.abs(out["w"] - g["w"])))
    assert 0 < err < 2e-2


def test_int8_ef_residual_carries():
    rs = np.random.RandomState(1)
    g = {"w": jnp.asarray(rs.randn(256) * 0.01, jnp.float32)}
    ef = ef_init(g)
    acc_c = np.zeros(256)
    acc_t = np.zeros(256)
    for i in range(60):
        gi = {"w": g["w"] * (1.0 + 0.1 * np.sin(i))}
        c, ef = compress_grads(gi, ef, "int8_ef")
        acc_c += np.asarray(c["w"])
        acc_t += np.asarray(gi["w"])
    rel = np.max(np.abs(acc_c - acc_t)) / np.max(np.abs(acc_t))
    assert rel < 0.02, rel  # error feedback keeps the accumulated signal


def test_unknown_mode_raises():
    with pytest.raises(ValueError):
        compress_grads({"w": jnp.zeros(3)}, None, "fp4")


_RING_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.collectives import ring_all_reduce
    mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.asarray(np.random.RandomState(0).randn(8, 37), jnp.float32)
    out = jax.jit(lambda v: ring_all_reduce(v, mesh, "x"))(x)
    want = jnp.broadcast_to(x.sum(0), x.shape)
    err = float(jnp.max(jnp.abs(out - want)))
    assert err < 1e-4, err
    print("RING_OK")
    """
)


def test_ring_all_reduce_8dev():
    r = subprocess.run(
        [sys.executable, "-c", _RING_SCRIPT],
        capture_output=True, text=True, timeout=300,
        # JAX_PLATFORMS=cpu: without it jax probes the bundled libtpu on this
        # image and hangs for minutes before falling back to CPU
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    assert "RING_OK" in r.stdout, r.stderr[-2000:]
