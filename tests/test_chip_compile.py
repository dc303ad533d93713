"""Deviceless compiles for a described TPU v5e (no chip attached).

The main-path Pallas kernels at Qwen2-0.5B's published widths (d_model 896,
14/2 heads of 64, d_ff 4864, vocab 151936, bf16), at the per-microbatch
shapes of an 8 x 1024 train step, each at its heuristic config — the config
an empty tuning database dispatches. The TPU compiler refuses here what
interpret mode accepts: misaligned blocks, tiles over the scoped VMEM
limit, and Mosaic kernels that XLA would have to partition. The last test
compiles one dispatch sharded over a 4-chip mesh, which the runtime wraps
in ``jax.shard_map``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        if log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels():
    return {n: importlib.import_module(f"repro.kernels.{n}")
            for n in ("matmul", "rmsnorm", "attention", "xent")}


def _case(name):
    """(pallas fn, heuristic, arg shapes/dtypes, kwargs) per kernel."""
    k = _kernels()
    mm, rms, att, xe = k["matmul"], k["rmsnorm"], k["attention"], k["xent"]
    rows, d = 2048, 896
    q, kv = (2, 14, 1024, 64), (2, 2, 1024, 64)
    lg = (1024, 151936)
    return {
        "matmul": (mm.matmul_pallas, mm._matmul_heuristic,
                   [((rows, d), BF16), ((d, 4864), BF16)], {}),
        "rmsnorm": (rms.rmsnorm_pallas, rms._rmsnorm_heuristic,
                    [((rows, d), BF16), ((d,), BF16)], {"return_residuals": True}),
        "rmsnorm_bwd": (rms.rmsnorm_bwd_pallas, rms._rmsnorm_bwd_heuristic,
                        [((rows, d), BF16), ((rows, d), BF16), ((d,), BF16),
                         ((rows,), F32)], {}),
        "flash_attention": (att.flash_attention_pallas, att._attn_heuristic,
                            [(q, BF16), (kv, BF16), (kv, BF16)],
                            {"return_residuals": True}),
        "flash_attention_bwd": (att.flash_attention_bwd_pallas, att._attn_bwd_heuristic,
                                [(q, BF16), (q, BF16), (kv, BF16), (kv, BF16),
                                 (q, BF16), (q[:3], F32)], {}),
        "softmax_xent": (xe.softmax_xent_pallas, xe._xent_heuristic,
                         [(lg, BF16), (lg[:1], I32)], {"return_residuals": True}),
        "softmax_xent_bwd": (xe.softmax_xent_bwd_pallas, xe._xent_bwd_heuristic,
                             [(lg[:1], F32), (lg, BF16), (lg[:1], I32),
                              (lg[:1], F32)], {}),
    }[name]


MAIN_PATH = ["matmul", "rmsnorm", "rmsnorm_bwd", "flash_attention",
             "flash_attention_bwd", "softmax_xent", "softmax_xent_bwd"]


@pytest.mark.parametrize("name", MAIN_PATH)
def test_heuristic_config_is_legal_for_v5e(name):
    """The static legality pass admits the config an empty database
    dispatches, at the shapes and dtype the compile test below compiles."""
    from repro.core.gridmodel import config_verdict

    _, heuristic, specs, _ = _case(name)
    config = heuristic(*[jax.ShapeDtypeStruct(s, dt) for s, dt in specs])
    shapes = [s for s, _ in specs]
    dtype = jnp.dtype(specs[0][1]).name
    assert config_verdict(name, config, "tpu-v5e", shapes, dtype) is None, config


@pytest.mark.parametrize("name", MAIN_PATH)
def test_main_path_kernel_compiles_for_v5e(one_chip, name):
    fn, heuristic, specs, kwargs = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    config = heuristic(*args)
    compiled = jax.jit(
        lambda *a: fn(*a, **config, **kwargs, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (name, config)


def test_dispatch_on_four_chip_mesh_runs_kernel_per_device(topo):
    """matmul's weight gradient (x.T @ ct, token dim sharded 4 ways) through
    kernel-mode dispatch: one Mosaic kernel per device on its local rows,
    then a sum over the data axis."""
    import repro
    from repro.core.database import TuningDatabase
    from repro.distributed import sharding as shd
    from repro.launch.defaults import default_layout
    from repro.configs.base import get_config

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    layout = default_layout(get_config("qwen2-0.5b"))
    rows = NamedSharding(mesh, P("data"))
    x = jax.ShapeDtypeStruct((4096, 896), BF16, sharding=rows)
    ct = jax.ShapeDtypeStruct((4096, 4864), BF16, sharding=rows)

    def dw(x, ct):
        return repro.dispatch("matmul", x.T, ct, dp_dims={0: 1, 1: 0},
                              interpret=False)

    rt = repro.runtime(db=TuningDatabase(None), mode="kernel", guard=False,
                       platform="tpu-v5e")
    with rt, shd.mesh_context(mesh, layout, dp_degree=4):
        compiled = jax.jit(dw).lower(x, ct).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
    # keyed on each device's 1024 of the 4096 token rows (dims bucketed to
    # powers of two)
    (key,) = rt.telemetry.snapshot()["by_key"]
    assert key == "matmul|tpu-v5e|1024x1024/1024x8192|bfloat16"
