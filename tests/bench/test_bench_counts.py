"""The benchmark's operation and byte counts against hand counts at
Qwen2-0.5B's shapes, the kernel roofline files found by the kernel's name, and
the reading of device-op names."""
from __future__ import annotations

import os
import types

import pytest

import bench_smoke as B
import roofline
import run as harness

flops = harness.load_family(B.BENCH, "qwen2")

QWEN2_05B = {"num_hidden_layers": 24, "hidden_size": 896, "num_attention_heads": 14,
             "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 4864,
             "vocab_size": 151936}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_work_at_the_ffn_projection():
    # one microbatch of 2 x 1024 tokens through the up-projection
    ins = [("bf16", (2048, 896)), ("bf16", (896, 4864))]
    outs = [("bf16", (2048, 4864))]
    ops, nbytes = roofline.work("_matmul_kernel", ins, outs)
    assert ops == 2 * 2048 * 896 * 4864
    assert nbytes == 2 * (2048 * 896 + 896 * 4864 + 2048 * 4864)
    t, bound = roofline.least_time("_matmul_kernel", ins, outs, PEAK)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)


def test_decode_matmul_is_memory_bound():
    ins, outs = [("bf16", (32, 896)), ("bf16", (896, 151936))], [("bf16", (32, 151936))]
    t, bound = roofline.least_time("_matmul_kernel", ins, outs, PEAK)
    assert bound == "memory"
    assert t == pytest.approx(2 * (32 * 896 + 896 * 151936 + 32 * 151936) / 819e9)


def test_flash_attention_counts_causal_pairs():
    # 14 query heads over 2 kv heads, 1024 positions, head 64; batch 2
    q, kv = ("bf16", (28, 1024, 64)), ("bf16", (4, 1024, 64))
    outs = [("bf16", (28, 1024, 64)), ("f32", (28, 1024, 1))]
    ops, nbytes = roofline.work("_flash_kernel", [q, kv, kv], outs)
    assert ops == 4 * 28 * 64 * (1024 * 1025 // 2)
    assert nbytes == 2 * (28 + 4 + 4 + 28) * 1024 * 64 + 4 * 28 * 1024
    # a prefill chunk of 256 queries at the end of 1024 keys
    ops, _ = roofline.work("_flash_kernel", [("bf16", (14, 256, 64)), kv, kv], outs)
    assert ops == 4 * 14 * 64 * (256 * 768 + 256 * 257 // 2)


def test_unknown_kernel_raises():
    with pytest.raises(KeyError):
        roofline.work("_mystery_kernel", [("bf16", (8, 8))], [])
    assert roofline.family("_mystery_kernel") is None
    assert roofline.family("_rmsnorm_kernel") is None      # runs, but no metric reads it


def test_every_kernel_file_names_its_family():
    kernels = sorted(f[:-3] for f in os.listdir(os.path.join(B.BENCH, "kernels"))
                     if f.endswith(".py"))
    for k in kernels:
        mod = roofline.kernel(k)
        assert isinstance(mod.FAMILY, str) and callable(mod.work), k
    assert {k: roofline.family(k) for k in (
        "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel", "_flash_kernel", "_matmul_kernel")} == {
        "_flash_bwd_dkv_kernel": "flash_attention", "_flash_bwd_dq_kernel": "flash_attention",
        "_flash_kernel": "flash_attention", "_matmul_kernel": "matmul"}
    # the backward passes count as the forward does
    q, kv = ("bf16", (28, 1024, 64)), ("bf16", (4, 1024, 64))
    fwd = roofline.work("_flash_kernel", [q, kv, kv], [q])
    assert roofline.work("_flash_bwd_dq_kernel", [q, kv, kv], [q]) == fwd
    assert roofline.work("_flash_bwd_dkv_kernel", [q, kv, kv], [q]) == fwd


def test_a_kernel_added_as_a_file_is_found_and_read(tmp_path):
    """A kernel's roofline added as one file to a copy of the benchmark joins
    its family's metric; a kernel with no file still raises."""
    bench = B.make_copy(tmp_path)
    with open(os.path.join(bench, "kernels", "_scan_kernel.py"), "w") as f:
        f.write('"""A toy scan: one add per element, read and written once."""\n'
                "from roofline import nbytes\n\nFAMILY = 'scan'\n\n\n"
                "def work(ins, outs):\n"
                "    (_, (n, d)), = ins\n"
                "    return float(n * d), nbytes(ins) + nbytes(outs)\n")
    ins, outs = [("f32", (1024, 256))], [("f32", (1024, 256))]
    assert roofline.family("_scan_kernel", bench) == "scan"
    assert roofline.family("_scan_kernel") is None          # not in the benchmark itself
    assert roofline.work("_scan_kernel", ins, outs, bench) == (1024 * 256, 2 * 4 * 1024 * 256)
    with pytest.raises(KeyError):
        roofline.work("_mystery_kernel", ins, outs, bench)
    # the scan family's roofline over a hand trace: one call of 4 ms
    sig = roofline.signature(ins, outs)
    text = ("%closed_call.3 = f32[1024,256]{1,0} custom-call(f32[1024,256]{1,0} %x), "
            'custom_call_target="tpu_custom_call"')
    run = types.SimpleNamespace(
        bench=bench, peak=PEAK, span=(0, 10 * 10**6), kernels={sig: ("_scan_kernel", ins)},
        records={"devices": {"/device:TPU:0": [[text, 10**6, 4 * 10**6]]}, "host": []})
    import readers

    least = 2 * 4 * 1024 * 256 / PEAK["hbm_bytes_per_s"]
    assert readers.kernel_roofline(run, "scan") == pytest.approx(100 * least / 4e-3)
    assert readers.kernel_roofline(run, "matmul") is None


def test_train_flops_by_hand():
    per_layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert flops.matmul_params(QWEN2_05B) == 24 * per_layer + 896 * 151936
    tokens = 8 * 1024
    fwd = 2 * flops.matmul_params(QWEN2_05B) * tokens + 4 * 14 * 64 * 24 * 8 * (1024 * 1025 / 2)
    assert flops.train_flops(QWEN2_05B, 8, 1024) == pytest.approx(3 * fwd)
    # about 3.09 GFLOP a token at 1024 tokens a row
    assert flops.train_flops(QWEN2_05B, 8, 1024) / tokens == pytest.approx(3.09e9, rel=0.01)


def test_serve_flops_by_hand():
    head = 896 * 151936
    layers = flops.matmul_params(QWEN2_05B) - head
    L, n = 100, 3
    pairs = L * (L + 1) / 2 + (L + 1) + (L + 2)
    want = 2 * layers * (L + n - 1) + 2 * head * n + 4 * 14 * 64 * 24 * pairs
    assert flops.serve_flops(QWEN2_05B, [(L, n)]) == pytest.approx(want)


MATMUL_EVENT = (
    "%closed_call.9 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} custom-call(bf16[1024,1024]"
    "{1,0:T(8,128)(2,1)S(1)} %copy.18, bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} "
    "%dynamic-slice_bitcast_fusion.2), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[1024,1024]{1,0}, bf16[1024,1024]{1,0}}, "
    "frontend_attributes={kernel_metadata={}}")
FLASH_EVENT = (
    "%closed_call.10 = (bf16[16,1024,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[16,1024,1]{2,1,0:T(8,128)}) "
    "custom-call(bf16[16,1024,128]{2,1,0:T(8,128)(2,1)S(1)} %copy.17, bf16[2,1024,128]"
    "{2,1,0:T(8,128)(2,1)S(1)} %slice_bitcast_fusion.2, bf16[2,1024,128]{2,1,0:T(8,128)(2,1)S(1)} "
    "%slice_bitcast_fusion.2), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[16,1024,128]{2,1,0}, bf16[2,1024,128]{2,1,0}, "
    "bf16[2,1024,128]{2,1,0}}, frontend_attributes={kernel_metadata={}}")


def test_parse_device_op_names():
    name, opcode, ins, outs = roofline.parse_op(MATMUL_EVENT)
    assert (name, opcode) == ("closed_call.9", "custom-call")
    assert ins == [("bf16", (1024, 1024))] * 2 and outs == [("bf16", (1024, 1024))]
    name, opcode, ins, outs = roofline.parse_op(FLASH_EVENT)
    assert ins == [("bf16", (16, 1024, 128)), ("bf16", (2, 1024, 128)), ("bf16", (2, 1024, 128))]
    assert outs == [("bf16", (16, 1024, 128)), ("f32", (16, 1024, 1))]
    assert roofline.is_kernel_call(FLASH_EVENT)
    _, opcode, _, _ = roofline.parse_op(
        "%while = (s32[]{:T(128)}, bf16[8]{0}) while((s32[]{:T(128)}, bf16[8]{0}) %tuple.15), "
        "condition=%c, body=%b")
    assert opcode == "while"


def test_kernel_signatures_from_a_traced_program():
    """The signature of each Pallas call in a jaxpr is the one its device op
    carries in a trace."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    def f(x, y):
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                              interpret=True)(x, y)

    x = jnp.zeros((1024, 1024), jnp.bfloat16)
    sigs = roofline.pallas_kernels(jax.jit(f).trace(x, x).jaxpr.jaxpr)
    _, _, ins, outs = roofline.parse_op(MATMUL_EVENT)
    assert sigs == {roofline.signature(ins, outs): ("kernel", ins)}


def test_matmul_counts_the_work_before_padding():
    """A matmul whose operands were padded to its tiles is counted at the
    shapes it was asked for."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _matmul_kernel(x_ref, w_ref, o_ref):
        o_ref[...] = x_ref[...] @ w_ref[...]

    def f(x, w):
        xp, wp = jnp.pad(x, ((0, 0), (0, 128))), jnp.pad(w, ((0, 128), (0, 0)))
        return pl.pallas_call(_matmul_kernel, interpret=True, out_shape=jax.ShapeDtypeStruct(
            (x.shape[0], w.shape[1]), x.dtype))(xp, wp)

    x, w = jnp.zeros((256, 896), jnp.bfloat16), jnp.zeros((896, 512), jnp.bfloat16)
    (sig, (name, true_ins)), = roofline.pallas_kernels(jax.jit(f).trace(x, w).jaxpr.jaxpr).items()
    assert sig == "bf16[256,1024];bf16[1024,512]->bf16[256,512]"
    assert name == "_matmul_kernel" and true_ins == [("bf16", (256, 896)), ("bf16", (896, 512))]
    assert roofline.work(name, true_ins, [])[0] == 2 * 256 * 896 * 512
