"""The reduction from a profiler trace to busy time, idle gaps and kernel
time: on a hand-made trace with known answers, and on 6 ms recorded on a
TPU v5e in the training cell."""
from __future__ import annotations

import json
import os

import pytest

import bench_smoke  # noqa: F401  (puts the benchmark on the path)
import devtrace
import roofline

MS = 1_000_000
MM = ('%{n} = bf16[256,512]{{1,0}} custom-call(bf16[256,1024]{{1,0}} %a, bf16[1024,512]{{1,0}} %b), '
      'custom_call_target="tpu_custom_call"')
SIG = "bf16[256,1024];bf16[1024,512]->bf16[256,512]"


def hand_trace():
    """Window 0..10 ms. Device: a matmul 1..3 ms, a fusion 2..4 ms (overlaps),
    a loop 6..9 ms holding a matmul 6..7 ms. Host: a step span 0..10 ms and a
    data span 4..6 ms on the main thread."""
    ops = [[MM.format(n="closed_call.1"), 1 * MS, 2 * MS],
           ["%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop", 2 * MS, 2 * MS],
           ["%while.2 = (s32[]) while((s32[]) %t), body=%b", 6 * MS, 3 * MS],
           [MM.format(n="closed_call.7"), 6 * MS, 1 * MS]]
    host = [["bench.window", 0, 10 * MS, "python3"], ["bench.step", 0, 10 * MS, "python3"],
            ["train.data", 4 * MS, 2 * MS, "python3"], ["other", 4 * MS, 2 * MS, "worker"]]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_busy_and_idle_by_hand():
    rec = hand_trace()
    t0, t1 = devtrace.window(rec)
    assert (t0, t1) == (0, 10 * MS)
    # busy: 1..4 and 6..9 ms
    assert devtrace.busy_s(rec, t0, t1) == pytest.approx(6e-3)
    gaps = dict(devtrace.idle_gaps(rec, t0, t1))
    # idle 0..1 and 9..10 under the step span, 4..6 under train.data
    assert gaps["train.data"] == pytest.approx(2e-3)
    assert gaps["bench.step"] == pytest.approx(2e-3)
    assert sum(gaps.values()) == pytest.approx(4e-3)


def test_top_ops_and_kernel_calls_by_hand():
    rec = hand_trace()
    kernels = {SIG: ("_matmul_kernel", [("bf16", (256, 896)), ("bf16", (896, 512))])}
    top = dict(devtrace.top_ops(rec, 0, 10 * MS, kernels))
    assert top == pytest.approx({"_matmul_kernel": 3e-3, "fusion": 2e-3})   # the loop is left out
    calls = devtrace.kernel_calls(rec, 0, 10 * MS, kernels)
    assert [c[0] for c in calls] == ["_matmul_kernel"] * 2
    assert calls[0][1] == [("bf16", (256, 896)), ("bf16", (896, 512))]
    assert sum(c[3] for c in calls) == pytest.approx(3e-3)
    # clipped to the window
    assert devtrace.busy_s(rec, 2 * MS, 7 * MS) == pytest.approx(3e-3)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(__file__), "data", "v5e_train_6ms.json")) as f:
        return json.load(f)


def test_recorded_trace(recorded):
    t0, t1 = devtrace.window(recorded)
    window = (t1 - t0) * 1e-9
    busy = devtrace.busy_s(recorded, t0, t1)
    assert 0 < busy <= window
    idle = sum(v for _, v in devtrace.idle_gaps(recorded, t0, t1, n=1000))
    assert idle == pytest.approx(window - busy, rel=1e-9, abs=1e-12)
    # the kernel calls of the excerpt, with the signatures the training
    # step's jaxpr gives them
    sigs = {}
    for text, _, _ in next(iter(recorded["devices"].values())):
        if roofline.is_kernel_call(text):
            _, _, ins, outs = roofline.parse_op(text)
            sigs[roofline.signature(ins, outs)] = ins
    assert "bf16[2048,1024];bf16[1024,4864]->bf16[2048,4864]" in sigs
    assert ("bf16[28,1024,64];bf16[4,1024,64];bf16[4,1024,64]"
            "->bf16[28,1024,64];f32[28,1024,1]") in sigs
    kernels = {s: ("_matmul_kernel" if len(ins) == 2 and len(ins[1][1]) == 2 and ins[1][1][0] > 1
                   else "_other", ins) for s, ins in sigs.items()}
    calls = devtrace.kernel_calls(recorded, t0, t1, kernels)
    mm = [c for c in calls if c[0] == "_matmul_kernel"]
    assert mm and sum(c[3] for c in calls) <= busy
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = sum(roofline.least_time(k, ins, outs, peak)[0] for k, ins, outs, _ in mm)
    share = least / sum(c[3] for c in mm)
    assert 0 < share <= 1.05
    top = devtrace.top_ops(recorded, t0, t1, kernels)
    assert top[0][0] == "_matmul_kernel" and len(top) <= 10


def _collective_reader():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "m_collective_share", os.path.join(bench_smoke.BENCH, "metrics", "collective_share.train.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_collective_share_by_hand():
    """Window 0..10 ms on the first device: an async all-reduce (start 1..2,
    done 2..3 ms), an all-to-all 5..6 ms overlapping a fusion 5..7 ms, a
    reduce-scatter 9..11 ms cut at the window's end; a matmul and the fusion
    are not collectives. A second device's collectives are not read."""
    import types

    dev0 = [["%all-reduce-start.1 = (bf16[896]{0}, bf16[896]{0}) all-reduce-start(bf16[896]{0} %g), "
             "channel_id=1, replica_groups=[1,4]<=[4]", 1 * MS, 1 * MS],
            ["%all-reduce-done.1 = bf16[896]{0} all-reduce-done((bf16[896]{0}, bf16[896]{0}) "
             "%all-reduce-start.1)", 2 * MS, 1 * MS],
            [MM.format(n="closed_call.1"), 3 * MS, 2 * MS],
            ["%all-to-all.4 = (s32[1,2,64]{2,1,0}) all-to-all(s32[1,2,64]{2,1,0} %t)", 5 * MS, 1 * MS],
            ["%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop", 5 * MS, 2 * MS],
            ["%reduce-scatter.2 = f32[224]{0} reduce-scatter(f32[896]{0} %g), dimensions={0}",
             9 * MS, 2 * MS]]
    dev1 = [["%all-gather.2 = f32[896]{0} all-gather(f32[224]{0} %g), dimensions={0}", 0, 10 * MS]]
    read = _collective_reader()
    run = types.SimpleNamespace(
        span=(0, 10 * MS),
        records={"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, "host": []})
    # 1..3 + 5..6 + 9..10 ms of 10
    assert read(run) == pytest.approx(40.0)
    assert [devtrace.is_collective(op[0]) for op in dev0] == [True, True, False, True, False, True]
    # no collective in the window, or no device traced: nothing to read
    run.records["devices"] = {"/device:TPU:0": dev0[2:3] + dev0[4:5]}
    assert read(run) is None
    run.records["devices"] = {}
    assert read(run) is None
