"""The span-reading engine metrics on a hand-made trace with known answers."""
from __future__ import annotations

import importlib.util
import os
import types

import pytest

import bench_smoke as B
import spans

MS = 1_000_000


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(B.BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def hand_run(host=None):
    """Window 0..10 ms. Device busy 1..3 and 6..8 ms. Main thread: a tick
    0..5 ms holding a fetch 3..4 ms, an admission 5..9 ms. Another thread: a
    tick 2..9 ms, which the readers must ignore."""
    ops = [["%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x)", 1 * MS, 2 * MS],
           ["%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %x)", 6 * MS, 2 * MS]]
    if host is None:
        host = [["bench.window", 0, 10 * MS, "python3"],
                ["bench.wave", 0, 10 * MS, "python3"],
                ["serve.tick", 0, 5 * MS, "python3"],
                ["serve.fetch", 3 * MS, 1 * MS, "python3"],
                ["serve.admit", 5 * MS, 4 * MS, "python3"],
                ["serve.tick", 2 * MS, 7 * MS, "worker"]]
    return types.SimpleNamespace(records={"devices": {"/device:TPU:0": ops}, "host": host},
                                 span=(0, 10 * MS))


def test_engine_span_metrics_by_hand():
    run = hand_run()
    # idle inside the tick: 0..1 and 3..5 ms of the 10 ms window
    assert _reader("tick_idle_share")(run) == pytest.approx(30.0)
    assert _reader("admit_share")(run) == pytest.approx(40.0)


def test_spans_are_clipped_to_the_window_and_merged():
    run = hand_run()
    run.span = (4 * MS, 10 * MS)
    run.records["host"][0] = ["bench.window", 4 * MS, 6 * MS, "python3"]
    assert spans.covered(run, "serve.tick") == [[4 * MS, 5 * MS]]
    # idle 4..5 ms inside the clipped tick, over a 6 ms window
    assert spans.idle_share_in(run, "serve.tick") == pytest.approx(100.0 / 6)
    assert spans.overlap_ns([[0, 5], [6, 9]], [[1, 3], [4, 7], [8, 20]]) == 2 + 1 + 1 + 1


def test_a_window_with_no_spans_reads_none():
    run = hand_run(host=[["bench.window", 0, 10 * MS, "python3"],
                         ["serve.tick", 12 * MS, 1 * MS, "python3"]])
    assert _reader("tick_idle_share")(run) is None
    assert _reader("admit_share")(run) is None
    # spans but no device traced: the idle share has nothing to read
    run = hand_run()
    run.records["devices"] = {}
    assert _reader("tick_idle_share")(run) is None
    assert _reader("admit_share")(run) == pytest.approx(40.0)
