"""The engine counter reader ``device_pick_share`` on hand-made counters."""
from __future__ import annotations

import importlib.util
import os
import types

import pytest

import bench_smoke as B


def _read(stats):
    spec = importlib.util.spec_from_file_location(
        "m_device_pick_share", os.path.join(B.BENCH, "metrics", "device_pick_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    counters = {} if stats is None else {"stats": stats}
    return mod.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("stats, want", [
    ({"decode_steps": 1201, "device_pick_ticks": 1201}, 100.0),
    ({"decode_steps": 540, "device_pick_ticks": 270}, 50.0),
    ({"decode_steps": 540}, None),                       # an engine without the counter
    ({"decode_steps": 0, "device_pick_ticks": 0}, None),
    (None, None),
])
def test_device_pick_share(stats, want):
    got = _read(stats)
    assert got == (None if want is None else pytest.approx(want))
