"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the newest ``.xplane.pb`` under a profile directory into plain
records: per device, its ``XLA Ops`` events [name, start_ns, dur_ns]; for the
host, every event of its threads [name, start_ns, dur_ns, thread]. The
reductions below work on those records alone, so a small recorded trace can
test them.
"""
from __future__ import annotations

import functools
import glob
import os
import re

from roofline import is_kernel_call, parse_op, signature

WINDOW = "bench.window"
# Device ops whose interval holds other ops: counted in the busy union, left
# out of the per-op ranking (their time is their children's).
_CONTAINERS = {"while", "conditional", "call"}
# HLO opcodes of the collectives; each may also run as an async start/done pair.
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")
_COLLECTIVE_OPCODES = {c + s for c in COLLECTIVES for s in ("", "-start", "-done")}


def load(profile_dir):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    rec = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([e.name, e.start_ns, e.duration_ns] for e in line.events)
            rec["devices"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rec["host"].extend([e.name, e.start_ns, e.duration_ns, line.name]
                                   for e in line.events)
    return rec


def window(rec):
    """(start_ns, end_ns) of the host span that marks the measured window."""
    spans = [(s, s + d) for name, s, d, _ in rec["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(ops, t0, t1):
    out = []
    for name, s, d in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def busy_intervals(ops, t0, t1):
    """The union of the ops' intervals inside [t0, t1], merged and sorted."""
    merged = []
    for _, a, b in sorted(_clip(ops, t0, t1), key=lambda x: x[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_s(rec, t0, t1):
    """Seconds in which some op ran, averaged over the devices traced."""
    per = [sum(b - a for a, b in busy_intervals(ops, t0, t1)) * 1e-9
           for ops in rec["devices"].values()]
    return sum(per) / len(per) if per else 0.0


@functools.lru_cache(maxsize=None)
def _op(text):
    """(name without numeric suffix, opcode, operand types, result types,
    signature or None) of a device op's text; a step's programs repeat the
    same few thousand texts, so each is parsed once."""
    name, opcode, ins, outs = parse_op(text)
    sig = signature(ins, outs) if is_kernel_call(text) else None
    return re.sub(r"(\.\d+)+$", "", name), opcode, ins, outs, sig


def op_label(text, kernels):
    """(label, opcode) of a device op: the label is the Pallas kernel it
    runs, else its HLO instruction name without the numeric suffix."""
    name, opcode, _, _, sig = _op(text)
    if sig is not None:
        return kernels.get(sig, ("tpu_custom_call",))[0], opcode
    return name, opcode


@functools.lru_cache(maxsize=None)
def is_collective(text):
    """Whether a device op, by its HLO opcode, is a collective."""
    return re.sub(r"(\.\d+)+$", "", _op(text)[1]) in _COLLECTIVE_OPCODES


def top_ops(rec, t0, t1, kernels, n=10):
    """[[label, seconds], ...]: the device ops that took most time in the
    window (first device), summed by label; loops are left out."""
    tot = {}
    ops = next(iter(rec["devices"].values()), [])
    for text, a, b in _clip(ops, t0, t1):
        label, opcode = op_label(text, kernels)
        if opcode not in _CONTAINERS:
            tot[label] = tot.get(label, 0.0) + (b - a) * 1e-9
    return _top(tot, n)


def _top(tot, n):
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rec, t0, t1, n=10, short_ns=20_000):
    """[[label, seconds], ...]: the device's idle time in the window, summed
    by what the host's main thread was doing at the middle of each gap (the
    innermost host event covering it), most first. Gaps under ``short_ns``
    are summed as one entry."""
    ops = next(iter(rec["devices"].values()), [])
    busy = busy_intervals(ops, t0, t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    short = f"gaps under {short_ns // 1000} us"
    tot = {short: sum(b - a for a, b in gaps if b - a < short_ns) * 1e-9}
    main = next((th for name, _, _, th in rec["host"] if name == WINDOW), None)
    events = sorted((s, s + d, name) for name, s, d, th in rec["host"]
                    if th == main and name != WINDOW)
    active, i = [], 0
    for a, b in sorted(g for g in gaps if g[1] - g[0] >= short_ns):
        t = (a + b) / 2
        while i < len(events) and events[i][0] <= t:
            active.append(events[i])
            i += 1
        active = [e for e in active if e[1] > t]
        label = min(active, key=lambda e: e[1] - e[0])[2] if active else "host idle"
        tot[label] = tot.get(label, 0.0) + (b - a) * 1e-9
    return _top(tot, n)


def kernel_calls(rec, t0, t1, kernels):
    """[(kernel, operand types before padding, result types, seconds)] of
    every Pallas call that ran in the window, on every device traced; the
    kernel is None where ``kernels`` lacks the call's signature."""
    out = []
    for ops in rec["devices"].values():
        for text, a, b in _clip(ops, t0, t1):
            _, _, ins, outs, sig = _op(text)
            if sig is not None:
                kernel, true_ins = kernels.get(sig, (None, ins))
                out.append((kernel, true_ins, outs, (b - a) * 1e-9))
    return out
