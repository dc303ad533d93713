"""The arithmetic the per-layer metric readers share. Each reader returns a
number, or None where its cell gave it nothing to read."""
from __future__ import annotations

import sys

import devtrace
import flops
import roofline


def _window_s(run):
    t0, t1 = run.span
    return (t1 - t0) * 1e-9


def idle_share(run):
    """Percent of the traced window in which no op ran on the device."""
    t0, t1 = run.span
    if not run.records["devices"]:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(run.records, t0, t1) / _window_s(run))


def mfu(run, model_flops):
    """Model operations done in the window over what the chips' peak allows
    in it, in percent."""
    if not run.peak or not model_flops:
        return None
    chips = max(1, len(run.records["devices"]))
    return 100.0 * model_flops / (_window_s(run) * chips * run.peak["bf16_flops"])


def train_mfu(run):
    return mfu(run, flops.train_flops(run.cfg, run.counters["rows"], run.counters["seq_len"]))


def serve_mfu(run):
    return mfu(run, flops.serve_flops(run.cfg, run.counters["requests"]))


def kernel_roofline(run, family):
    """Sum of the least times of a kernel family's calls in the window over
    the sum of their device times, in percent; which bound (compute or
    memory) holds each call goes to standard error."""
    if not run.peak:
        return None
    t0, t1 = run.span
    least = spent = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    unknown = 0
    for kernel, ins, outs, secs in devtrace.kernel_calls(run.records, t0, t1, run.kernels):
        if kernel is None:
            unknown += 1
            continue
        if roofline.FAMILY.get(kernel) != family:
            continue
        t, bound = roofline.least_time(kernel, ins, outs, run.peak)
        least += t
        spent += secs
        by_bound[bound] += t
    if unknown:
        print(f"roofline {family}: {unknown} kernel calls with no known signature",
              file=sys.stderr)
    if spent <= 0:
        return None
    print(f"roofline {family}: least time {least!r} s over device time {spent!r} s; "
          f"least time by bound {by_bound}", file=sys.stderr)
    return 100.0 * least / spent
