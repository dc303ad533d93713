"""Program spans on the traced window's host plane: the arithmetic the
span-reading metrics share.

In a traced run every ``repro.obs`` span is also a profiler annotation of the
same name on the thread that opened it, so a span lies on the clock of the
device ops. Only the thread that ran the window (the one holding
``bench.window``) counts; spans are clipped to the window.
"""
from __future__ import annotations

import devtrace


def main_thread(rec):
    """The host thread that ran the measured window."""
    return next((th for name, _, _, th in rec["host"] if name == devtrace.WINDOW), None)


def covered(run, name):
    """[[start_ns, end_ns], ...]: the union, inside the window, of the main
    thread's spans called ``name``, sorted."""
    t0, t1 = run.span
    main = main_thread(run.records)
    spans = [(n, s, d) for n, s, d, th in run.records["host"] if n == name and th == main]
    return devtrace.busy_intervals(spans, t0, t1)


def overlap_ns(xs, ys):
    """Nanoseconds that two sorted lists of disjoint intervals share."""
    i = j = tot = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        tot += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def span_share(run, name):
    """Percent of the window that the spans called ``name`` cover; None where
    no such span lies in the window."""
    spans = covered(run, name)
    if not spans:
        return None
    t0, t1 = run.span
    return 100.0 * sum(b - a for a, b in spans) / (t1 - t0)


def idle_share_in(run, name):
    """Percent of the window in which the (first) device was idle inside the
    spans called ``name``; None where no such span lies in the window or no
    device was traced."""
    spans = covered(run, name)
    if not spans or not run.records["devices"]:
        return None
    t0, t1 = run.span
    busy = devtrace.busy_intervals(next(iter(run.records["devices"].values())), t0, t1)
    inside = sum(b - a for a, b in spans)
    return 100.0 * (inside - overlap_ns(spans, busy)) / (t1 - t0)
