#!/usr/bin/env python3
"""One measured run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: its file
``bench/workloads/<cell>.json`` names a configuration
(``bench/configs/<config>.json``), a driver (``bench/drivers/<driver>.py``),
its chips and its traffic; ``BENCHMARK.json`` lists the metrics each cell
reports, and each per-layer metric is read by ``bench/metrics/<metric>.py``.

A run checks for the chips the cell needs (none found: exit 3, no result),
builds the system under test from the seed, warms up every shape the window
uses (set-up), measures for ``--seconds``, reads the device's peak memory,
frees the system and then checks what the window produced against the plain
reference (``bench/reference.py``). With ``--trace 1`` the window runs under
the profiler and the per-layer metrics are read from the trace. Standard
error carries the set-up split, the dispatch tiers, compilations in the
window and, last, each number compared beside its limit; the last line of
standard output is the result as one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


class CompileClock:
    """Seconds and count of JAX's own compile events (tracing, lowering,
    backend compilation)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds, self.backend_compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration_secs, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration_secs
            self.backend_compiles += event == self.EVENTS[2]


class Run:
    """What a driver gets from the harness: the cell, its configuration, the
    seed, the window's length and whether it is traced; and the clocks and
    records it reports through."""

    def __init__(self, bench, name, cell, cfg, seed, seconds, trace, peak):
        self.bench, self.name, self.cell, self.cfg = bench, name, cell, cfg
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.peak = peak
        self.compiles = CompileClock()
        self.setup_split = {}
        self.setup_s = None
        self.in_window = None
        self.memory_peak_bytes = None
        self.counters = {}       # what the per-layer readers count with
        self.kernels = {}        # Pallas call signature -> kernel name
        self.records = None      # the reduced trace, when traced
        self.span = None         # (start_ns, end_ns) of the traced window

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        yield
        self.setup_split[name] = self.setup_split.get(name, 0.0) + time.perf_counter() - t0

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it opens."""
        import jax

        self.setup_s = time.perf_counter() - T_START
        c0, n0 = self.compiles.seconds, self.compiles.backend_compiles
        prof_dir = None
        with contextlib.ExitStack() as stack:
            if self.trace:
                import repro.obs as obs

                prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(prof_dir, profiler_options=opts)
                stack.callback(jax.profiler.stop_trace)
                stack.enter_context(obs.collect(name="bench", xla_annotations=True))
            stack.enter_context(jax.profiler.TraceAnnotation("bench.window"))
            yield
        self.in_window = (self.compiles.seconds - c0, self.compiles.backend_compiles - n0)
        if prof_dir:
            import devtrace as tr

            try:
                self.records = tr.load(prof_dir)
                self.span = tr.window(self.records)
            finally:
                shutil.rmtree(prof_dir, ignore_errors=True)

    def read_memory_peak(self):
        """The peak bytes in use on the fullest chip; read before the
        reference runs (a process's peak never falls again)."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


def _finite(x):
    return isinstance(x, (int, float)) and math.isfinite(x)


def main(argv=None, bench=BENCH, program_root=None, chip_check=True, compile_cache=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.abspath(bench))
    program_root = program_root or root
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    if args.workload not in {w["name"] for w in spec["workloads"]}:
        log(f"bench: no cell {args.workload!r} in BENCHMARK.json")
        return 2
    cell = load_json(os.path.join(bench, "workloads", args.workload + ".json"))
    cfg = load_json(os.path.join(bench, "configs", cell["config"] + ".json"))

    import jax

    devices = jax.devices()
    if chip_check and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        log(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s) ({devices[0].device_kind})")
        return 3
    src = os.path.join(program_root, "src")
    sys.path.insert(0, src)
    try:
        import repro
    except ImportError as e:
        log(f"bench: the system under test is not in {src} ({e})")
        return 2
    if not os.path.realpath(repro.__file__).startswith(os.path.realpath(src) + os.sep):
        log(f"bench: imported repro from {repro.__file__}, not from {src}")
        return 2
    if compile_cache:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import roofline

    peak = roofline.peaks(devices[0].device_kind) if chip_check else None
    run = Run(bench, args.workload, cell, cfg, args.seed, args.seconds, bool(args.trace), peak)
    driver = load_module(os.path.join(bench, "drivers", cell["driver"] + ".py"),
                         f"bench_driver_{cell['driver']}")
    res = driver.run(run)

    log("set-up split (s): " + json.dumps(
        {"total": run.setup_s, "compile": res.get("setup_compile_s"), **run.setup_split}))
    log(f"in the window: {run.in_window[1]} backend compilations, "
        f"{run.in_window[0]} s of compile events")
    for line in res.get("notes", []):
        log(line)

    want = [m for m in spec["end_to_end"] if applies(m, args.workload)]
    metrics = {m["name"]: {"value": res["metrics"][m["name"]], "unit": m["unit"]} for m in want}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": None, "attempted": res["attempted"], "failed": res["failed"]}
    if args.trace:
        import devtrace as tr

        t0, t1 = run.span
        out["metrics"] = read_per_layer(run, spec, bench)
        out["breakdown"] = {"device_ops": tr.top_ops(run.records, t0, t1, run.kernels),
                            "idle_gaps": tr.idle_gaps(run.records, t0, t1)}
        device["busy_s"], device["window_s"] = tr.busy_s(run.records, t0, t1), (t1 - t0) * 1e-9
    else:
        out["metrics"] = metrics
    out["device"] = device
    checks = res["checks"]
    correct = bool(checks) and all(_finite(v) and v <= lim for v, lim in checks.values())
    out["correct"] = correct
    out["checks"] = {k: {"value": v if _finite(v) else str(v), "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    print(json.dumps(out), flush=True)
    return 0


def read_per_layer(run, spec, bench):
    """The cell's per-layer metrics, each by its own reader; a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        if applies(m, run.name):
            mod = load_module(os.path.join(bench, "metrics", m["name"] + ".py"),
                              "bench_metric_" + m["name"].replace(".", "_"))
            v = mod.read(run)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


if __name__ == "__main__":
    sys.exit(main())
