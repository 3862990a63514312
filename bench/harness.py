"""Run one cell once and assemble its result line.

The driver named by the cell's deployment builds the system and drives
the window; this module keeps what every cell shares: the compile cache
and the count of compiles inside the window, the profiler trace of a
``--trace 1`` run and its reduction, the metric readers found by name,
the device stamp, and the comparison that decides ``correct``.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import jax
import numpy as np

import cells
import trace_reduce

BENCH = pathlib.Path(__file__).resolve().parent

#: JAX's own monitoring event for a backend compile (a persistent-cache
#: hit does not emit it) and for tracing a function to a jaxpr
_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    def __init__(self):
        self.compiles = 0
        self.traces = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE:
            self.compiles += 1
        elif event == _TRACE:
            self.traces += 1


class Window:
    """Marks the measured window for the driver: counts what compiled in
    it, and with ``trace`` records a profiler trace of it."""

    def __init__(self, trace: bool, counter: CompileCounter):
        self.trace = trace
        self.counter = counter
        self.log_dir = tempfile.mkdtemp(prefix="bench-trace-") \
            if trace else None
        self.compiles = self.traces = 0

    def begin(self) -> None:
        self._c0 = (self.counter.compiles, self.counter.traces)
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def end(self) -> None:
        self.compiles = self.counter.compiles - self._c0[0]
        self.traces = self.counter.traces - self._c0[1]
        if self.trace:
            jax.profiler.stop_trace()


def load_peaks(kind: str, bench: pathlib.Path = BENCH) -> dict:
    with open(bench / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json")
    return table[kind]


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        devices: List, t_start: float, bench: pathlib.Path = BENCH) -> Dict:
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # every program into the persistent cache, however quick to compile,
    # so that a later run's set-up finds them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    window = Window(trace, counter)
    driver = cells.driver(cell.config["driver"], bench)
    dev = devices[0]
    peaks = load_peaks(dev.device_kind, bench) if trace else None
    try:
        out = driver.run(cell, seed, seconds, window, devices, t_start)
        print(f"window: {window.compiles} compiles, {window.traces} traces "
              f"inside it; setup_s {out.setup_s:.3f}; producer lateness "
              f"p95 {_p95_ms(out.lateness)} ms", flush=True)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": out.memory_peak_bytes}
        breakdown = None
        if trace:
            t0 = time.perf_counter()
            out.trace, out.summary = trace_reduce.reduce_dir(window.log_dir)
            out.peaks = peaks
            device["busy_s"] = out.summary.busy_s
            device["window_s"] = out.summary.window_s
            breakdown = {"device_ops": out.summary.device_ops,
                         "idle_gaps": out.summary.idle_gaps}
            print(f"trace reduced in {time.perf_counter() - t0:.1f} s",
                  flush=True)
        metrics = {}
        for m in (cell.layer if trace else cell.e2e):
            kind = "layer_metrics" if trace else "e2e_metrics"
            value = cells.reader(kind, m["name"], bench).read(out)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif not trace:
                raise RuntimeError(f"{m['name']} read nothing")
        t0 = time.perf_counter()
        checks = out.checks()
        print(f"reference compared in {time.perf_counter() - t0:.1f} s",
              flush=True)
    finally:
        if window.log_dir:
            shutil.rmtree(window.log_dir, ignore_errors=True)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _p95_ms(x) -> str:
    return "n/a" if len(x) == 0 else f"{float(np.percentile(x, 95)) * 1e3:.3f}"


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
