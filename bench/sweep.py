"""Find an open cell's knee: the highest tick rate whose staleness does
not grow over the window.

    python3 bench/sweep.py --workload <config>.open --seconds <s> \
        --rates <ticks/s>,... [--sub-rates <submissions/s>,...] --seed <n>

For each tick rate it runs the cell once (one process holds the chip for
all) and prints the staleness median of the window's first and second
half, its 95th percentile, and the mean tick spans.  A rate the fleet
sustains shows the two halves alike; above the knee the second half
grows with the backlog.  ``--sub-rates`` then runs the cell at the last
tick rate with each submission rate, to show where the worker path
stands.  The knee goes into the deployment file by hand.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def _row(out, rate, sub_rate) -> dict:
    import numpy as np
    stale = out.tick_pub - out.tick_due
    half = stale.size // 2
    late = np.where(np.isnan(out.sub_done), np.inf, out.sub_done - out.sub_due)
    return {"tick_rate": rate, "sub_rate": sub_rate,
            "ticks": int(stale.size),
            "stale_ms_first_half_p50": float(np.median(stale[:half]) * 1e3),
            "stale_ms_second_half_p50": float(np.median(stale[half:]) * 1e3),
            "stale_ms_p95": float(np.percentile(stale, 95) * 1e3),
            "decision_ms_p95": float(np.percentile(late, 95) * 1e3)
            if late.size else None,
            "shed": int(out.sub_shed.sum()),
            "tick_total_ms": (out.span_mean("tick.total") or 0) * 1e3,
            "snapshot_build_ms": (out.span_mean("snapshot.build") or 0) * 1e3,
            "tick_reprice_ms": (out.span_mean("tick.reprice") or 0) * 1e3,
            "serve_worker_us": (out.span_mean("serve.worker") or 0) * 1e6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--sub-rates", default="")
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    import cells
    import harness
    import jax
    cell = cells.resolve(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    driver = cells.driver(cell.config["driver"])
    counter = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    rates = [float(r) for r in args.rates.split(",")]
    plan = [(r, None) for r in rates]
    plan += [(rates[-1], float(s)) for s in args.sub_rates.split(",") if s]
    for rate, sub_rate in plan:
        window = harness.Window(False, counter)
        out = driver.run(cell, args.seed, args.seconds, window,
                         devices[:1], time.perf_counter(), tick_rate=rate,
                         sub_rate=sub_rate)
        row = _row(out, rate, sub_rate)
        row["compiles_in_window"] = window.compiles
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
