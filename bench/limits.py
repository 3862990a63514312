"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/limits.py --workload <cell> --seconds <s> \
        --seeds <n>,<n>,... [--control]

Runs the cell once per seed, as ``run.py`` would, and prints each run's
compared numbers: the program's (the lower readings) or, with
``--control``, those of the control of ``control.py`` (the upper
readings).  One process holds the chip for all seeds.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    import cells
    import jax
    cell = cells.resolve(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("needs a TPU", file=sys.stderr)
        return 1
    import control
    import harness
    variant = "control" if args.control else "program"
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = control.installed() if args.control \
            else contextlib.nullcontext()
        with ctx:
            res = harness.run(cell, seed, args.seconds, False,
                              devices[:cell.chips], time.perf_counter())
        print(json.dumps({"workload": args.workload, "variant": variant,
                          "seed": seed, "correct": res["correct"],
                          "checks": {k: v["value"]
                                     for k, v in res["checks"].items()}}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
