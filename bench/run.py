"""Run one benchmark cell once.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's files are found by name (``cells.py``).  The run needs as many
TPU chips as the cell asks for: without them it prints why and exits 1
before anything runs.  The last line of standard output is the result as
JSON; the numbers that decided ``correct`` are also the last lines of
standard error, each beside its limit.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cells
    cell = cells.resolve(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing ran.",
              file=sys.stderr)
        return 1
    import harness
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices[:cell.chips], T_START)
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
