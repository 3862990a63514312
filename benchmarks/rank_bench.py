"""Vectorized rank vs the historical per-pair dict loop.

    PYTHONPATH=src python benchmarks/rank_bench.py

Prints ``name,cells,us_dict,us_numpy,speedup`` CSV rows.  The
acceptance bar: the vectorized formulation must beat the dict loop from
~1k (job x config) cells up (at 10k+ cells the ranking is one fused
matrix op instead of ~cells dict lookups).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Hashable, List, Mapping, Sequence, Tuple

import numpy as np

from repro.selector import rank_dense, rank_pairs


def rank_dict_loop(
    runtime_hours: Mapping[Tuple[Hashable, Hashable], float],
    jobs: Sequence[Hashable],
    config_ids: Sequence[Hashable],
    hourly_cost: Callable[[Hashable], float],
) -> List[Tuple[Hashable, float]]:
    """The pre-selector implementation, kept verbatim as the baseline."""
    scores: Dict[Hashable, float] = {c: 0.0 for c in config_ids}
    for j in jobs:
        costs = {c: runtime_hours[(j, c)] * hourly_cost(c)
                 for c in config_ids if (j, c) in runtime_hours}
        if not costs:
            continue
        best = min(costs.values())
        for c, v in costs.items():
            scores[c] += v / best
    order = {c: i for i, c in enumerate(config_ids)}
    return sorted(scores.items(), key=lambda kv: (kv[1], order[kv[0]]))


def synth_universe(n_jobs: int, n_cfgs: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    jobs = [f"j{i}" for i in range(n_jobs)]
    cfgs = [f"c{i}" for i in range(n_cfgs)]
    hours = rng.uniform(0.05, 10.0, size=(n_jobs, n_cfgs))
    prices = rng.uniform(0.5, 20.0, size=n_cfgs)
    pairs = {(j, c): float(hours[r, k])
             for r, j in enumerate(jobs) for k, c in enumerate(cfgs)}
    return jobs, cfgs, hours, np.ones_like(hours, dtype=bool), prices, pairs


def _timed(fn, repeat: int) -> float:
    fn()                                    # warmup (caches)
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat * 1e6


def compare(n_jobs: int, n_cfgs: int, repeat: int = 20) -> Dict[str, float]:
    jobs, cfgs, hours, mask, prices, pairs = synth_universe(n_jobs, n_cfgs)
    price_of = dict(zip(cfgs, prices)).__getitem__
    us_dict = _timed(lambda: rank_dict_loop(pairs, jobs, cfgs, price_of),
                     repeat)
    us_numpy = _timed(lambda: rank_dense(hours, mask, prices, cfgs), repeat)
    # sanity: identical winner and ordering
    base = [c for c, _ in rank_dict_loop(pairs, jobs, cfgs, price_of)]
    vec = [r.config_id for r in rank_pairs(pairs, jobs, cfgs, price_of)]
    assert base == vec, "vectorized ranking diverged from the dict loop"
    return {"cells": n_jobs * n_cfgs, "us_dict": us_dict,
            "us_numpy": us_numpy, "speedup": us_dict / us_numpy}


def main() -> None:
    print("name,cells,us_dict,us_numpy,speedup")
    for n_jobs, n_cfgs in ((10, 10), (50, 20), (100, 100), (500, 100),
                           (1000, 250)):
        r = compare(n_jobs, n_cfgs, repeat=5 if n_jobs >= 500 else 20)
        print(f"rank_{n_jobs}x{n_cfgs},{r['cells']},{r['us_dict']:.1f},"
              f"{r['us_numpy']:.1f},{r['speedup']:.1f}x")


if __name__ == "__main__":
    main()
