"""Open-loop job submissions: Poisson arrivals with bursts, Zipf routes.

Arrivals are a Poisson process at ``rate_per_s``, multiplied by
``burst_factor`` for ``burst_s`` seconds every ``burst_every_s`` seconds
(the bursts sit at the same times for every seed).  Each submission picks
a live route by Zipf(``zipf_s``) rank; the map from rank to live route
rotates by ``rotate_step`` places every ``rotate_every_s`` seconds, so the
hot set moves.  A share ``new_route_share`` of submissions instead opens a
route that is not live (when the deployment has one): it takes the place
of the least recently used live route, which is retired first, so the
number of live routes stays fixed.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Stream:
    due_s: np.ndarray        # (N,) float64 seconds after the window opens
    route: np.ndarray        # (N,) int32 route index
    retire: np.ndarray       # (N,) int32 route retired just before, or -1
    live_end: List[int]      # live routes after the last submission


def _arrivals(mix: dict, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    rate, factor = mix["rate_per_s"], mix["burst_factor"]
    every, length = mix["burst_every_s"], mix["burst_s"]
    edges = sorted({0.0, float(seconds)}
                   | {x for k in range(int(seconds // every) + 1)
                      for x in (k * every + mix["burst_at_s"],
                                k * every + mix["burst_at_s"] + length)
                      if 0.0 < x < seconds})
    times = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2 - mix["burst_at_s"]
        in_burst = mid >= 0 and (mid % every) < length
        n = rng.poisson((rate * factor if in_burst else rate) * (b - a))
        times.append(np.sort(rng.uniform(a, b, n)))
    return np.concatenate(times)


def stream(mix: dict, n_routes: int, live0: List[int], seconds: float,
           rng: np.random.Generator) -> Stream:
    due = _arrivals(mix, seconds, rng)
    n = due.shape[0]
    live = list(live0)
    n_live = len(live)
    spare = [r for r in range(n_routes) if r not in set(live)]
    weights = 1.0 / np.arange(1, n_live + 1) ** mix["zipf_s"]
    ranks = rng.choice(n_live, n, p=weights / weights.sum())
    opens = rng.random(n) < mix["new_route_share"] if spare \
        else np.zeros(n, dtype=bool)
    picks = rng.random(n)
    last_use = np.full(n_live, -1.0)
    route = np.empty(n, dtype=np.int32)
    retire = np.full(n, -1, dtype=np.int32)
    for i in range(n):
        turn = int(due[i] // mix["rotate_every_s"]) * mix["rotate_step"]
        slot = (int(ranks[i]) + turn) % n_live
        if opens[i]:
            slot = int(np.argmin(last_use))
            new = spare.pop(int(picks[i] * len(spare)))
            retire[i] = live[slot]
            spare.append(live[slot])
            live[slot] = new
        route[i] = live[slot]
        last_use[slot] = due[i]
    return Stream(due_s=due, route=route, retire=retire, live_end=live)
