"""Spot price ticks: a vectorised copy of ``SimulatedSpotFeed``'s walk.

Each tick re-quotes a fixed share of the catalog, drawn from the spot
columns only.  A quote is a mean-reverting step of the log price toward
the column's (event-adjusted) target under a Gaussian shock, clamped to
``base * [1 / band, band]``: the dynamics of
``repro.market.feed.SimulatedSpotFeed.poll``, drawn from a seeded numpy
generator instead of per-quote md5 hashes.  Every ``event_every`` ticks a
regional eviction event starts: for ``event_ticks`` ticks the region's
target is ``base * event_factor``, and at both boundaries every spot
column of the region snaps to its new target (plus a shock), as the
original does at an event's boundary.  The first event starts at tick
``event_first``, so that warm-up can meet a boundary tick before the
window does.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

Batch = Tuple[np.ndarray, np.ndarray]    # (int32 columns, float64 $/h)


def spot_batches(base: np.ndarray, spot_cols: np.ndarray,
                 region_of_col: np.ndarray, n_regions: int, n_ticks: int,
                 walk: dict, rng: np.random.Generator) -> List[Batch]:
    """``n_ticks`` batches of absolute re-quotes, in tick order."""
    n_quotes = int(round(walk["change_fraction"] * base.shape[0]))
    if not 0 < n_quotes <= spot_cols.shape[0]:
        raise ValueError(f"{n_quotes} quotes per tick from "
                         f"{spot_cols.shape[0]} spot columns")
    rev, vol, band = walk["reversion"], walk["volatility"], walk["band"]
    every, length = walk["event_every"], walk["event_ticks"]
    first = walk["event_first"]
    if length >= every:
        raise ValueError("eviction events may not overlap")
    price = base.copy()
    lo, hi = base / band, base * band
    factor = np.ones(n_regions)
    region_spot = [spot_cols[region_of_col[spot_cols] == r]
                   for r in range(n_regions)]
    event_region = -1
    out: List[Batch] = []
    for t in range(n_ticks):
        snap = np.zeros(0, dtype=np.int64)
        if t >= first and (t - first) % every == 0:
            event_region = int(rng.integers(n_regions))
            factor[event_region] = walk["event_factor"]
            snap = region_spot[event_region]
        elif t > first and (t - first) % every == length:
            factor[event_region] = 1.0
            snap = region_spot[event_region]
        walked = rng.choice(spot_cols, n_quotes, replace=False)
        walked = walked[~np.isin(walked, snap)]
        target_snap = base[snap] * factor[region_of_col[snap]]
        new_snap = target_snap * np.exp(vol * rng.standard_normal(snap.size))
        cur = price[walked]
        target = base[walked] * factor[region_of_col[walked]]
        step = rev * (np.log(target) - np.log(cur)) \
            + vol * rng.standard_normal(walked.size)
        new_walk = cur * np.exp(step)
        cols = np.concatenate([snap, walked])
        new = np.clip(np.concatenate([new_snap, new_walk]), lo[cols],
                      hi[cols])
        price[cols] = new
        out.append((cols.astype(np.int32), new))
    return out
