"""The plain reference of a store that grows while it serves: numpy
float64 over the generated data, epoch by epoch.

Independent of the program: it reads only what ``gen.deployment`` and
``gen.records`` made from the seed, and ``reference.py``'s formulas and
error measures.  The store starts with each job's initial shapes; tick
``t`` writes its records (``per_tick`` of the pool, replayed
cyclically) and then applies price batch ``t``, so price epoch ``e``
holds the records and prices of the ticks before ``e``.  One shape's
runtime fills every column of that shape, so the store is kept per
shape (NaN where a job has not run the shape) and widened to the
catalog's columns where a ranking needs them.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

import reference as refmod


class IngestReference:
    def __init__(self, dep, start: np.ndarray, records: Sequence,
                 per_tick: int, batches):
        self.dep = dep
        self.records = records
        self.per_tick = per_tick
        self.batches = batches
        self.shape_of_col = dep.shape_of_col
        h0 = np.full(dep.shape_hours.shape, np.nan)
        rows = np.arange(dep.n_jobs)[:, None]
        h0[rows, start] = dep.shape_hours[rows, start]
        self.h0 = h0
        self._rows: Dict[int, np.ndarray] = {}

    def tick_records(self, t: int) -> List:
        n = len(self.records)
        return [self.records[i % n] for i in range(t * self.per_tick,
                                                   (t + 1) * self.per_tick)]

    def walk(self, epochs: Sequence[int], before_prices: bool = False
             ) -> Iterator[Tuple[int, np.ndarray, np.ndarray]]:
        """``(epoch, prices, shape hours)`` for each epoch, ascending: the
        state after the ticks before it.  With ``before_prices`` each
        epoch ``e >= 1`` is instead yielded as tick ``e - 1`` starts:
        the state its ingest meets, before its records and prices.  The
        arrays are reused: copy them to keep them."""
        prices = self.dep.base_prices.copy()
        hours = self.h0.copy()
        pool = len(self.batches)
        done = 0
        for e in sorted(set(epochs)):
            last = e - 1 if before_prices else e
            while done < last:
                for r in self.tick_records(done):
                    hours[r.job, r.shape] = r.hours
                cols, new = self.batches[done % pool]
                prices[cols] = new
                done += 1
            yield e, prices, hours

    def rows(self, route: int) -> np.ndarray:
        r = self._rows.get(route)
        if r is None:
            r = self._rows[route] = self.dep.rows_of(self.dep.routes[route])
        return r

    def norm(self, prices: np.ndarray, hours: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray]:
        """(J, C) normalised cost of every cell and the profiled mask."""
        cost = hours[:, self.shape_of_col] * prices[None, :]
        mask = ~np.isnan(cost)
        cost = np.where(mask, cost, np.inf)
        best = cost.min(axis=1, keepdims=True)
        return np.where(mask, cost / best, 0.0), mask

    def scores(self, norm: np.ndarray, mask: np.ndarray,
               route: int) -> np.ndarray:
        """(C,) float64 scores of one route, +inf where unprofiled."""
        rows = self.rows(route)
        n = mask[rows].sum(axis=0)
        return np.where(n > 0, norm[rows].sum(axis=0), np.inf)

    def tick_changes(self, epochs: Sequence[int]
                     ) -> Dict[int, List[Tuple[int, np.ndarray,
                                               np.ndarray]]]:
        """For the ingest that made each epoch's tick: per touched job
        row, ``(row, written columns, renormalised columns)`` -- the
        columns whose normalised cost changed: the written ones whose
        runtime changed, or every profiled column where the row's
        cheapest cost moved."""
        out = {}
        for e, prices, hours in self.walk([e for e in epochs if e >= 1],
                                          before_prices=True):
            recs = self.tick_records(e - 1)
            touched = sorted({r.job for r in recs})
            before = {j: hours[j].copy() for j in touched}
            # the walk stopped ahead of tick e - 1: apply its records on
            # a copy, since the walk's own array moves on
            after = {j: hours[j].copy() for j in touched}
            for r in recs:
                after[r.job][r.shape] = r.hours
            changes = []
            for j in touched:
                old = before[j][self.shape_of_col] * prices
                new = after[j][self.shape_of_col] * prices
                written = np.flatnonzero(np.isin(
                    self.shape_of_col, [r.shape for r in recs
                                        if r.job == j]))
                moved = np.nanmin(old) != np.nanmin(new)
                if moved:
                    renormed = np.flatnonzero(~np.isnan(new))
                else:
                    renormed = written[old[written] != new[written]]
                changes.append((j, written, renormed))
            out[e] = changes
        return out


def served_numbers(ref: IngestReference, items, k: int
                   ) -> Dict[str, float]:
    """What ``selector_frontend.served_numbers`` gives, against the
    store of each item's epoch."""
    score_err = rank_err = 0.0
    mismatches = 0
    by_epoch: Dict[int, list] = {}
    for it in items:
        by_epoch.setdefault(it[0], []).append(it)
    for epoch, prices, hours in ref.walk(list(by_epoch)):
        norm, mask = ref.norm(prices, hours)
        cache: Dict[int, np.ndarray] = {}
        for _, route, served, cost in by_epoch[epoch]:
            want = cache.get(route)
            if want is None:
                want = cache[route] = ref.scores(norm, mask, route)
            s, r = refmod.head_errors(served, want, k)
            score_err, rank_err = max(score_err, s), max(rank_err, r)
            if cost != prices[served[0][0]]:
                mismatches += 1
    return {"head_score_err": score_err, "head_rank_err": rank_err,
            "cost_mismatches": mismatches}


def fleet_number(ref: IngestReference, fleet: Dict[int, np.ndarray],
                 epoch: int) -> float:
    worst = 0.0
    for _, prices, hours in ref.walk([epoch]):
        norm, mask = ref.norm(prices, hours)
        for route, got in fleet.items():
            worst = max(worst, float(refmod.rel_err(
                got, ref.scores(norm, mask, route)).max()))
    return worst
