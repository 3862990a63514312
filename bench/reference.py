"""The plain reference: numpy float64 ranking of the generated data.

Independent of the program: it reads only what ``gen.deployment`` made
from the seed.  A job's cost on a configuration is its runtime times the
configuration's price; each job's costs are normalised by that job's
cheapest profiled configuration; a route's score for a configuration is
the sum of the normalised costs of the route's jobs profiled there, and
a configuration none of them profiled scores +inf.  Lower is better, and
ties go to the earlier catalog position (Flora, arXiv 2502.21046, §II).
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np


class Reference:
    def __init__(self, dep):
        self.n_cfgs = dep.n_cfgs
        self.cols = dep.profiled                      # (J, P) int32
        self.hours = dep.hours_of()                   # (J, P) float64
        self._rows: Dict[int, np.ndarray] = {}
        self._dep = dep

    def rows(self, route: int) -> np.ndarray:
        r = self._rows.get(route)
        if r is None:
            r = self._rows[route] = self._dep.rows_of(self._dep.routes[route])
        return r

    def norm(self, prices: np.ndarray) -> np.ndarray:
        """(J, P) normalised cost of every profiled cell."""
        cost = self.hours * prices[self.cols]
        return cost / cost.min(axis=1, keepdims=True)

    def scores(self, norm: np.ndarray, route: int) -> np.ndarray:
        """(C,) float64 scores of one route, +inf where unprofiled."""
        rows = self.rows(route)
        cols = self.cols[rows].ravel()
        s = np.bincount(cols, weights=norm[rows].ravel(),
                        minlength=self.n_cfgs)
        n = np.bincount(cols, minlength=self.n_cfgs)
        return np.where(n > 0, s, np.inf)


def head(scores: np.ndarray, k: int) -> np.ndarray:
    """Column positions of the ``k`` best scores, ties by position."""
    idx = np.argpartition(scores, k - 1)[:k]
    kth = scores[idx].max()
    cand = np.flatnonzero(scores <= kth)
    return cand[np.lexsort((cand, scores[cand]))][:k]


class PriceHistory:
    """Prices after each applied batch: batch ``(n - 1) % len(batches)``
    is the last one applied at price epoch ``n`` (epoch 0 is the base
    prices).  The feed replays its pool of batches cyclically; a batch
    holds absolute prices, so a replayed one sets the same quotes."""

    def __init__(self, base: np.ndarray,
                 batches: Sequence[Tuple[np.ndarray, np.ndarray]]):
        self.base = base
        self.batches = batches

    def walk(self, epochs: Sequence[int]) -> Iterator[Tuple[int, np.ndarray]]:
        """(epoch, prices) for each epoch, in ascending order; the yielded
        array is reused, so copy it to keep it."""
        prices = self.base.copy()
        applied = 0
        pool = len(self.batches)
        for e in sorted(set(epochs)):
            while applied < e:
                cols, new = self.batches[applied % pool]
                prices[cols] = new
                applied += 1
            yield e, prices


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """|got - want| / |want|, 0 where both are +inf, +inf where only one
    is (an unprofiled configuration scored, or a profiled one dropped)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    both_inf = np.isinf(got) & np.isinf(want)
    with np.errstate(invalid="ignore"):
        err = np.abs(got - want) / np.abs(want)
    err = np.where(both_inf, 0.0, err)
    return np.where(np.isinf(got) ^ np.isinf(want), np.inf, err)


def head_errors(served: List[Tuple[int, float]], want: np.ndarray,
                k: int) -> Tuple[float, float]:
    """A served head [(column, score)] against reference scores: the worst
    relative error of a served score against its configuration's reference
    score, and of each served entry's reference score against the
    reference's own entry at that rank (near-ties may swap, a wrong pick
    may not)."""
    best = want[head(want, k)]
    cols = np.asarray([c for c, _ in served], dtype=np.int64)
    got = np.asarray([s for _, s in served], dtype=np.float64)
    if cols.size != min(k, want.size):
        return float("inf"), float("inf")
    score_err = float(rel_err(got, want[cols]).max())
    rank_err = float(rel_err(want[cols], best).max())
    return score_err, rank_err
