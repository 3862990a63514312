"""Vectorized normalized-cost ranking (paper §II, step 2).

The ranking is one matrix computation instead of a per-pair dict loop:

    cost   = runtime_hours (J x C)  *  price_vector (C,)     # broadcast
    norm   = cost / row-min(cost over profiled cells)        # row-normalize
    score  = column-sum of norm over profiled cells          # per config

A config with **zero** profiled cells scores ``+inf`` and therefore ranks
last (an unprofiled config must never win by default — the historical dict
loop left it at 0.0, i.e. argmin).

Backends:

  * ``"numpy"`` (default): float64, bit-stable with the historical
    per-pair arithmetic — the paper-table reproductions and the
    reference every journal audit re-ranks against;
  * ``"jax_batched"``, ``"jax_sharded"``, ``"jax_pallas"``: the fleet
    backends (float32, on the device).

Each backend carries an explicit :class:`ScoreContract` (DESIGN.md §9):
numpy guarantees bit-identity between the incremental and cold paths;
the float32 fleets guarantee the same winner (or a winner tied within
tolerance) with scores inside a rel/abs envelope.  Incremental repricing
lives in :class:`RankState` (numpy) and in the fleet states:
:class:`BatchedRankState` stacks a whole fleet of (class, exclusion)
rankings over one shared device-resident hours matrix, so a price tick
is a *single* dispatch for every live ranking (DESIGN.md §10); the
``"jax_batched"`` backend name selects it at the service level.
``"jax_sharded"`` (:mod:`repro.selector.sharded`) shards that batched
universe's config axis across every local device, so one *collective*
dispatch per tick reprices the fleet at catalogs no single device holds
(DESIGN.md §13).  ``"jax_pallas"``
(:mod:`repro.selector.pallas_rank`) replaces the batched tick's
two-matmul + mask/min/norm XLA sequence with ONE fused Pallas kernel
over the tiled universe (:mod:`repro.kernels.rank_delta`, DESIGN.md
§14).  A cold :func:`rank_dense` on any jax-family name is one jitted
``jax.numpy`` ranking.  Every state also serves :meth:`top_k` — the
head of the ranking without materializing and sorting all C configs
(``jax.lax.top_k`` on device for the fleets, a partial selection on
numpy).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import (Any, Callable, Hashable, List, Mapping, Optional,
                    Sequence, Tuple, Union)

import numpy as np

from repro.obs import MetricsRegistry, maybe_span

try:  # accelerator path; the selector core works without jax installed
    import jax
    import jax.numpy as jnp
    _HAVE_JAX = True
except ImportError:  # pragma: no cover
    _HAVE_JAX = False

#: what a :class:`~repro.selector.SelectionService` can be built with.
#: ``"numpy"`` keeps one float64 :class:`RankState` per live (class,
#: exclusion) ranking.  ``"jax_batched"`` stacks every live ranking into
#: one :class:`BatchedRankState` — one dispatch per tick for the fleet.
#: ``"jax_sharded"`` additionally shards the config axis of that fleet
#: universe across every local device
#: (:class:`~repro.selector.sharded.ShardedBatchedRankState`) — one
#: *collective* dispatch per tick for catalogs too large for one
#: device (DESIGN.md §13).  ``"jax_pallas"``
#: (:class:`~repro.selector.pallas_rank.PallasBatchedRankState`) runs
#: the batched tick as ONE fused Pallas kernel
#: (:mod:`repro.kernels.rank_delta`) instead of the two-matmul +
#: mask/min/norm XLA sequence — native on TPU, ``interpret=True``
#: elsewhere (DESIGN.md §14).
BACKENDS = ("numpy", "jax_batched", "jax_sharded", "jax_pallas")
#: the fleet backends: a SelectionService on one of these stacks every
#: live (class, exclusion) ranking into a single shared state, so a
#: price tick is one (possibly collective) kernel dispatch fleet-wide.
FLEET_BACKENDS = ("jax_batched", "jax_sharded", "jax_pallas")
#: span names of a device ``top_k``: the enqueue of the jitted call
#: (per head on the sharded state, with its slot lookup and slices;
#: on :class:`BatchedRankState` once over every slot per
#: state change whose heads the reprice did not already take) and the
#: blocking readback of its two results; the host assembly of a head
#: lies outside both
TOPK_DISPATCH_SPAN = "topk.dispatch"
TOPK_READBACK_SPAN = "topk.readback"
#: span name of a fleet state's profile-ingest enqueue (host preparation
#: outside it, no sync inside it)
INGEST_DISPATCH_SPAN = "ingest.dispatch"
#: backends whose runtime dependency is jax.
_JAX_FAMILY = ("jax", "jax_batched", "jax_sharded", "jax_pallas")


class BackendUnavailableError(RuntimeError):
    """A ranking backend was requested whose runtime dependency is not
    installed (today: a jax-family backend without jax).  Typed so callers —
    and test harnesses — can skip rather than die: distinguishable from
    both misconfiguration ``ValueError``\\ s (unknown backend names) and
    genuine crashes."""


@dataclasses.dataclass(frozen=True)
class ScoreContract:
    """What a backend promises about incremental-vs-cold score equality.

    * numpy/float64: **bit-identical** — the incremental
      :class:`RankState` recomputes updated cells with the cold path's
      exact elementwise arithmetic and re-reduces scores with the same
      full ``norm.sum(axis=0)``, so any reprice sequence equals a cold
      ``rank_dense`` down to the last ulp (``rel_tol == abs_tol == 0``).
    * jax/float32: **same-winner-or-tied within tolerance** — float32
      has no bit-identity story for delta updates (DESIGN.md §9): the
      jitted kernel folds per-tick deltas into standing score
      accumulators, so scores drift by ulps per tick, and two configs
      whose true scores are closer than the drift may swap.  The
      contract is that every score lies within ``rel_tol``/``abs_tol``
      of the cold value and the reported winner is either identical to
      the cold winner or tied with it within the same envelope.
    """

    backend: str
    bit_identical: bool
    rel_tol: float = 0.0
    abs_tol: float = 0.0

    def scores_match(self, a: float, b: float) -> bool:
        """Are two scores equal under this contract?  (``inf == inf``
        counts: unprofiled configs score ``+inf`` on every backend.)"""
        if a == b:
            return True
        if self.bit_identical:
            return False
        return abs(a - b) <= self.abs_tol + self.rel_tol * max(abs(a),
                                                               abs(b))

    def winner_matches(self, config_id: Hashable,
                       ranking: Sequence["RankedConfig"]) -> bool:
        """Is ``config_id`` an acceptable winner against a cold
        ``ranking``?  Identical to the cold winner always qualifies; a
        tolerance backend also accepts a config whose *cold* score ties
        the cold winner's within the contract (float32 drift can swap
        near-ties, never separated configs)."""
        if not ranking:
            return False
        if config_id == ranking[0].config_id:
            return True
        if self.bit_identical:
            return False
        for r in ranking:
            if r.config_id == config_id:
                return self.scores_match(r.score, ranking[0].score)
        return False


#: Per-backend contracts.  The jax tolerances cover float32 rounding of
#: the inputs (~1e-7 relative) plus delta-accumulation drift across
#: ticks, with two orders of magnitude of headroom (DESIGN.md §9).
SCORE_CONTRACTS: Mapping[str, ScoreContract] = {
    "numpy": ScoreContract("numpy", bit_identical=True),
    # the stamp of journals from the retired per-state writer (replay only)
    "jax": ScoreContract("jax", bit_identical=False,
                         rel_tol=1e-4, abs_tol=1e-6),
    # the float32 fleet: row-min/norm intermediates shared by every
    # member, changed columns re-reduced from scratch and handed-off rows
    # delta-folded into the accumulators (DESIGN.md §9-§10).
    "jax_batched": ScoreContract("jax_batched", bit_identical=False,
                                 rel_tol=1e-4, abs_tol=1e-6),
    # sharding the C axis changes *where* each column's arithmetic runs,
    # not the arithmetic: per-shard row minima combine through
    # `lax.pmin` (exact on floats), and every norm/score term is the
    # same float32 expression as "jax_batched", so the envelope is
    # again identical (DESIGN.md §13).
    "jax_sharded": ScoreContract("jax_sharded", bit_identical=False,
                                 rel_tol=1e-4, abs_tol=1e-6),
    # the fused Pallas kernel recomputes cost/norm in-stream from the
    # same float32 elementwise expressions (deterministic IEEE ops ->
    # bit-identical cells), re-reduces changed columns from scratch and
    # delta-folds handoff rows exactly like the XLA step — only matmul
    # reduction *order* differs, which the shared rel/abs envelope
    # already covers, so journals and tolerance-mode audits carry over
    # unchanged (DESIGN.md §14).
    "jax_pallas": ScoreContract("jax_pallas", bit_identical=False,
                                rel_tol=1e-4, abs_tol=1e-6),
}


def backend_available(backend: str) -> bool:
    """Can ``backend`` actually run here?  ``"numpy"`` always; the
    jax-family backends (the fleets, and ``"jax"``, which a cold
    :func:`rank_dense` and old journals still name) only when jax
    imports.  Unknown names are *not* an error from this predicate
    (they fail later with ``ValueError`` at dispatch)."""
    return backend not in _JAX_FAMILY or _HAVE_JAX


def score_contract(backend: str) -> ScoreContract:
    """The :class:`ScoreContract` for ``backend`` (raises on unknown)."""
    try:
        return SCORE_CONTRACTS[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r} "
                         f"(expected one of {BACKENDS})")


class NothingRankableError(ValueError):
    """The selection has no rankable universe — an empty job selection or
    an entirely-unprofiled catalog.  A routine per-submission outcome
    (e.g. an exclusion set that empties a class), distinct from the other
    ``ValueError``\\ s raised here, which indicate misconfiguration (shape
    mismatches, missing price sources, broken traces) and should never be
    swallowed as a rejection."""


@dataclasses.dataclass(frozen=True)
class RankedConfig:
    config_id: Hashable
    score: float           # sum of normalized costs; lower is better
    mean_norm_cost: float  # score / number of contributing test jobs


def _canonicalize_universe(
        hours: np.ndarray, mask: np.ndarray, prices: np.ndarray,
        job_ids: Optional[Sequence[Hashable]]
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Shared input validation for every dense entry point
    (:func:`rank_dense`, :class:`RankState`, the fleet states):
    canonicalize dtypes, check shapes, reject empty job axes and
    non-positive profiled costs (both indicate a broken trace, not a
    rankable universe)."""
    hours = np.asarray(hours, dtype=np.float64)
    mask = np.asarray(mask, dtype=bool)
    prices = np.asarray(prices, dtype=np.float64)
    if hours.shape != mask.shape or hours.shape[1] != prices.shape[0]:
        raise ValueError(f"shape mismatch: hours {hours.shape}, "
                         f"mask {mask.shape}, prices {prices.shape}")
    if hours.shape[0] == 0:
        raise NothingRankableError("no test jobs to learn from")
    bad = mask & ~((hours * prices[None, :]) > 0)
    if bad.any():
        row = int(np.argwhere(bad)[0][0])
        job = job_ids[row] if job_ids is not None else row
        raise ValueError(f"non-positive cost for job {job!r}")
    return hours, mask, prices


def _position_index(config_ids: Sequence[Hashable]
                    ) -> "dict[Hashable, int]":
    """Config id -> column position; rejects duplicates (the states key
    reprice deltas on it, so a duplicate would silently alias columns)."""
    pos = {c: i for i, c in enumerate(config_ids)}
    if len(pos) != len(config_ids):
        raise ValueError("duplicate config ids")
    return pos


def _scores_numpy(hours: np.ndarray, mask: np.ndarray, prices: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    cost = np.where(mask, hours * prices[None, :], np.inf)
    row_best = np.min(cost, axis=1, initial=np.inf)
    with np.errstate(invalid="ignore"):
        norm = np.where(mask, cost / row_best[:, None], 0.0)
    return norm.sum(axis=0), mask.sum(axis=0)


def _materialize(scores: np.ndarray, counts: np.ndarray,
                 config_ids: Sequence[Hashable]) -> List[RankedConfig]:
    """Scores/counts -> sorted RankedConfig list (shared by the cold and
    incremental paths so their rankings are identical by construction)."""
    ranked = [
        RankedConfig(
            c,
            float(scores[i]) if counts[i] else float("inf"),
            float(scores[i] / counts[i]) if counts[i] else float("inf"))
        for i, c in enumerate(config_ids)]
    order = {c: i for i, c in enumerate(config_ids)}
    ranked.sort(key=lambda r: (r.score, order[r.config_id]))
    return ranked


def _check_k(k: int, n_cfgs: int) -> int:
    """Validate a top-k depth; clamps to the universe size (asking for
    more head than exists is a serving convenience, not an error)."""
    if not isinstance(k, int) or isinstance(k, bool) or k < 1:
        raise ValueError(f"top_k needs a positive integer k, got {k!r}")
    return min(k, n_cfgs)


def _top_k_numpy(scores: np.ndarray, counts: np.ndarray,
                 config_ids: Sequence[Hashable], k: int
                 ) -> List[RankedConfig]:
    """The head of :func:`_materialize`'s ranking without building and
    sorting all C ``RankedConfig``\\ s: partial-select the k best scores,
    then order only the boundary candidates by the same (score, catalog
    position) key — element-wise identical to ``_materialize(...)[:k]``
    by construction, ties included."""
    k = _check_k(k, len(config_ids))
    eff = np.where(counts > 0, scores, np.inf)
    kth = np.partition(eff, k - 1)[k - 1]
    # every config strictly better than the k-th plus the whole tie at
    # the boundary: ordering those few by (score, position) reproduces
    # the full sort's head even when the boundary is a multi-way tie
    cand = np.flatnonzero(eff <= kth)
    cand = cand[np.lexsort((cand, eff[cand]))][:k]
    return [
        RankedConfig(
            config_ids[i],
            float(scores[i]) if counts[i] else float("inf"),
            float(scores[i] / counts[i]) if counts[i] else float("inf"))
        for i in cand]


if _HAVE_JAX:
    @jax.jit
    def _scores_jax(hours, mask, prices):
        cost = jnp.where(mask, hours * prices[None, :], jnp.inf)
        row_best = jnp.min(cost, axis=1)
        norm = jnp.where(mask, cost / row_best[:, None], 0.0)
        return norm.sum(axis=0), mask.sum(axis=0)


def rank_dense(hours: np.ndarray, mask: np.ndarray, prices: np.ndarray,
               config_ids: Sequence[Hashable],
               job_ids: Optional[Sequence[Hashable]] = None,
               backend: str = "numpy") -> List[RankedConfig]:
    """Rank configs from dense (J x C) runtime-hours + profiled-mask.

    ``prices`` is the current $/h per config, aligned with ``config_ids``.
    Raises on an empty job axis and on non-positive profiled costs (both
    indicate a broken trace, not a rankable universe).
    """
    hours, mask, prices = _canonicalize_universe(hours, mask, prices,
                                                 job_ids)
    if backend in _JAX_FAMILY:
        # batching/sharding is a *serving* distinction (how live states
        # share a tick dispatch); a cold full rank is the same fused
        # kernel
        if not _HAVE_JAX:
            raise BackendUnavailableError(
                f"backend={backend!r} requested but jax is not installed "
                "(the numpy backend needs no extras)")
        scores, counts = (np.asarray(x) for x in _scores_jax(
            jnp.asarray(hours), jnp.asarray(mask), jnp.asarray(prices)))
    elif backend == "numpy":
        scores, counts = _scores_numpy(hours, mask, prices)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return _materialize(scores, counts, config_ids)


def rank_pairs(
    runtime_hours: Mapping[Tuple[Hashable, Hashable], float],
    jobs: Sequence[Hashable],
    config_ids: Sequence[Hashable],
    hourly_cost: Union[Callable[[Hashable], float], Mapping[Hashable, float]],
    backend: str = "numpy",
) -> List[RankedConfig]:
    """Rank from sparse ``{(job, config): hours}`` pairs (legacy shape).

    Densifies and dispatches to :func:`rank_dense`; kept so existing
    callers of ``repro.core.flora.rank_generic`` keep one code path.
    """
    if not jobs:
        raise NothingRankableError("no test jobs to learn from")
    price_of = hourly_cost if callable(hourly_cost) else hourly_cost.__getitem__
    hours = np.zeros((len(jobs), len(config_ids)))
    mask = np.zeros_like(hours, dtype=bool)
    for r, j in enumerate(jobs):
        for k, c in enumerate(config_ids):
            v = runtime_hours.get((j, c))
            if v is not None:
                hours[r, k] = v
                mask[r, k] = True
    prices = np.asarray([price_of(c) for c in config_ids], dtype=np.float64)
    return rank_dense(hours, mask, prices, config_ids, job_ids=list(jobs),
                      backend=backend)


class RankState:
    """Incremental repricing over a fixed (job x config) runtime matrix.

    The live-market path (DESIGN.md §6): when only k of C prices move in a
    tick, a full :func:`rank_dense` recomputes every intermediate from
    scratch — cost broadcast, row-min, normalize, sum, plus building and
    sorting C ``RankedConfig`` objects.  ``RankState`` instead keeps the
    dense intermediates (cost, row-min, normalized-cost matrices) alive and
    on :meth:`reprice` touches only

      * the k changed cost/norm columns, and
      * the rows whose masked row-minimum was or becomes a changed column
        (every cell of those rows renormalizes).

    **Bit-identity contract**: scores after any ``reprice`` sequence are
    bit-identical to a cold ``rank_dense`` at the same prices.  Updated
    cells are recomputed with the exact elementwise arithmetic of the cold
    path, and scores are reduced with the same full ``norm.sum(axis=0)``
    (numpy's pairwise summation is *not* decomposable, so per-column delta
    updates would drift by ulps — the one full pass over the norm matrix is
    the price of exactness, and it is still ~100x cheaper than the cold
    path at 10k configs; see ``benchmarks/market_bench.py``).

    numpy/float64 only — float32 has no exact incremental story, so the
    accelerator-resident fleet states (:class:`BatchedRankState` and its
    siblings) serve a *tolerance* contract instead (same winner or tied
    within tolerance; see :class:`ScoreContract` and DESIGN.md §9).
    """

    def __init__(self, hours: np.ndarray, mask: np.ndarray,
                 prices: np.ndarray, config_ids: Sequence[Hashable],
                 job_ids: Optional[Sequence[Hashable]] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.config_ids = list(config_ids)
        self.job_ids = list(job_ids) if job_ids is not None else None
        # optional shared telemetry (DESIGN.md §12): host materializations
        # tick an aggregate counter on the injected registry; the plain
        # per-state ``materializations`` int stays authoritative for the
        # freshness tests.
        self._c_mat = (None if metrics is None
                       else metrics.counter("rank.materializations"))
        self.hours, self.mask, self.prices = _canonicalize_universe(
            hours, mask, prices, self.job_ids)
        self.prices = self.prices.copy()        # mutated by reprice
        self._pos = _position_index(self.config_ids)
        #: ticks applied since construction (diagnostics, cache keys).
        self.reprices = 0
        #: full-ranking sorts actually performed (the memoization
        #: counter the freshness tests assert on).
        self.materializations = 0
        self._ranking_memo: Optional[Tuple[int, List[RankedConfig]]] = None
        self._rebuild()

    def _check_positive(self, mask: np.ndarray, cost: np.ndarray) -> None:
        bad = mask & ~(cost > 0)
        if bad.any():
            row = int(np.argwhere(bad)[0][0])
            job = self.job_ids[row] if self.job_ids is not None else row
            raise ValueError(f"non-positive cost for job {job!r}")

    def _rebuild(self) -> None:
        # the cold-path arithmetic, verbatim (bit-identity anchor)
        self.cost = np.where(self.mask, self.hours * self.prices[None, :],
                             np.inf)
        self.row_best = np.min(self.cost, axis=1, initial=np.inf)
        with np.errstate(invalid="ignore"):
            self.norm = np.where(self.mask,
                                 self.cost / self.row_best[:, None], 0.0)
        self.scores = self.norm.sum(axis=0)
        self.counts = self.mask.sum(axis=0)

    def reprice(self, deltas: Union[Mapping[Hashable, float],
                                    Sequence[Tuple[Hashable, float]]]) -> int:
        """Apply ``{config_id: new $/h}`` deltas; returns #rows whose
        masked row-minimum moved (the expensive case)."""
        table = deltas if isinstance(deltas, Mapping) else dict(deltas)
        if not table:
            return 0
        try:
            cols = np.asarray([self._pos[c] for c in table], dtype=np.intp)
        except KeyError as e:
            raise ValueError(f"unknown config id in deltas: {e.args[0]!r}")
        new_prices = np.asarray(list(table.values()), dtype=np.float64)
        # same elementwise ops as the cold broadcast -> bit-identical cells
        new_cost = np.where(self.mask[:, cols],
                            self.hours[:, cols] * new_prices[None, :],
                            np.inf)
        self._check_positive(self.mask[:, cols], new_cost)
        old_cost = self.cost[:, cols]
        self.prices[cols] = new_prices
        self.cost[:, cols] = new_cost
        # rows whose masked minimum was in a changed column, or where a
        # changed column undercuts the old minimum, need a fresh row-min
        was_min = old_cost.min(axis=1, initial=np.inf) == self.row_best
        undercut = new_cost.min(axis=1, initial=np.inf) < self.row_best
        candidates = np.flatnonzero(was_min | undercut)
        moved = np.array([], dtype=np.intp)
        if candidates.size:
            fresh = np.min(self.cost[candidates, :], axis=1, initial=np.inf)
            changed = fresh != self.row_best[candidates]
            moved = candidates[changed]
            self.row_best[moved] = fresh[changed]
        with np.errstate(invalid="ignore"):
            self.norm[:, cols] = np.where(
                self.mask[:, cols],
                self.cost[:, cols] / self.row_best[:, None], 0.0)
            if moved.size:
                self.norm[moved, :] = np.where(
                    self.mask[moved, :],
                    self.cost[moved, :] / self.row_best[moved, None], 0.0)
        # full-matrix reduction, identical to the cold path (see docstring)
        self.scores = self.norm.sum(axis=0)
        self.reprices += 1
        return int(moved.size)

    def ranking(self) -> List[RankedConfig]:
        """The full sorted ranking (bit-identical to ``rank_dense``),
        memoized on the state's tick count: repeat calls between two
        reprices reuse the last sort instead of re-materializing all C
        ``RankedConfig``\\ s (a fresh list copy is returned each call, so
        callers may not corrupt the memo)."""
        if self._ranking_memo is None or \
                self._ranking_memo[0] != self.reprices:
            self.materializations += 1
            if self._c_mat is not None:
                self._c_mat.inc()
            self._ranking_memo = (
                self.reprices,
                _materialize(self.scores, self.counts, self.config_ids))
        return list(self._ranking_memo[1])

    def top_k(self, k: int) -> List[RankedConfig]:
        """The first ``k`` entries of :meth:`ranking` without building
        and sorting all C configs — a partial selection over the score
        vector, element-wise identical to ``ranking()[:k]`` (same
        (score, catalog-order) tie-break)."""
        return _top_k_numpy(self.scores, self.counts, self.config_ids, k)

    def winner(self) -> RankedConfig:
        """argmin only — O(C), no list build/sort.  A cheap peek for
        callers that only need the top pick; the serving path proper goes
        through :meth:`ranking`, since a ``Decision`` always carries the
        full sorted list."""
        finite = self.counts > 0
        if not finite.any():
            i = 0
        else:
            masked = np.where(finite, self.scores, np.inf)
            i = int(np.argmin(masked))
        c = self.config_ids[i]
        s = float(self.scores[i]) if self.counts[i] else float("inf")
        m = float(self.scores[i] / self.counts[i]) if self.counts[i] \
            else float("inf")
        return RankedConfig(c, s, m)


# --- the accelerator-resident incremental path (fleet backends) -----------------

def _validated_deltas(pos: Mapping[Hashable, int],
                      deltas: Union[Mapping[Hashable, float],
                                    Sequence[Tuple[Hashable, float]]]
                      ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Validate a delta batch for the jitted jax states: resolve config
    ids to column positions and reject non-positive / non-finite prices.
    Returns unpadded ``(cols, new_prices)`` or ``None`` for an empty
    batch.  (Bucket padding is the caller's concern — the single-device
    states pad the whole batch, the sharded state routes columns to
    their owning shard first and pads per shard.)"""
    table = deltas if isinstance(deltas, Mapping) else dict(deltas)
    if not table:
        return None
    try:
        cols = np.asarray([pos[c] for c in table], dtype=np.int32)
    except KeyError as e:
        raise ValueError(f"unknown config id in deltas: {e.args[0]!r}")
    new_prices = np.asarray(list(table.values()), dtype=np.float64)
    bad = ~(np.isfinite(new_prices) & (new_prices > 0))
    if bad.any():
        offender = list(table)[int(np.flatnonzero(bad)[0])]
        raise ValueError(f"non-positive or non-finite price for "
                         f"config {offender!r}")
    return cols, new_prices


def _bucket_size(n: int, bucket_base: int) -> int:
    """Next power-of-4 bucket >= ``n`` (starting at ``bucket_base``), so
    the jitted steps compile O(log C) shape variants."""
    bucket = bucket_base
    while bucket < n:
        bucket *= 4
    return bucket


def _validated_delta_cols(pos: Mapping[Hashable, int],
                          deltas: Union[Mapping[Hashable, float],
                                        Sequence[Tuple[Hashable, float]]],
                          bucket_base: int) -> Optional[np.ndarray]:
    """Delta-batch preparation for :class:`BatchedRankState`'s jitted
    step: validate ids and prices (:func:`_validated_deltas`), pad the
    batch to the next power-of-4 column-count bucket so the jitted step
    compiles O(log C) shape variants, and pack it into the step's one
    host operand: an ``int32`` array of length 2·bucket holding the
    columns, then the bits of the prices rounded to float32 (the
    rounding the device's convert gives; :func:`_unpack_delta` splits
    it).  Padding repeats the first (column, price) pair, which every
    kernel op treats idempotently.  Returns ``None`` for an empty
    batch."""
    validated = _validated_deltas(pos, deltas)
    if validated is None:
        return None
    cols, new_prices = validated
    k = cols.shape[0]
    bucket = _bucket_size(k, bucket_base)
    delta = np.empty(2 * bucket, dtype=np.int32)
    prices = delta[bucket:].view(np.float32)
    delta[:bucket], prices[:] = cols[0], new_prices[0]
    delta[:k], prices[:k] = cols, new_prices
    return delta


def _unpacked_heads(out: np.ndarray, k: int
                    ) -> Tuple[int, np.ndarray, np.ndarray]:
    """The host half of :func:`_pack_heads`: ``(moved, idx, vals)``
    from the one ``int32`` readback of a reprice that took the heads,
    ``idx`` and ``vals`` of shape (capacity, k), ``vals`` lifted to
    float64."""
    n = (out.shape[0] - 1) // 2
    return (int(out[0]), out[1:1 + n].reshape(-1, k),
            out[1 + n:].view(np.float32).reshape(-1, k).astype(np.float64))


if _HAVE_JAX:
    _JAX_COLD_FN: Optional[Any] = None
    _JAX_TOPK_FN: Optional[Any] = None
    #: guards every lazy jitted-kernel singleton below (double-checked
    #: locking): the serving front-end first-calls from N snapshot
    #: workers plus the tick thread concurrently, and an unlocked
    #: check-then-build can build twice and interleave partially-
    #: initialized reads (regression-stressed in tests/test_kernels.py)
    _JAX_FNS_LOCK = threading.Lock()

    def _cost_of(hours, mask, prices):
        """Masked cost: runtime times price, ``+inf`` where unprofiled.
        With :func:`_norm_of`, the one float32 expression every jitted
        step (cold, price delta, profile ingest) computes a cell with."""
        return jnp.where(mask, hours * prices, jnp.inf)

    def _norm_of(cost, mask, row_best):
        """Normalised cost at the rows' minima, 0 where unprofiled."""
        return jnp.where(mask, cost / row_best[:, None], 0.0)

    def _delta_universe_update(prices, cost, row_best, hours, mask,
                               cols, new_prices):
        """The universe half of the batched delta step (the sharded
        step's local half mirrors it, ``sharded.py``):

        * changed columns: gather, recompute cells, scatter back;
        * min-handoff rows: the masked row-minimum was in a changed
          column, or a changed column undercuts it — those rows get a
          fresh minimum;
        * ``fresh_rows`` renormalizes the whole matrix at the new
          minima (consumers select only the ``moved`` rows from it);
        * ``col_norm`` re-derives the changed columns' normalized
          costs, idempotent under the duplicate indices the power-of-4
          bucket padding introduces.
        """
        sub_mask = mask[:, cols]
        new_cost = _cost_of(hours[:, cols], sub_mask, new_prices[None, :])
        old_cost = cost[:, cols]
        prices = prices.at[cols].set(new_prices)
        cost = cost.at[:, cols].set(new_cost)
        was_min = old_cost.min(axis=1) == row_best
        undercut = new_cost.min(axis=1) < row_best
        fresh = jnp.where(was_min | undercut, cost.min(axis=1),
                          row_best)
        moved = fresh != row_best
        row_best = fresh
        fresh_rows = _norm_of(cost, mask, row_best)
        col_norm = _norm_of(cost[:, cols], sub_mask, row_best)
        return prices, cost, row_best, fresh_rows, moved, col_norm

    def _unpack_cells(idx, vals):
        """An ingest's operands, packed host-side into one int32 and one
        float32 array (two transfers, not five): ``idx`` holds the
        cells' rows, their columns and the touched rows, ``vals`` the
        cells' runtimes and the touched rows' weights."""
        n = idx.shape[0] - vals.shape[0]
        return idx[:n], idx[n:2 * n], vals[:n], idx[2 * n:], vals[n:]

    def _ingest_universe(hours, mask, prices, rows, cols, new_hours,
                         trows):
        """The universe half of a profile ingest: write the new
        runtimes at ``(rows, cols)`` (the padded duplicates of a bucket
        re-set the same cell, so ``.set`` is idempotent) and recompute
        the touched rows ``trows`` whole -- their cost, masked minimum
        and normalised row -- with the cold path's expressions."""
        hours = hours.at[rows, cols].set(new_hours)
        mask = mask.at[rows, cols].set(True)
        t_mask = mask[trows]
        t_cost = _cost_of(hours[trows], t_mask, prices)
        t_best = t_cost.min(axis=1)
        return hours, mask, t_cost, t_best, _norm_of(t_cost, t_mask,
                                                     t_best)

    def _fold_rows(scores, finite, row_masks, trows, row_w, row_delta,
                   t_mask):
        """Fold the touched rows' change of normalised cost into every
        member holding them (``row_masks[:, trows] @ delta``, the
        bucket's padded rows weighted 0), and mark the configurations a
        new cell made finite for those members."""
        w = row_masks[:, trows] * row_w[None, :]
        scores = scores + _fleet_matmul(w, row_delta)
        finite = finite | (_fleet_matmul(w, t_mask.astype(w.dtype)) > 0)
        return scores, finite

    def _fleet_matmul(a, b):
        """The member-axis reductions of every fleet step, at float32
        precision: a TPU's default float32 matmul rounds its operands to
        bfloat16 (8 significand bits), far outside the jax
        ScoreContract's 1e-4."""
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def _masked_top_k(scores, finite, k):
        """``jax.lax.top_k`` over the last axis of the (possibly
        batched) score buffer with unprofiled configs masked to
        ``+inf``: ``(idx, vals)``.  Scores rank ascending (lower is
        better), so the kernel negates; ``lax.top_k`` breaks value ties
        by lower index, which after negation is exactly the
        catalog-order tie-break of :func:`_materialize` -- and makes the
        depth-``k`` head the prefix of every deeper one."""
        masked = jnp.where(finite, scores, jnp.inf)
        neg, idx = jax.lax.top_k(-masked, k)
        return idx, -neg

    def _jax_topk_fn() -> Any:
        """``topk(scores, finite, k)``: :func:`_masked_top_k` jitted.
        ``k`` is static — one compile per requested depth, the same
        O(distinct shapes) discipline as the delta buckets."""
        global _JAX_TOPK_FN
        if _JAX_TOPK_FN is None:
            with _JAX_FNS_LOCK:
                if _JAX_TOPK_FN is None:
                    _JAX_TOPK_FN = jax.jit(_masked_top_k, static_argnums=2)
        return _JAX_TOPK_FN

    def _jax_cold_fn() -> Any:
        """``cold(hours, mask, prices)``: the cold-path arithmetic in
        float32 -- cost, row minima, normalised cost and its column sums,
        the state a fleet's delta stream starts from -- jitted once on
        first use (so importing the selector never initializes an
        accelerator backend)."""
        global _JAX_COLD_FN
        if _JAX_COLD_FN is None:
            with _JAX_FNS_LOCK:
                if _JAX_COLD_FN is None:
                    def cold(hours, mask, prices):
                        # the cold-path arithmetic (float32): the state a
                        # delta stream starts from
                        cost = _cost_of(hours, mask, prices[None, :])
                        row_best = jnp.min(cost, axis=1)
                        norm = _norm_of(cost, mask, row_best)
                        return cost, row_best, norm, norm.sum(axis=0)

                    _JAX_COLD_FN = jax.jit(cold)
        return _JAX_COLD_FN


# --- batched multi-state repricing (jax_batched backend) --------------------------

if _HAVE_JAX:
    _JAX_BATCHED_FNS: Optional[Tuple[Any, Any, Any]] = None

    def _batched_step(prices, cost, row_best, norm, scores, hours, mask,
                      row_masks, cols, new_prices):
        """The batched delta step's traced body, shared by the plain
        step and the step that also takes the fleet's heads
        (:func:`_jax_batched_fns`)."""
        # the universe half: the shared traced helper
        (prices, cost, row_best, fresh_rows, moved,
         col_norm) = _delta_universe_update(prices, cost, row_best,
                                            hours, mask, cols,
                                            new_prices)
        # -- handed-off rows: fold the renormalization delta into
        #    every member's standing accumulators at once (S×J @
        #    J×C; rows that did not move contribute exact zeros, so
        #    a tick with no handoffs is drift-free here)
        row_delta = jnp.where(moved[:, None], fresh_rows - norm, 0.0)
        scores = scores + _fleet_matmul(row_masks, row_delta)
        norm = jnp.where(moved[:, None], fresh_rows, norm)
        # -- changed columns: re-reduce every member from scratch
        #    with a .set — idempotent under the duplicate indices
        #    the power-of-4 bucket padding introduces
        norm = norm.at[:, cols].set(col_norm)
        scores = scores.at[:, cols].set(_fleet_matmul(row_masks,
                                                      col_norm))
        return prices, cost, row_best, norm, scores, moved.sum()

    def _unpack_delta(delta):
        """A reprice's one host operand (:func:`_validated_delta_cols`)
        split back into the bucket's columns and float32 prices."""
        n = delta.shape[0] // 2
        return delta[:n], jax.lax.bitcast_convert_type(delta[n:],
                                                       jnp.float32)

    def _pack_heads(moved, idx, vals):
        """The handoff count and every slot's head as one ``int32``
        array, ``[moved, idx.ravel(), bits of vals.ravel()]``, so the
        reprice reads its results back in one transfer
        (:func:`_unpacked_heads`)."""
        return jnp.concatenate([
            moved.astype(jnp.int32)[None], idx.ravel(),
            jax.lax.bitcast_convert_type(vals, jnp.int32).ravel()])

    def _jax_batched_fns() -> Tuple[Any, Any, Any]:
        """``(step, step_heads, member_scores)`` jitted kernels for
        :class:`BatchedRankState`, built once on first use.

        The key observation that makes batching cheap (DESIGN.md §10):
        every member state shares the store's profiled mask, so the
        masked row-minimum — and therefore the whole normalized-cost
        matrix — is *identical* across members.  A member's scores are
        just a row-masked column reduction of the one shared norm
        matrix:

            scores[s, c] = Σ_j row_masks[s, j] · norm[j, c]

        so the per-tick step updates the shared cost/row-min/norm
        buffers once (:func:`_delta_universe_update`) and then
        refreshes *all* member accumulators with two small matmuls
        (handed-off-row deltas folded in; changed columns re-reduced
        from scratch) — one dispatch for the whole fleet, independent
        of the member count.  Both steps take the delta batch as one
        packed host operand (:func:`_unpack_delta`).
        ``step_heads(..., finite, k)`` is the same step followed by
        :func:`_masked_top_k` over the new scores at the static depth
        ``k``, its handoff count and heads returned as one packed array
        (:func:`_pack_heads`): the tick and every slot's head in one
        program and one readback."""
        global _JAX_BATCHED_FNS
        if _JAX_BATCHED_FNS is not None:
            return _JAX_BATCHED_FNS
        with _JAX_FNS_LOCK:
            if _JAX_BATCHED_FNS is not None:
                return _JAX_BATCHED_FNS
            return _build_jax_batched_fns()

    def _build_jax_batched_fns() -> Tuple[Any, Any, Any]:
        global _JAX_BATCHED_FNS

        def step(prices, cost, row_best, norm, scores, hours, mask,
                 row_masks, delta):
            return _batched_step(prices, cost, row_best, norm, scores,
                                 hours, mask, row_masks,
                                 *_unpack_delta(delta))

        def step_heads(prices, cost, row_best, norm, scores, hours, mask,
                       row_masks, delta, finite, k):
            out = step(prices, cost, row_best, norm, scores, hours, mask,
                       row_masks, delta)
            heads = _pack_heads(out[5], *_masked_top_k(out[4], finite, k))
            return out[:5] + (heads,)

        def member_scores(norm, row_mask):
            # a new member's accumulators from the current shared norm
            return _fleet_matmul(row_mask, norm)

        donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3, 4)
        _JAX_BATCHED_FNS = (
            jax.jit(step, donate_argnums=donate),
            jax.jit(step_heads, donate_argnums=donate, static_argnums=10),
            jax.jit(member_scores))
        return _JAX_BATCHED_FNS

    _JAX_INGEST_FN: Optional[Any] = None

    def _jax_ingest_fn() -> Any:
        """The jitted profile-ingest step of :class:`BatchedRankState`,
        built once on first use: new runtimes written into the resident
        universe, the touched rows recomputed whole
        (:func:`_ingest_universe`) and their change folded into every
        member (:func:`_fleet_matmul` at HIGHEST, like the reprice
        step).  Donates the seven buffers it rewrites (not on CPU)."""
        global _JAX_INGEST_FN
        if _JAX_INGEST_FN is None:
            with _JAX_FNS_LOCK:
                if _JAX_INGEST_FN is None:
                    def ingest(hours, mask, cost, row_best, norm, scores,
                               finite, prices, row_masks, idx, vals):
                        rows, cols, new_hours, trows, row_w = \
                            _unpack_cells(idx, vals)
                        (hours, mask, t_cost, t_best,
                         t_norm) = _ingest_universe(hours, mask, prices,
                                                    rows, cols, new_hours,
                                                    trows)
                        scores, finite = _fold_rows(
                            scores, finite, row_masks, trows, row_w,
                            t_norm - norm[trows], mask[trows])
                        return (hours, mask, cost.at[trows].set(t_cost),
                                row_best.at[trows].set(t_best),
                                norm.at[trows].set(t_norm), scores, finite)

                    donate = () if jax.default_backend() == "cpu" \
                        else tuple(range(7))
                    _JAX_INGEST_FN = jax.jit(ingest, donate_argnums=donate)
        return _JAX_INGEST_FN


class BatchedRankState:
    """One device dispatch per tick for a whole fleet of rankings.

    The serving problem this solves (DESIGN.md §10): a live
    :class:`~repro.selector.SelectionService` holds one ranking state
    per (job class, exclusion set) — a *fleet* of states over the same
    profiling store.  With one device state per selection a price tick
    would be one kernel dispatch *per state*; ``BatchedRankState`` stacks
    the fleet over a single shared device-resident universe — hours,
    profiled mask, cost, row-min and normalized-cost buffers are stored
    **once** (they are member-independent: every member shares the
    store's mask, so the masked row minima are identical) — with the
    per-member structure reduced to a row-mask matrix (S×J) and a score
    accumulator matrix (S×C), both carrying the member axis in front.
    :meth:`reprice` then runs one batched jitted delta-update kernel
    (donated state buffers, delta batches padded to power-of-4 column
    buckets) that refreshes every member's scores in the
    same dispatch.

    Members are added (:meth:`add_state`) and retired
    (:meth:`retire_state`) mid-stream; slot capacity grows by doubling,
    so the step kernel compiles O(log S) member-axis variants, and
    retired slots are zero-masked (they contribute nothing and are
    reused by later adds).

    Serving is per member: :meth:`ranking` materializes the full sorted
    list (memoized on the tick count), :meth:`top_k` serves the head of
    the ranking from the device score buffer without the C-object
    build/sort, and :meth:`winner` is ``top_k(1)``.  Heads are served
    fleet-wide: the first :meth:`top_k` after the scores or the
    membership changed runs ONE ``jax.lax.top_k`` over every slot and
    reads the (capacity, k) result back once; every later head, of any
    member, until the next change is a host slice of that readback.
    Once heads are being read, a :meth:`reprice` takes them in its own
    program and readback, so a tick that reprices and then publishes
    makes one round trip to the device, not two.

    **Contract** (:data:`SCORE_CONTRACTS` ``["jax_batched"]``): float32
    scores within the rel/abs envelope of a cold float64 rank, the
    winner identical or tied within it (DESIGN.md §9-§10); a fleet of
    one member is the plain incremental float32 ranking.
    """

    backend = "jax_batched"
    contract = SCORE_CONTRACTS["jax_batched"]
    _BUCKET_BASE = 8
    _CAPACITY_BASE = 8

    def __init__(self, hours: np.ndarray, mask: np.ndarray,
                 prices: np.ndarray, config_ids: Sequence[Hashable],
                 job_ids: Optional[Sequence[Hashable]] = None,
                 capacity: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None):
        if not _HAVE_JAX:
            raise BackendUnavailableError(
                "BatchedRankState requires jax; use RankState (numpy) "
                "when it is not installed")
        self.config_ids = list(config_ids)
        self.job_ids = list(job_ids) if job_ids is not None else None
        self._metrics = metrics
        hours, mask, prices = _canonicalize_universe(hours, mask, prices,
                                                     self.job_ids)
        self._pos = _position_index(self.config_ids)
        self._job_pos = (None if self.job_ids is None else
                         {j: i for i, j in enumerate(self.job_ids)})
        self._mask = mask.copy()              # host copy: member counts
        self._n_jobs = hours.shape[0]
        cold = _jax_cold_fn()
        (self._step, self._step_heads,
         self._member_scores) = _jax_batched_fns()
        # shared read-only residents (uploaded once, never donated)
        self.d_hours = jnp.asarray(hours, dtype=jnp.float32)
        self.d_mask = jnp.asarray(mask)
        # shared donated state buffers (the universe)
        self.d_prices = jnp.asarray(prices, dtype=jnp.float32)
        (self.d_cost, self.d_row_best, self.d_norm,
         _) = cold(self.d_hours, self.d_mask, self.d_prices)
        self._init_members(self._CAPACITY_BASE if capacity is None
                           else max(1, capacity))

    def _init_members(self, cap: int) -> None:
        """The member axis at ``cap`` empty slots (slot tables, batched
        accumulators), the serving memos and the counters."""
        self._capacity = cap
        self._slots: "dict[Hashable, int]" = {}
        #: keys retired via :meth:`retire_state`; serving one raises
        #: :class:`NothingRankableError` (a never-registered key stays a
        #: plain ``ValueError`` — that is caller misconfiguration).
        self._retired: "set" = set()
        self._free: List[int] = list(range(cap - 1, -1, -1))
        self.d_row_masks = jnp.zeros((cap, self._n_jobs),
                                     dtype=jnp.float32)
        #: host copy of the row masks: which members a new cell counts in
        self._member_rows = np.zeros((cap, self._n_jobs), dtype=bool)
        self.d_scores = jnp.zeros((cap, len(self.config_ids)),
                                  dtype=jnp.float32)
        self._counts = np.zeros((cap, len(self.config_ids)),
                                dtype=np.int64)
        self._d_finite = jnp.zeros((cap, len(self.config_ids)),
                                   dtype=bool)
        #: ticks applied since construction; one tick == one kernel
        #: dispatch regardless of the member count (the benchmark's
        #: ``one_dispatch_per_tick`` gate reads this).
        self.reprices = 0
        #: alias making the dispatch accounting explicit at call sites.
        self.dispatches = 0
        #: capacity doublings since construction.  A retire-all /
        #: re-add cycle must reuse the zero-masked slots and leave this
        #: untouched (regression-pinned) — growth is for genuinely new
        #: concurrent members only.
        self.realloc_count = 0
        self.materializations = 0
        self._ranking_memo: "dict[Hashable, Tuple[int, List[RankedConfig]]]" = {}
        #: ``(d_scores, d_finite, {k: (idx, vals)})``: every slot's head,
        #: valid while both buffers are the arrays it was computed from
        #: (:meth:`_fleet_heads`)
        self._head_memo: Tuple[Any, Any, dict] = (None, None, {})
        #: head depths asked since the previous reprice: the next
        #: reprice takes the heads in its own program (:meth:`reprice`)
        self._asked: "set[int]" = set()
        m = self._metrics
        self._c_mat = (None if m is None
                       else m.counter("rank.materializations"))
        self._c_head_batches = (None if m is None
                                else m.counter("rank.head_batches"))
        self._c_head_hits = (None if m is None
                             else m.counter("rank.head_memo_hits"))
        self._c_reprice_heads = (None if m is None
                                 else m.counter("rank.reprice_heads"))
        self._c_reprice_transfers = (
            None if m is None else m.counter("rank.reprice_transfers"))
        self._c_ingests = (None if m is None
                           else m.counter("rank.ingest_batches"))

    # -- member management --------------------------------------------------
    def __contains__(self, key: Hashable) -> bool:
        return key in self._slots

    @property
    def n_active(self) -> int:
        """Live member count (what one tick dispatch refreshes)."""
        return len(self._slots)

    def keys(self) -> List[Hashable]:
        return list(self._slots)

    def has_job(self, job_id: Hashable) -> bool:
        """Is ``job_id`` a row of the universe (what :meth:`ingest`
        can write)?"""
        return self._job_pos is not None and job_id in self._job_pos

    def _slot_of(self, key: Hashable) -> int:
        try:
            return self._slots[key]
        except KeyError:
            if key in self._retired:
                # a member that *was* live and has been retired: serving
                # it is a rankable-nothing condition, not a caller bug —
                # typed so the service/daemon path journals a genuine
                # rejection instead of dying on the masked slot
                raise NothingRankableError(
                    f"member state {key!r} was retired")
            raise ValueError(f"unknown member state {key!r}")

    def _grow(self) -> None:
        cap = self._capacity * 2
        self.d_row_masks = jnp.zeros(
            (cap, self._n_jobs), dtype=jnp.float32
        ).at[:self._capacity].set(self.d_row_masks)
        self.d_scores = jnp.zeros(
            (cap, len(self.config_ids)), dtype=jnp.float32
        ).at[:self._capacity].set(self.d_scores)
        self._d_finite = jnp.zeros(
            (cap, len(self.config_ids)), dtype=bool
        ).at[:self._capacity].set(self._d_finite)
        counts = np.zeros((cap, len(self.config_ids)), dtype=np.int64)
        counts[:self._capacity] = self._counts
        self._counts = counts
        member_rows = np.zeros((cap, self._n_jobs), dtype=bool)
        member_rows[:self._capacity] = self._member_rows
        self._member_rows = member_rows
        self._free.extend(range(cap - 1, self._capacity - 1, -1))
        self._capacity = cap
        self.realloc_count += 1

    def _rows_of(self, rows: Optional[Sequence[int]],
                 jobs: Optional[Sequence[Hashable]]) -> np.ndarray:
        if (rows is None) == (jobs is None):
            raise ValueError("pass exactly one of rows= or jobs=")
        if jobs is not None:
            if self._job_pos is None:
                raise ValueError(
                    "jobs= needs a state constructed with job_ids")
            try:
                rows = [self._job_pos[j] for j in jobs]
            except KeyError as e:
                raise ValueError(f"unknown job id {e.args[0]!r}")
        idx = np.asarray(list(rows), dtype=np.intp)
        if idx.size and (idx.min() < 0 or idx.max() >= self._n_jobs):
            raise ValueError(f"row index out of range for "
                             f"{self._n_jobs} jobs")
        if np.unique(idx).size != idx.size:
            raise ValueError("duplicate rows in member selection")
        return idx

    def add_state(self, key: Hashable, *,
                  rows: Optional[Sequence[int]] = None,
                  jobs: Optional[Sequence[Hashable]] = None) -> None:
        """Register a member ranking over a subset of the job axis
        (``rows`` indices, or ``jobs`` ids when the state was built with
        ``job_ids``).  The member's accumulators are computed from the
        *current* shared norm matrix, so a member added mid-stream is
        immediately in sync with every tick applied so far."""
        if key in self._slots:
            raise ValueError(f"duplicate member state {key!r}")
        self._retired.discard(key)      # re-registering revives the key
        idx = self._rows_of(rows, jobs)
        if not self._free:
            self._grow()
        slot = self._free.pop()
        row_mask = np.zeros(self._n_jobs, dtype=np.float32)
        row_mask[idx] = 1.0
        counts = self._mask[idx].sum(axis=0) if idx.size else \
            np.zeros(len(self.config_ids), dtype=np.int64)
        d_row = jnp.asarray(row_mask)
        self.d_row_masks = self.d_row_masks.at[slot].set(d_row)
        self._member_rows[slot] = row_mask > 0
        self.d_scores = self.d_scores.at[slot].set(
            self._new_member_scores(d_row))
        self._counts[slot] = counts
        self._d_finite = self._d_finite.at[slot].set(
            jnp.asarray(counts > 0))
        self._slots[key] = slot

    def _new_member_scores(self, d_row):
        """A new member's accumulators from the current shared norm."""
        return self._member_scores(self.d_norm, d_row)

    def retire_state(self, key: Hashable) -> None:
        """Drop a member: its slot is zero-masked (contributes nothing
        to later ticks) and reused by the next :meth:`add_state`.
        Serving a retired key afterwards raises
        :class:`NothingRankableError` — never a raw ``KeyError`` or a
        masked-slot score — so service/daemon callers journal a genuine
        rejection (DESIGN.md §10)."""
        slot = self._slots.pop(key, None)
        if slot is None:
            raise ValueError(f"unknown member state {key!r}")
        zeros_j = jnp.zeros(self._n_jobs, dtype=jnp.float32)
        self.d_row_masks = self.d_row_masks.at[slot].set(zeros_j)
        self._member_rows[slot] = False
        self.d_scores = self.d_scores.at[slot].set(
            jnp.zeros(len(self.config_ids), dtype=jnp.float32))
        self._counts[slot] = 0
        self._d_finite = self._d_finite.at[slot].set(
            jnp.zeros(len(self.config_ids), dtype=bool))
        self._ranking_memo.pop(key, None)
        self._retired.add(key)
        self._free.append(slot)

    # -- the batched tick ---------------------------------------------------
    @property
    def prices(self) -> np.ndarray:
        """Current per-config $/h as seen by the kernel (float32 quotes
        lifted to a host float64 vector)."""
        return np.asarray(self.d_prices, dtype=np.float64)

    def scores(self, key: Hashable) -> np.ndarray:
        """A member's score accumulators on the host (float64 lift)."""
        return np.asarray(self.d_scores[self._slot_of(key)],
                          dtype=np.float64)

    def counts(self, key: Hashable) -> np.ndarray:
        """A member's per-config contributing-cell counts."""
        return self._counts[self._slot_of(key)].copy()

    def reprice(self, deltas: Union[Mapping[Hashable, float],
                                    Sequence[Tuple[Hashable, float]]]
                ) -> int:
        """Apply ``{config_id: new $/h}`` deltas to the shared universe
        and refresh **every** member's accumulators in one batched
        kernel dispatch; returns #rows whose masked row-minimum handed
        off (synced to host, so a return means the tick's kernel has
        completed).  The batch crosses to the device as one packed host
        operand and the results come back in one readback.

        When heads were asked since the previous reprice
        (:meth:`_fleet_heads`), the same program also runs the fleet's
        ``top_k`` over the new scores at the deepest depth asked, and
        the one readback, packed with the handoff count, brings every
        slot's head home into the heads memo, each asked depth a prefix
        of the deepest (``lax.top_k`` keeps the lower index first on
        ties)."""
        delta = _validated_delta_cols(self._pos, deltas, self._BUCKET_BASE)
        if delta is None:
            return 0
        args = (self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
                self.d_scores, self.d_hours, self.d_mask, self.d_row_masks,
                delta)
        asked, self._asked = self._asked, set()
        if asked:
            k = max(asked)
            (self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
             self.d_scores, out) = self._step_heads(*args, self._d_finite,
                                                    k)
            moved, idx, vals = _unpacked_heads(jax.device_get(out), k)
            self._head_memo = (self.d_scores, self._d_finite,
                               {d: (idx[:, :d], vals[:, :d])
                                for d in asked})
            if self._c_reprice_heads is not None:
                self._c_reprice_heads.inc()
        else:
            (self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
             self.d_scores, moved) = self._step(*args)
        if self._c_reprice_transfers is not None:
            # the packed delta in, the packed result (or the bare
            # handoff count) out
            self._c_reprice_transfers.inc(2)
        self.reprices += 1
        self.dispatches += 1
        return int(moved)

    # -- profile ingest ---------------------------------------------------
    def ingest(self, cells: Sequence[Tuple[Hashable, Hashable, float]]
               ) -> int:
        """Write ``(job, config, runtime hours)`` cells into the resident
        universe and refresh every member in one jitted dispatch, with
        no host sync: the touched job rows are recomputed whole (cost,
        masked minimum, normalised row) and their change is folded into
        each member that holds them, so a re-run that moves a row's
        minimum, or a new cell that makes a configuration rankable for a
        member, lands without a cold rebuild.  Jobs must be on the job
        axis and configurations in the catalog (``ValueError``
        otherwise); a later cell overwrites an earlier one.  Cells are
        padded to power-of-4 buckets (touched rows too, weighted 0), so
        the step compiles O(log) variants.  Returns the cells written."""
        prepared = self._prepared_cells(cells)
        if prepared is None:
            return 0
        operands, n = prepared
        with maybe_span(self._metrics, INGEST_DISPATCH_SPAN):
            self._dispatch_ingest(*operands)
        # every score, finite flag and ranking may have moved: the heads
        # memo sees new buffers; the per-member sorts are dropped
        self._ranking_memo.clear()
        if self._c_ingests is not None:
            self._c_ingests.inc()
        return n

    def _prepared_cells(self, cells):
        """Host half of :meth:`ingest`: ids to positions, runtimes
        checked, the host mask and member counts updated; returns the
        bucket-padded device operands packed for :func:`_unpack_cells`
        and the distinct cells, or ``None`` for an empty batch."""
        if self._job_pos is None:
            raise ValueError("ingest needs a state constructed with "
                             "job_ids")
        cells = list(cells)
        if not cells:
            return None
        try:
            rows = np.array([self._job_pos[j] for j, _, _ in cells],
                            dtype=np.int32)
            cols = np.array([self._pos[c] for _, c, _ in cells],
                            dtype=np.int32)
        except KeyError as e:
            raise ValueError(f"unknown job or config id in ingested "
                             f"cells: {e.args[0]!r}")
        hours = np.array([h for _, _, h in cells], dtype=np.float64)
        bad = np.flatnonzero(~((hours > 0) & (hours < np.inf)))
        if bad.size:
            job, config, _ = cells[bad[0]]
            raise ValueError(f"non-positive or non-finite runtime for "
                             f"{job!r} on {config!r}")
        # a later cell overwrites an earlier one: keep each last write
        key = rows.astype(np.int64) * len(self.config_ids) + cols
        _, last = np.unique(key[::-1], return_index=True)
        if last.size < key.size:
            keep = np.sort(key.size - 1 - last)
            rows, cols, hours = rows[keep], cols[keep], hours[keep]
        n = rows.size
        new = ~self._mask[rows, cols]
        if new.any():
            self._mask[rows[new], cols[new]] = True
            # each new cell counts once in every member holding its row
            np.add.at(self._counts.T, cols[new],
                      self._member_rows[:, rows[new]].T)
        trows = np.unique(rows)
        t = trows.size
        nb = _bucket_size(n, self._BUCKET_BASE)
        tb = _bucket_size(t, 1)
        # pads repeat the first cell (an idempotent re-set) and the
        # first touched row at weight 0
        idx = np.empty(2 * nb + tb, dtype=np.int32)
        vals = np.zeros(nb + tb, dtype=np.float32)
        idx[:nb], idx[nb:2 * nb], idx[2 * nb:] = rows[0], cols[0], trows[0]
        idx[:n], idx[nb:nb + n], idx[2 * nb:2 * nb + t] = rows, cols, trows
        vals[:nb] = hours[0]
        vals[:n], vals[nb:nb + t] = hours, 1.0
        return (idx, vals), n

    def _dispatch_ingest(self, idx, vals) -> None:
        (self.d_hours, self.d_mask, self.d_cost, self.d_row_best,
         self.d_norm, self.d_scores, self._d_finite) = _jax_ingest_fn()(
            self.d_hours, self.d_mask, self.d_cost, self.d_row_best,
            self.d_norm, self.d_scores, self._d_finite, self.d_prices,
            self.d_row_masks, idx, vals)

    # -- per-member serving -------------------------------------------------
    def ranking(self, key: Hashable) -> List[RankedConfig]:
        """A member's full sorted ranking under the tolerance contract
        (memoized on the tick count, like the other states; a fresh
        list copy is returned each call)."""
        memo = self._ranking_memo.get(key)
        if memo is None or memo[0] != self.reprices:
            slot = self._slot_of(key)
            self.materializations += 1
            if self._c_mat is not None:
                self._c_mat.inc()
            memo = (self.reprices,
                    _materialize(self.scores(key), self._counts[slot],
                                 self.config_ids))
            self._ranking_memo[key] = memo
        return list(memo[1])

    def _fleet_heads(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Every slot's ``k``-head as host arrays ``(idx, vals)`` of
        shape (capacity, k): one jitted ``top_k`` over the whole score
        buffer (``lax.top_k`` sorts the last axis, so each row keeps its
        catalog-order tie-break) and one readback of the pair.  Memoized
        per ``k`` on the identity of ``d_scores`` and ``_d_finite``:
        every path that changes scores or membership (the reprice, add,
        retire, growth) assigns new arrays, so identity is the
        state-change test.  Each ``k`` asked, hit or miss, is recorded
        for the next :meth:`reprice` to take in its own program."""
        self._asked.add(k)
        scores, finite = self.d_scores, self._d_finite
        memo_scores, memo_finite, heads = self._head_memo
        if memo_scores is not scores or memo_finite is not finite:
            heads = {}
            self._head_memo = (scores, finite, heads)
        hit = heads.get(k)
        if hit is not None:
            if self._c_head_hits is not None:
                self._c_head_hits.inc()
            return hit
        with maybe_span(self._metrics, TOPK_DISPATCH_SPAN):
            idx, vals = _jax_topk_fn()(scores, finite, k)
        with maybe_span(self._metrics, TOPK_READBACK_SPAN):
            idx, vals = jax.device_get((idx, vals))
        if self._c_head_batches is not None:
            self._c_head_batches.inc()
        heads[k] = hit = (idx, vals.astype(np.float64))
        return hit

    def top_k(self, key: Hashable, k: int) -> List[RankedConfig]:
        """The head of a member's ranking, sliced from the fleet's heads
        (:meth:`_fleet_heads`: the first call after a state change runs
        one ``jax.lax.top_k`` over every slot, later calls touch no
        device) — no C-object materialization, same catalog-order
        tie-break as :meth:`ranking` (see :func:`_jax_topk_fn`)."""
        slot = self._slot_of(key)
        k = _check_k(k, len(self.config_ids))
        idx, vals = self._fleet_heads(k)
        counts = self._counts[slot]
        out = []
        for i, s in zip(idx[slot], vals[slot]):
            n = int(counts[i])
            out.append(RankedConfig(
                self.config_ids[int(i)],
                float(s) if n else float("inf"),
                float(s) / n if n else float("inf")))
        return out

    def winner(self, key: Hashable) -> RankedConfig:
        """The member's top pick — ``top_k(key, 1)`` on device."""
        return self.top_k(key, 1)[0]
