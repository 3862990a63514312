"""Vectorized normalized-cost ranking (paper §II, step 2).

The ranking is one matrix computation instead of a per-pair dict loop:

    cost   = runtime_hours (J x C)  *  price_vector (C,)     # broadcast
    norm   = cost / row-min(cost over profiled cells)        # row-normalize
    score  = column-sum of norm over profiled cells          # per config

A config with **zero** profiled cells scores ``+inf`` and therefore ranks
last (an unprofiled config must never win by default — the historical dict
loop left it at 0.0, i.e. argmin).

Two backends:

  * ``"numpy"`` (default): float64, bit-stable with the historical
    per-pair arithmetic — used for the paper-table reproductions;
  * ``"jax"``: a jitted ``jax.numpy`` kernel (float32 on CPU/TPU) that
    fuses the whole ranking into one XLA computation — the serving-scale
    path for 10k+ (job x config) cells, benchmarked in
    ``benchmarks/rank_bench.py``.

Each backend carries an explicit :class:`ScoreContract` (DESIGN.md §9):
numpy guarantees bit-identity between the incremental and cold paths;
jax is float32 and guarantees the same winner (or a winner tied within
tolerance) with scores inside a rel/abs envelope.  Incremental repricing
lives in :class:`RankState` (numpy) and :class:`JaxRankState` (the
accelerator-resident jitted delta-update kernel with donated buffers).
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
§14).  Every state also serves :meth:`top_k` — the head of
the ranking without materializing and sorting all C configs
(``jax.lax.top_k`` on device for the jax-family states, a partial
selection on numpy).
"""
from __future__ import annotations

import dataclasses
import os
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

#: the knob CI's backend matrix turns; resolved by :func:`default_backend`.
BACKEND_ENV_VAR = "FLORA_RANK_BACKEND"
#: ``"jax_batched"`` shares the jax cold kernel and ScoreContract but
#: makes the *service* stack every live (class, exclusion) ranking into
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
BACKENDS = ("numpy", "jax", "jax_batched", "jax_sharded", "jax_pallas")
#: the fleet backends: a SelectionService on one of these stacks every
#: live (class, exclusion) ranking into a single shared state, so a
#: price tick is one (possibly collective) kernel dispatch fleet-wide.
FLEET_BACKENDS = ("jax_batched", "jax_sharded", "jax_pallas")
#: span names of a device ``top_k`` head: the enqueue (slot lookup,
#: slices, the jitted call) and the blocking readback of its two
#: results; the host assembly of the head lies outside both
TOPK_DISPATCH_SPAN = "topk.dispatch"
TOPK_READBACK_SPAN = "topk.readback"
#: backends whose runtime dependency is jax.
_JAX_FAMILY = ("jax", "jax_batched", "jax_sharded", "jax_pallas")


class BackendUnavailableError(RuntimeError):
    """A ranking backend was requested whose runtime dependency is not
    installed (today: ``backend="jax"`` without jax).  Typed so callers —
    and test harnesses — can skip rather than die: distinguishable from
    both misconfiguration ``ValueError``\\ s (unknown backend names) and
    genuine crashes."""


def default_backend() -> str:
    """The backend used when a :class:`~repro.selector.SelectionService`
    is built without an explicit ``backend=``: the ``FLORA_RANK_BACKEND``
    env var, else ``"numpy"``.  ``rank_dense`` itself always defaults to
    numpy — the float64 bit-stable reference that replay audits re-rank
    against must not move under the env var."""
    backend = os.environ.get(BACKEND_ENV_VAR, "numpy")
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r} in ${BACKEND_ENV_VAR} "
            f"(expected one of {BACKENDS})")
    return backend


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
    "jax": ScoreContract("jax", bit_identical=False,
                         rel_tol=1e-4, abs_tol=1e-6),
    # same float32 physics as "jax" (shared row-min/norm intermediates,
    # delta-folded accumulators); batching adds no new drift source —
    # member scores are re-reduced per changed column like the per-state
    # kernel, so the envelope is identical (DESIGN.md §10).
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
    jax-family backends (``"jax"``, ``"jax_batched"``,
    ``"jax_sharded"``, ``"jax_pallas"``) only when jax imports.
    Unknown names are *not*
    an error from this predicate (they fail later with ``ValueError``
    at dispatch)."""
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
    (:func:`rank_dense`, :class:`RankState`, :class:`JaxRankState`):
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
    jax backend's accelerator-resident counterpart,
    :class:`JaxRankState`, serves a *tolerance* contract instead
    (same winner or tied within tolerance; see :class:`ScoreContract`
    and DESIGN.md §9).
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


# --- the accelerator-resident incremental path (jax backend) ----------------------

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
                          bucket_base: int
                          ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Shared delta-batch preparation for the jitted jax states
    (:class:`JaxRankState`, :class:`BatchedRankState`): validate ids and
    prices (:func:`_validated_deltas`), then pad ``(cols, new_prices)``
    to the next power-of-4 column-count bucket so the jitted step
    compiles O(log C) shape variants.  Padding repeats the first
    (column, price) pair, which every kernel op treats idempotently.
    Returns ``None`` for an empty batch."""
    validated = _validated_deltas(pos, deltas)
    if validated is None:
        return None
    cols, new_prices = validated
    k = cols.shape[0]
    bucket = _bucket_size(k, bucket_base)
    if bucket > k:
        cols = np.concatenate(
            [cols, np.full(bucket - k, cols[0], dtype=np.int32)])
        new_prices = np.concatenate(
            [new_prices, np.full(bucket - k, new_prices[0])])
    return cols, new_prices


if _HAVE_JAX:
    _JAX_STATE_FNS: Optional[Tuple[Any, Any, Any]] = None
    _JAX_TOPK_FN: Optional[Any] = None
    #: guards every lazy jitted-kernel singleton below (double-checked
    #: locking): the serving front-end first-calls from N snapshot
    #: workers plus the tick thread concurrently, and an unlocked
    #: check-then-build can build twice and interleave partially-
    #: initialized reads (regression-stressed in tests/test_kernels.py)
    _JAX_FNS_LOCK = threading.Lock()

    def _delta_universe_update(prices, cost, row_best, hours, mask,
                               cols, new_prices):
        """The shared universe half of every jitted delta step (traced
        inside both the per-state and the batched kernels, so the two
        backends can never silently diverge on the numerically critical
        logic):

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
        new_cost = jnp.where(sub_mask,
                             hours[:, cols] * new_prices[None, :],
                             jnp.inf)
        old_cost = cost[:, cols]
        prices = prices.at[cols].set(new_prices)
        cost = cost.at[:, cols].set(new_cost)
        was_min = old_cost.min(axis=1) == row_best
        undercut = new_cost.min(axis=1) < row_best
        fresh = jnp.where(was_min | undercut, cost.min(axis=1),
                          row_best)
        moved = fresh != row_best
        row_best = fresh
        fresh_rows = jnp.where(mask, cost / row_best[:, None], 0.0)
        col_norm = jnp.where(sub_mask,
                             cost[:, cols] / row_best[:, None], 0.0)
        return prices, cost, row_best, fresh_rows, moved, col_norm

    def _fleet_matmul(a, b):
        """The member-axis reductions of every fleet step, at float32
        precision: a TPU's default float32 matmul rounds its operands to
        bfloat16 (8 significand bits), far outside the jax
        ScoreContract's 1e-4."""
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def _jax_topk_fn() -> Any:
        """``topk(scores, finite, k)`` — ``jax.lax.top_k`` over the
        (possibly batched) score buffer with unprofiled configs masked
        to ``+inf``.  Scores rank ascending (lower is better), so the
        kernel negates; ``lax.top_k`` breaks value ties by lower index,
        which after negation is exactly the catalog-order tie-break of
        :func:`_materialize`.  ``k`` is static — one compile per
        requested depth, the same O(distinct shapes) discipline as the
        delta buckets."""
        global _JAX_TOPK_FN
        if _JAX_TOPK_FN is None:
            with _JAX_FNS_LOCK:
                if _JAX_TOPK_FN is None:
                    def topk(scores, finite, k):
                        masked = jnp.where(finite, scores, jnp.inf)
                        neg, idx = jax.lax.top_k(-masked, k)
                        return idx, -neg
                    _JAX_TOPK_FN = jax.jit(topk, static_argnums=2)
        return _JAX_TOPK_FN

    def _jax_state_fns() -> Tuple[Any, Any, Any]:
        """``(cold, step, winner)`` jitted kernels, built once on first
        use (so importing the selector never initializes an accelerator
        backend).  The step donates its five state buffers — a tick
        updates the resident arrays in place instead of allocating a
        fresh universe — except on CPU, whose client cannot donate and
        would warn on every call site."""
        global _JAX_STATE_FNS
        if _JAX_STATE_FNS is not None:
            return _JAX_STATE_FNS
        with _JAX_FNS_LOCK:
            if _JAX_STATE_FNS is not None:
                return _JAX_STATE_FNS
            return _build_jax_state_fns()

    def _build_jax_state_fns() -> Tuple[Any, Any, Any]:
        global _JAX_STATE_FNS

        def cold(hours, mask, prices):
            # the cold-path arithmetic (float32): the state a delta
            # stream starts from
            cost = jnp.where(mask, hours * prices[None, :], jnp.inf)
            row_best = jnp.min(cost, axis=1)
            norm = jnp.where(mask, cost / row_best[:, None], 0.0)
            return cost, row_best, norm, norm.sum(axis=0)

        def step(prices, cost, row_best, norm, scores, hours, mask,
                 cols, new_prices):
            (prices, cost, row_best, fresh_rows, moved,
             col_norm) = _delta_universe_update(prices, cost, row_best,
                                                hours, mask, cols,
                                                new_prices)
            # handed-off rows renormalize whole rows; the delta folds
            # into the standing score accumulators — the per-tick ulp
            # drift the jax ScoreContract tolerances cover (DESIGN.md §9)
            scores = scores + jnp.where(moved[:, None],
                                        fresh_rows - norm, 0.0).sum(axis=0)
            norm = jnp.where(moved[:, None], fresh_rows, norm)
            # changed columns re-sum from scratch with a .set — the
            # duplicate indices bucket padding introduces are idempotent
            # under .set (a .add of deltas would double-count them)
            norm = norm.at[:, cols].set(col_norm)
            scores = scores.at[cols].set(col_norm.sum(axis=0))
            return prices, cost, row_best, norm, scores, moved.sum()

        def winner(scores, finite):
            masked = jnp.where(finite, scores, jnp.inf)
            i = jnp.argmin(masked)
            return i, scores[i]

        donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3, 4)
        _JAX_STATE_FNS = (jax.jit(cold),
                          jax.jit(step, donate_argnums=donate),
                          jax.jit(winner))
        return _JAX_STATE_FNS


class JaxRankState:
    """Accelerator-resident incremental repricing (the jax backend).

    The float32 counterpart of :class:`RankState` for serving-scale
    universes: the runtime matrix, mask and every intermediate (cost,
    row-min, normalized-cost, score accumulators) live as device arrays,
    and :meth:`reprice` runs one jitted delta-update kernel whose state
    buffers are donated — a tick updates the universe in place, touching
    only the changed cost/norm columns plus the rows whose masked
    row-minimum handed off, with per-column score re-sums for changed
    columns and delta-folds for handed-off rows.  Host traffic per tick
    is the delta batch in and one scalar (the handoff count) out; a cold
    ``rank_dense(backend="jax")`` instead re-uploads the whole float64
    universe and re-materializes every ranking
    (``benchmarks/market_bench.py`` quantifies the gap).

    **Tolerance contract** (:data:`SCORE_CONTRACTS` ``["jax"]``): float32
    sums are not decomposable, and the delta-folded score accumulators
    drift by ulps per tick, so — unlike :class:`RankState` — rankings
    are *not* bit-identical to a cold re-rank.  The contract is
    same-winner-or-tied-within-tolerance, scores inside the rel/abs
    envelope; ``JournalReplayer.audit`` verifies journals produced
    through this path in exactly those terms (DESIGN.md §9).

    Delta batches are padded to power-of-4 column-count buckets so the
    jitted step compiles O(log C) shape variants, not one per batch
    size; padding repeats the first (column, price) pair, which every
    kernel op treats idempotently.
    """

    backend = "jax"
    contract = SCORE_CONTRACTS["jax"]
    _BUCKET_BASE = 8

    def __init__(self, hours: np.ndarray, mask: np.ndarray,
                 prices: np.ndarray, config_ids: Sequence[Hashable],
                 job_ids: Optional[Sequence[Hashable]] = None,
                 metrics: Optional[MetricsRegistry] = None):
        if not _HAVE_JAX:
            raise BackendUnavailableError(
                "JaxRankState requires jax; use RankState (numpy) "
                "when it is not installed")
        self.config_ids = list(config_ids)
        self.job_ids = list(job_ids) if job_ids is not None else None
        self._metrics = metrics
        self._c_mat = (None if metrics is None
                       else metrics.counter("rank.materializations"))
        hours, mask, prices = _canonicalize_universe(hours, mask, prices,
                                                     self.job_ids)
        self._pos = _position_index(self.config_ids)
        cold, self._step, self._winner_fn = _jax_state_fns()
        # read-only residents (uploaded once, never donated)
        self.d_hours = jnp.asarray(hours, dtype=jnp.float32)
        self.d_mask = jnp.asarray(mask)
        self.counts = mask.sum(axis=0)
        self._d_finite = jnp.asarray(self.counts > 0)
        # the donated state buffers
        self.d_prices = jnp.asarray(prices, dtype=jnp.float32)
        (self.d_cost, self.d_row_best, self.d_norm,
         self.d_scores) = cold(self.d_hours, self.d_mask, self.d_prices)
        #: ticks applied since construction (diagnostics, cache keys).
        self.reprices = 0
        #: host materializations actually performed: :meth:`ranking` is
        #: memoized on ``reprices``, so repeat calls between two ticks —
        #: previously a fresh device→host transfer + C-object build +
        #: sort *every call* — reuse the last sort (the counter the
        #: freshness regression test asserts on).
        self.materializations = 0
        self._ranking_memo: Optional[Tuple[int, List[RankedConfig]]] = None

    @property
    def prices(self) -> np.ndarray:
        """Current per-config $/h as seen by the kernel (float32 quotes
        lifted to a host float64 vector)."""
        return np.asarray(self.d_prices, dtype=np.float64)

    @property
    def scores(self) -> np.ndarray:
        """Current score accumulators on the host (float64 lift)."""
        return np.asarray(self.d_scores, dtype=np.float64)

    def reprice(self, deltas: Union[Mapping[Hashable, float],
                                    Sequence[Tuple[Hashable, float]]]
                ) -> int:
        """Apply ``{config_id: new $/h}`` deltas on device; returns
        #rows whose masked row-minimum handed off (synced to host, so a
        return means the tick's kernel has completed)."""
        prepared = _validated_delta_cols(self._pos, deltas,
                                         self._BUCKET_BASE)
        if prepared is None:
            return 0
        cols, new_prices = prepared
        (self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
         self.d_scores, moved) = self._step(
            self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
            self.d_scores, self.d_hours, self.d_mask,
            jnp.asarray(cols), jnp.asarray(new_prices, dtype=jnp.float32))
        self.reprices += 1
        return int(moved)

    def ranking(self) -> List[RankedConfig]:
        """The full sorted ranking under the tolerance contract: one
        device→host score transfer, then the same materialization as
        every other path (ties broken by catalog order).  Memoized on
        the state's tick count — the host sort used to re-run on *every*
        call even when no tick had been applied since the last
        materialization (the dominant serving cost at 10k configs); a
        fresh list copy is returned each call so the memo stays
        pristine."""
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
        """The first ``k`` entries of :meth:`ranking` served from the
        device: ``jax.lax.top_k`` over the resident score buffer, then
        an O(k) readback — the full C-config materialize/sort never
        happens.  Tie-break (catalog order on equal scores) matches the
        materialized ranking; see :func:`_jax_topk_fn`."""
        with maybe_span(self._metrics, TOPK_DISPATCH_SPAN):
            k = _check_k(k, len(self.config_ids))
            idx, vals = _jax_topk_fn()(self.d_scores, self._d_finite, k)
        with maybe_span(self._metrics, TOPK_READBACK_SPAN):
            idx = np.asarray(idx)
            vals = np.asarray(vals, dtype=np.float64)
        out = []
        for i, s in zip(idx, vals):
            n = int(self.counts[i])
            out.append(RankedConfig(
                self.config_ids[int(i)],
                float(s) if n else float("inf"),
                float(s) / n if n else float("inf")))
        return out

    def winner(self) -> RankedConfig:
        """argmin on device — only two scalars cross to the host."""
        i, s = self._winner_fn(self.d_scores, self._d_finite)
        i = int(i)
        c = self.config_ids[i]
        if not self.counts[i]:
            return RankedConfig(c, float("inf"), float("inf"))
        return RankedConfig(c, float(s), float(s) / int(self.counts[i]))


# --- batched multi-state repricing (jax_batched backend) --------------------------

if _HAVE_JAX:
    _JAX_BATCHED_FNS: Optional[Tuple[Any, Any]] = None

    def _jax_batched_fns() -> Tuple[Any, Any]:
        """``(step, member_scores)`` jitted kernels for
        :class:`BatchedRankState`, built once on first use.

        The key observation that makes batching cheap (DESIGN.md §10):
        every member state shares the store's profiled mask, so the
        masked row-minimum — and therefore the whole normalized-cost
        matrix — is *identical* across members.  A member's scores are
        just a row-masked column reduction of the one shared norm
        matrix:

            scores[s, c] = Σ_j row_masks[s, j] · norm[j, c]

        so the per-tick step updates the shared cost/row-min/norm
        buffers exactly like :class:`JaxRankState`'s kernel and then
        refreshes *all* member accumulators with two small matmuls
        (handed-off-row deltas folded in; changed columns re-reduced
        from scratch) — one dispatch for the whole fleet, independent
        of the member count."""
        global _JAX_BATCHED_FNS
        if _JAX_BATCHED_FNS is not None:
            return _JAX_BATCHED_FNS
        with _JAX_FNS_LOCK:
            if _JAX_BATCHED_FNS is not None:
                return _JAX_BATCHED_FNS
            return _build_jax_batched_fns()

    def _build_jax_batched_fns() -> Tuple[Any, Any]:
        global _JAX_BATCHED_FNS

        def step(prices, cost, row_best, norm, scores, hours, mask,
                 row_masks, cols, new_prices):
            # the universe half is the SAME traced helper as the
            # per-state kernel — the backends cannot diverge on it
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

        def member_scores(norm, row_mask):
            # a new member's accumulators from the current shared norm
            return _fleet_matmul(row_mask, norm)

        donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3, 4)
        _JAX_BATCHED_FNS = (jax.jit(step, donate_argnums=donate),
                            jax.jit(member_scores))
        return _JAX_BATCHED_FNS


class BatchedRankState:
    """One device dispatch per tick for a whole fleet of rankings.

    The serving problem this solves (DESIGN.md §10): a live
    :class:`~repro.selector.SelectionService` holds one ranking state
    per (job class, exclusion set) — a *fleet* of states over the same
    profiling store.  With per-state :class:`JaxRankState`\\ s a price
    tick is one kernel dispatch *per state*; ``BatchedRankState`` stacks
    the fleet over a single shared device-resident universe — hours,
    profiled mask, cost, row-min and normalized-cost buffers are stored
    **once** (they are member-independent: every member shares the
    store's mask, so the masked row minima are identical) — with the
    per-member structure reduced to a row-mask matrix (S×J) and a score
    accumulator matrix (S×C), both carrying the member axis in front.
    :meth:`reprice` then runs one batched jitted delta-update kernel
    (donated state buffers, the same power-of-4 delta bucketing as
    :class:`JaxRankState`) that refreshes every member's scores in the
    same dispatch.

    Members are added (:meth:`add_state`) and retired
    (:meth:`retire_state`) mid-stream; slot capacity grows by doubling,
    so the step kernel compiles O(log S) member-axis variants, and
    retired slots are zero-masked (they contribute nothing and are
    reused by later adds).

    Serving is per member: :meth:`ranking` materializes the full sorted
    list (memoized on the tick count), :meth:`top_k` serves the head of
    the ranking straight from the device score buffer
    (``jax.lax.top_k`` + an O(k) readback — the C-object build/sort
    never happens), and :meth:`winner` is ``top_k(1)``.

    **Contract** (:data:`SCORE_CONTRACTS` ``["jax_batched"]``): same
    float32 tolerance envelope as the per-state jax kernel — batching
    adds no drift source beyond the member-axis reduction order, which
    the shared rel/abs tolerances already cover (DESIGN.md §10).
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
        self._c_mat = (None if metrics is None
                       else metrics.counter("rank.materializations"))
        hours, mask, prices = _canonicalize_universe(hours, mask, prices,
                                                     self.job_ids)
        self._pos = _position_index(self.config_ids)
        self._job_pos = (None if self.job_ids is None else
                         {j: i for i, j in enumerate(self.job_ids)})
        self._mask = mask                     # host copy: member counts
        self._n_jobs = hours.shape[0]
        cold = _jax_state_fns()[0]
        self._step, self._member_scores = _jax_batched_fns()
        # shared read-only residents (uploaded once, never donated)
        self.d_hours = jnp.asarray(hours, dtype=jnp.float32)
        self.d_mask = jnp.asarray(mask)
        # shared donated state buffers (the universe)
        self.d_prices = jnp.asarray(prices, dtype=jnp.float32)
        (self.d_cost, self.d_row_best, self.d_norm,
         _) = cold(self.d_hours, self.d_mask, self.d_prices)
        # the member axis: slot tables + batched accumulators
        cap = self._CAPACITY_BASE if capacity is None else max(1, capacity)
        self._capacity = cap
        self._slots: "dict[Hashable, int]" = {}
        #: keys retired via :meth:`retire_state`; serving one raises
        #: :class:`NothingRankableError` (a never-registered key stays a
        #: plain ``ValueError`` — that is caller misconfiguration).
        self._retired: "set" = set()
        self._free: List[int] = list(range(cap - 1, -1, -1))
        self.d_row_masks = jnp.zeros((cap, self._n_jobs),
                                     dtype=jnp.float32)
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

    # -- member management --------------------------------------------------
    def __contains__(self, key: Hashable) -> bool:
        return key in self._slots

    @property
    def n_active(self) -> int:
        """Live member count (what one tick dispatch refreshes)."""
        return len(self._slots)

    def keys(self) -> List[Hashable]:
        return list(self._slots)

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
        self.d_scores = self.d_scores.at[slot].set(
            self._member_scores(self.d_norm, d_row))
        self._counts[slot] = counts
        self._d_finite = self._d_finite.at[slot].set(
            jnp.asarray(counts > 0))
        self._slots[key] = slot

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
        completed)."""
        prepared = _validated_delta_cols(self._pos, deltas,
                                         self._BUCKET_BASE)
        if prepared is None:
            return 0
        cols, new_prices = prepared
        (self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
         self.d_scores, moved) = self._step(
            self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
            self.d_scores, self.d_hours, self.d_mask, self.d_row_masks,
            jnp.asarray(cols), jnp.asarray(new_prices, dtype=jnp.float32))
        self.reprices += 1
        self.dispatches += 1
        return int(moved)

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

    def top_k(self, key: Hashable, k: int) -> List[RankedConfig]:
        """The head of a member's ranking served from the device score
        buffer: ``jax.lax.top_k`` on the member's row plus an O(k)
        readback — no C-object materialization, same catalog-order
        tie-break as :meth:`ranking` (see :func:`_jax_topk_fn`)."""
        with maybe_span(self._metrics, TOPK_DISPATCH_SPAN):
            slot = self._slot_of(key)
            k = _check_k(k, len(self.config_ids))
            idx, vals = _jax_topk_fn()(self.d_scores[slot],
                                       self._d_finite[slot], k)
        with maybe_span(self._metrics, TOPK_READBACK_SPAN):
            idx = np.asarray(idx)
            vals = np.asarray(vals, dtype=np.float64)
        counts = self._counts[slot]
        out = []
        for i, s in zip(idx, vals):
            n = int(counts[i])
            out.append(RankedConfig(
                self.config_ids[int(i)],
                float(s) if n else float("inf"),
                float(s) / n if n else float("inf")))
        return out

    def winner(self, key: Hashable) -> RankedConfig:
        """The member's top pick — ``top_k(key, 1)`` on device."""
        return self.top_k(key, 1)[0]
