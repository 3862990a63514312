"""Fleet repricing through the fused Pallas delta-rank kernel
(the ``jax_pallas`` backend).

:class:`PallasBatchedRankState` serves the same fleet API as
:class:`~repro.selector.rank.BatchedRankState` — member slots,
one dispatch per tick, per-member serving — but the tick itself is ONE
``pl.pallas_call`` (:mod:`repro.kernels.rank_delta`) instead of the
two-matmul + separate mask/min/norm XLA sequence.  The resident
universe shrinks accordingly (DESIGN.md §14): no cost or norm matrix
lives on device — both are recomputed in-stream from the read-only
``hours``/``mask`` residents and the price vector, which float32 IEEE
elementwise ops make bit-identical to what a stored matrix would hold.
Per-tick state is the price vector, the masked row minima and the
member score accumulators.

Two structural differences from the XLA delta path, both
simplifications:

* **no delta bucketing** — the kernel streams the whole universe every
  tick anyway, so deltas arrive as a dense ``(1, C)`` price vector plus
  a changed-column mask: one compiled shape total (vs O(log C)
  buckets), and duplicate deltas are idempotent *by construction*
  rather than by ``.set`` semantics;
* **padded job axis** — J is padded host-side to the tile size with
  ``mask=False`` rows (invisible: masked cells normalize to 0 and an
  all-``inf`` row minimum never registers as a handoff), so the kernel
  grid divides evenly.

The contract story carries over unchanged: ``jax_pallas`` registers
the same float32 tolerance envelope as the jax family
(:data:`~repro.selector.rank.SCORE_CONTRACTS`), so journals written
under it replay through the unmodified ``JournalReplayer.audit``
tolerance mode.

:meth:`PallasBatchedRankState.reprice_with_heads` exposes the fused
reprice+top-k variant — the tick *and* every member's k-head in a
single kernel launch (single C tile only).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Hashable, List, Mapping, Optional, \
    Sequence, Tuple, Union

import numpy as np

from repro.selector.rank import (
    _HAVE_JAX,
    BackendUnavailableError,
    BatchedRankState,
    RankedConfig,
    SCORE_CONTRACTS,
    _canonicalize_universe,
    _check_k,
    _position_index,
    _validated_deltas,
)
from repro.obs import MetricsRegistry

if _HAVE_JAX:
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import _interpret
    from repro.kernels.rank_delta import (VMEM_LIMIT_BYTES, fused_vmem_bytes,
                                          rank_delta_fns)
    from repro.selector.rank import (_cost_of, _fleet_matmul, _fold_rows,
                                     _ingest_universe, _norm_of,
                                     _unpack_cells)

__all__ = ["PallasBatchedRankState"]


if _HAVE_JAX:
    # small helpers beside the fused tick (cold row minima, a new
    # member's accumulators, the profile ingest), jitted once under a
    # lock -- the same double-checked discipline as the rank.py
    # singletons and rank_delta_fns()
    _HELPER_FNS: Optional[Tuple[Any, Any, Any]] = None
    _HELPER_LOCK = threading.Lock()

    def _helper_fns() -> Tuple[Any, Any, Any]:
        global _HELPER_FNS
        if _HELPER_FNS is None:
            with _HELPER_LOCK:
                if _HELPER_FNS is None:
                    def cold_row_best(hours, mask, prices):
                        cost = jnp.where(mask, hours * prices, jnp.inf)
                        return jnp.min(cost, axis=1, keepdims=True)

                    def member_scores(hours, mask, prices, row_best,
                                      row_mask):
                        # the member's accumulators from the *implied*
                        # norm matrix — recomputed exactly as the fused
                        # kernel recomputes it in-stream
                        norm = jnp.where(mask, (hours * prices) / row_best,
                                         0.0)
                        return _fleet_matmul(row_mask, norm)

                    def ingest(hours, mask, row_best, scores, finite,
                               prices, row_masks, idx, vals):
                        rows, cols, new_hours, trows, row_w = \
                            _unpack_cells(idx, vals)
                        # the touched rows' normalised costs before the
                        # write, implied as the kernel streams them
                        old = _norm_of(
                            _cost_of(hours[trows], mask[trows], prices),
                            mask[trows], row_best[trows, 0])
                        (hours, mask, _, t_best,
                         t_norm) = _ingest_universe(hours, mask, prices,
                                                    rows, cols, new_hours,
                                                    trows)
                        scores, finite = _fold_rows(
                            scores, finite, row_masks, trows, row_w,
                            t_norm - old, mask[trows])
                        return (hours, mask,
                                row_best.at[trows, 0].set(t_best), scores,
                                finite)

                    _HELPER_FNS = (jax.jit(cold_row_best),
                                   jax.jit(member_scores), jax.jit(ingest))
        return _HELPER_FNS


class PallasBatchedRankState(BatchedRankState):
    """One *fused-kernel* dispatch per tick for a whole fleet.

    Drop-in for :class:`~repro.selector.rank.BatchedRankState` (same
    member management, serving and validation surface — inherited), but
    :meth:`reprice` runs :func:`repro.kernels.rank_delta.fused_reprice`
    and the resident universe is the reduced set described in the
    module docstring.  ``block_j``/``block_c`` pick the kernel tiling
    (defaults: 8-row job tiles, a single C tile); the job axis is
    padded to a ``block_j`` multiple with masked-off rows.

    A fused tick holds its member-by-``block_c`` tiles in the chip's
    VMEM (:func:`~repro.kernels.rank_delta.fused_vmem_bytes`): building
    the state, growing its member capacity and a fused top-k tick each
    raise ``ValueError`` naming the limit when the tiles would not fit,
    rather than leaving the chip's compiler to refuse the tick.

    **Contract** (:data:`SCORE_CONTRACTS` ``["jax_pallas"]``): the jax
    float32 tolerance envelope.  The fused kernel's changed-column
    re-reductions and unchanged-column delta folds reorder float32 sums
    relative to the XLA path, which is exactly the drift source the
    rel/abs tolerances already cover — and a tick with no handoffs is
    drift-free here for the same exact-zero reason (DESIGN.md §14).
    """

    backend = "jax_pallas"
    contract = SCORE_CONTRACTS["jax_pallas"]
    _BLOCK_J = 8

    def __init__(self, hours: np.ndarray, mask: np.ndarray,
                 prices: np.ndarray, config_ids: Sequence[Hashable],
                 job_ids: Optional[Sequence[Hashable]] = None,
                 capacity: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 block_j: Optional[int] = None,
                 block_c: Optional[int] = None):
        if not _HAVE_JAX:
            raise BackendUnavailableError(
                "PallasBatchedRankState requires jax; use RankState "
                "(numpy) when it is not installed")
        self.config_ids = list(config_ids)
        self.job_ids = list(job_ids) if job_ids is not None else None
        self._metrics = metrics
        hours, mask, prices = _canonicalize_universe(hours, mask, prices,
                                                     self.job_ids)
        self._pos = _position_index(self.config_ids)
        self._job_pos = (None if self.job_ids is None else
                         {j: i for i, j in enumerate(self.job_ids)})
        self._mask = mask.copy()              # host copy: member counts
        n_cfgs = len(self.config_ids)
        #: true (unpadded) job count — what ``rows=`` validates against
        self._n_true_jobs = hours.shape[0]
        self._block_j = self._BLOCK_J if block_j is None else block_j
        self._block_c = n_cfgs if block_c is None else block_c
        # pad the job axis to a block_j multiple with invisible rows:
        # mask=False everywhere, so their cells normalize to 0 and the
        # all-inf row minimum can never register as a handoff
        pad = (-self._n_true_jobs) % self._block_j
        if pad:
            hours = np.concatenate(
                [hours, np.ones((pad, n_cfgs), hours.dtype)])
            mask = np.concatenate(
                [mask, np.zeros((pad, n_cfgs), bool)])
        #: padded job count — the kernel-facing row axis (the inherited
        #: slot machinery sizes row masks off ``_n_jobs``)
        self._n_jobs = hours.shape[0]
        # read-only residents (uploaded once)
        self.d_hours = jnp.asarray(hours, dtype=jnp.float32)
        self.d_mask = jnp.asarray(mask)
        # per-tick resident state: prices, row minima, accumulators —
        # no cost/norm matrix (recomputed in-stream, DESIGN.md §14).
        # The host float32 price mirror builds each tick's dense price
        # vector without a device readback; float32 so host and device
        # quotes can never disagree by a rounding.
        self._host_prices = np.asarray(prices,
                                       dtype=np.float32).reshape(1, -1)
        self.d_prices = jnp.asarray(self._host_prices)
        self.d_row_best = _helper_fns()[0](self.d_hours, self.d_mask,
                                           self.d_prices)
        # the member axis: slot tables + batched accumulators (the
        # inherited add/retire/grow machinery manages these)
        cap = self._CAPACITY_BASE if capacity is None else max(1, capacity)
        self._check_vmem(cap, heads=False)
        self._init_members(cap)

    # -- member management (only the pieces the padding touches) ------------
    def _check_vmem(self, capacity: int, heads: bool) -> None:
        need = fused_vmem_bytes(capacity, self._n_jobs, self._block_c, heads)
        if need > VMEM_LIMIT_BYTES:
            raise ValueError(
                f"jax_pallas: a fused {'top-k ' if heads else ''}tick over "
                f"{capacity} member slots x {self._block_c}-config tiles "
                f"needs ~{need / 2**20:.1f} MiB of VMEM, over the chip's "
                f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB; serve fewer members "
                f"or a smaller catalog on this backend, or use jax_batched")

    def _grow(self) -> None:
        self._check_vmem(self._capacity * 2, heads=False)
        super()._grow()

    def _rows_of(self, rows, jobs) -> np.ndarray:
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
        # validate against the TRUE job count — the padded rows are a
        # kernel-tiling artifact, never addressable by members
        if idx.size and (idx.min() < 0 or idx.max() >= self._n_true_jobs):
            raise ValueError(f"row index out of range for "
                             f"{self._n_true_jobs} jobs")
        if np.unique(idx).size != idx.size:
            raise ValueError("duplicate rows in member selection")
        return idx

    def _new_member_scores(self, d_row):
        """A new member's accumulators from the *implied* current norm
        matrix (recomputed from the residents exactly as the kernel
        streams it), so a mid-stream add is immediately in sync."""
        return _helper_fns()[1](self.d_hours, self.d_mask, self.d_prices,
                                self.d_row_best, d_row)

    def _dispatch_ingest(self, idx, vals) -> None:
        (self.d_hours, self.d_mask, self.d_row_best, self.d_scores,
         self._d_finite) = _helper_fns()[2](
            self.d_hours, self.d_mask, self.d_row_best, self.d_scores,
            self._d_finite, self.d_prices, self.d_row_masks, idx, vals)

    # -- the fused tick -----------------------------------------------------
    @property
    def prices(self) -> np.ndarray:
        """Current per-config $/h (float32 quotes lifted to float64)."""
        return self._host_prices[0].astype(np.float64)

    def _dense_tick(self, deltas) -> Optional[Tuple[np.ndarray,
                                                    np.ndarray]]:
        """Validate a delta batch and densify it: the fused kernel takes
        the full ``(1, C)`` new-price vector plus a changed-column mask
        (one compiled shape; duplicates idempotent by construction)."""
        validated = _validated_deltas(self._pos, deltas)
        if validated is None:
            return None
        cols, new_prices = validated
        newp = self._host_prices.copy()
        newp[0, cols] = new_prices.astype(np.float32)
        changed = np.zeros_like(newp)
        changed[0, cols] = 1.0
        return newp, changed

    def _tick_call(self, newp: np.ndarray, changed: np.ndarray,
                   k: Optional[int] = None):
        """The fused tick as :meth:`reprice` (``k=None``) and
        :meth:`reprice_with_heads` dispatch it: ``(jitted kernel entry,
        arguments, static keywords)``, so it can also be lowered as is."""
        reprice, heads = rank_delta_fns()
        args = (self.d_hours, self.d_mask, self.d_prices, jnp.asarray(newp),
                jnp.asarray(changed), self.d_row_best, self.d_row_masks,
                self.d_scores)
        tiles = dict(block_j=self._block_j, block_c=self._block_c,
                     interpret=_interpret())
        if k is None:
            return reprice, args, tiles
        return heads, args + (self._d_finite,), dict(tiles, k=k)

    def reprice(self, deltas: Union[Mapping[Hashable, float],
                                    Sequence[Tuple[Hashable, float]]]
                ) -> int:
        """Apply ``{config_id: new $/h}`` deltas with ONE fused Pallas
        kernel launch refreshing every member; returns #rows whose
        masked row-minimum handed off (synced to host, so a return
        means the tick's kernel has completed)."""
        dense = self._dense_tick(deltas)
        if dense is None:
            return 0
        fn, args, tiles = self._tick_call(*dense)
        self.d_scores, self.d_row_best, moved = fn(*args, **tiles)
        self.d_prices = args[3]
        self._host_prices = dense[0]
        self.reprices += 1
        self.dispatches += 1
        return int(np.asarray(moved)[0, 0])

    def reprice_with_heads(self, deltas: Union[Mapping[Hashable, float],
                                               Sequence[Tuple[Hashable,
                                                              float]]],
                           k: int
                           ) -> Tuple[int, Dict[Hashable,
                                                List[RankedConfig]]]:
        """The fused reprice+top-k tick: apply the deltas AND serve
        every live member's ``k``-head from the same single kernel
        launch (``(moved, {key: [RankedConfig]})``).  Requires the
        single-C-tile layout (``block_c == C``); an empty delta batch
        degrades to plain :meth:`top_k` serving with no dispatch."""
        k = _check_k(k, len(self.config_ids))
        dense = self._dense_tick(deltas)
        if dense is None:
            return 0, {key: self.top_k(key, k) for key in self._slots}
        self._check_vmem(self._capacity, heads=True)
        fn, args, tiles = self._tick_call(*dense, k=k)
        self.d_scores, self.d_row_best, moved, ti, tv = fn(*args, **tiles)
        self.d_prices = args[3]
        self._host_prices = dense[0]
        self.reprices += 1
        self.dispatches += 1
        ti_h = np.asarray(ti)
        tv_h = np.asarray(tv, dtype=np.float64)
        heads: Dict[Hashable, List[RankedConfig]] = {}
        for key, slot in self._slots.items():
            counts = self._counts[slot]
            out = []
            for i, s in zip(ti_h[slot], tv_h[slot]):
                n = int(counts[i])
                out.append(RankedConfig(
                    self.config_ids[int(i)],
                    float(s) if n else float("inf"),
                    float(s) / n if n else float("inf")))
            heads[key] = out
        return int(np.asarray(moved)[0, 0]), heads
