"""Unified, substrate-agnostic resource selection (the Flora pipeline).

This package is the single public API for cloud/accelerator resource
selection.  The paper's insight — normalized-cost ranking over a profiling
trace is substrate-agnostic (§II) — is realised as four layers:

  catalog  -- :class:`ResourceCatalog`: the ordered universe of selectable
              configurations (GCP VM clusters, TPU slices, ...), each with
              an id, resource totals and an hourly cost under a price source;
  store    -- :class:`ProfilingStore`: dense (job x config) runtime-hours
              matrices with incremental insert, partial-profiling masks and
              versioned JSONL persistence;
  rank     -- :func:`rank_dense`: the vectorized normalized-cost ranking
              (runtime matrix x price vector, row-normalize, column-sum);
              :class:`RankState` keeps the intermediates alive for
              incremental repricing under streaming price deltas;
  service  -- :class:`SelectionService`: ``submit(job, annotation) ->
              Decision`` with per-(class, price-epoch) ranking caches and
              ``reprice(deltas)`` for live :class:`PriceTable` sources.

The live-market layer on top of this package — streaming price feeds,
the tick loop, the continuous selection daemon and the migration advisor
— lives in :mod:`repro.market` (DESIGN.md §6).

The legacy entry points (:class:`repro.core.flora.Flora`,
:class:`repro.core.tpu_flora.TpuFlora`) remain as thin adapters over this
package; new substrates should implement :class:`ResourceCatalog` directly.
See DESIGN.md for the full architecture.
"""
from repro.selector.catalog import (BaseCatalog, GcpVmCatalog,
                                    IdentityCatalog, PriceTable,
                                    ResourceCatalog, TpuSliceCatalog)
from repro.selector.rank import (BACKENDS, FLEET_BACKENDS,
                                 BackendUnavailableError, BatchedRankState,
                                 NothingRankableError, RankedConfig,
                                 RankState, SCORE_CONTRACTS, ScoreContract,
                                 backend_available, rank_dense, rank_pairs,
                                 score_contract)
from repro.selector.pallas_rank import PallasBatchedRankState
from repro.selector.sharded import ShardedBatchedRankState
from repro.selector.store import ProfilingStore
from repro.selector.service import Decision, SelectionService

__all__ = [
    "BACKENDS", "BackendUnavailableError", "BaseCatalog",
    "BatchedRankState", "Decision", "FLEET_BACKENDS", "GcpVmCatalog",
    "IdentityCatalog", "NothingRankableError", "PallasBatchedRankState",
    "PriceTable", "ProfilingStore", "RankState",
    "RankedConfig", "ResourceCatalog", "SCORE_CONTRACTS", "ScoreContract",
    "SelectionService", "ShardedBatchedRankState", "TpuSliceCatalog",
    "backend_available", "rank_dense", "rank_pairs", "score_contract",
]
