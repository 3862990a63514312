"""The selector's served path while test-job executions land in the store:
``selector_frontend``'s saturating tick loop, with each tick also
carrying execution records that ``ServeFrontend.add_profiles`` hands to
the tick thread.

Set-up generates the deployment from the seed, fills the store with each
job's initial shapes (``gen.records``), registers the live routes and
runs the warm-up ticks, which carry records too, so the fleet's ingest
step compiles there.  In the window the tick thread polls the replayed
feed (the poll of tick ``t`` hands the front end tick ``t``'s records),
ingests the records, reprices the fleet and publishes a snapshot, the
feed never blocking.  The benchmark stamps, from its own code, what
``selector_frontend`` stamps, and a ``jax.profiler.TraceAnnotation``
around ``SelectionService.ingest`` (``bench.ingest``, with the price
epoch its tick makes).  Nothing is synced that the program does not
sync, traced or not: the ingest step's device time is read by its
program from the trace (``layer_metrics/ingest_roofline.py``).

After the window the published heads and the fleet's final scores are
held to the epoch-aware reference (``ingest_reference.py``), and every
record the window's ticks carried is looked up in the journal and in
the final store.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.trace import JobClass
from repro.market import ServeFrontend
from repro.selector import (IdentityCatalog, PriceTable, ProfilingStore,
                            SelectionService)

import cells
import ingest_reference as inref
import ingest_work as iwork
from gen import deployment as gendep
from gen import records as genrec
from gen.spot_walk import spot_batches

sf = cells.driver("selector_frontend")

#: program counters read over the window (service, store registries)
COUNTERS = ("rank.cold_rebuilds", "rank.ingest_batches",
            "service.ingest_fallbacks")


class IngestFeed(sf.ReplayFeed):
    """``ReplayFeed`` whose poll of tick ``t`` first hands the front end
    the records tick ``t`` carries (``per_tick`` of the pool, replayed
    cyclically)."""

    def __init__(self, ids, batches, warm, record_cells, per_tick):
        super().__init__(ids, batches, warm, None)
        self.record_cells = record_cells
        self.per_tick = per_tick
        self.frontend = None

    def poll(self, t: int):
        n = len(self.record_cells)
        for i in range(t * self.per_tick, (t + 1) * self.per_tick):
            self.frontend.add_profiles(self.record_cells[i % n])
        return super().poll(t)


class IngestService(sf.BenchService):
    """``bench.ingest`` around each ingest."""

    def ingest(self, cells):
        with TraceAnnotation("bench.ingest", epoch=self.price_epoch + 1):
            return super().ingest(cells)


@dataclasses.dataclass
class IngestRun(sf.Run):
    """``selector_frontend.Run`` plus what the ingest readers need."""

    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    trace_dir: Optional[str] = None       # the profiler's, --trace 1
    _ingest_work: object = None

    def ingest_work(self, epochs: Sequence[int]) -> Dict[int, object]:
        return self._ingest_work(epochs)


def build_store(dep, start: np.ndarray, cols_of_shape) -> ProfilingStore:
    ids = dep.config_ids
    store = ProfilingStore(config_ids=ids)
    for j, job in enumerate(dep.job_ids):
        cells_j = [(job, ids[c], float(dep.shape_hours[j, s]))
                   for s in start[j].tolist()
                   for c in cols_of_shape[s].tolist()]
        first, rest = cells_j[0], cells_j[1:]
        store.add(*first, job_class=JobClass(dep.job_class[j]),
                  group=dep.job_group[j])
        store.add_cells(rest)
    return store


def _counters(*regs) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for reg in regs:
        out.update(reg.snapshot()["counters"])
    return out


def run(cell, seed: int, seconds: float, window, devices,
        t_start: float) -> IngestRun:
    if not (hasattr(ServeFrontend, "add_profiles")
            and hasattr(SelectionService, "ingest")):
        raise RuntimeError("this program cannot take profile records "
                           "while serving (no ServeFrontend.add_profiles "
                           "or SelectionService.ingest): nothing ran")
    spec, mix = cell.config, cell.traffic
    dep = gendep.build(spec, seed)
    start = genrec.initial(spec, dep.n_jobs, seed)
    recs = genrec.pool(spec, mix["records"], start, seed)
    per_tick = mix["records"]["per_tick"]
    ticks = mix["ticks"]
    warm = mix["warm_ticks"]
    batches = spot_batches(dep.base_prices, dep.spot_cols, dep.region_of_col,
                           dep.n_regions, ticks["pool"], ticks,
                           gendep.rng_for(seed, gendep.STREAM_WALK))
    ids = dep.config_ids
    cols_of_shape = [np.flatnonzero(dep.shape_of_col == s)
                     for s in range(dep.shape_hours.shape[1])]
    record_cells = [[(dep.job_ids[r.job], ids[c], r.hours)
                     for c in cols_of_shape[r.shape].tolist()] for r in recs]

    store = build_store(dep, start, cols_of_shape)
    service = IngestService(IdentityCatalog(ids), store,
                            PriceTable(dict(zip(ids, dep.base_prices))),
                            backend=spec["backend"],
                            serve_top_k=spec["serve_top_k"])
    feed = IngestFeed(ids, batches, warm, record_cells, per_tick)
    fe = sf.BenchFrontend(service, feed, workers=spec["workers"],
                          queue_capacity=spec["queue_capacity"])
    feed.frontend = fe
    fe.warm([sf._submission(f"warm-{r}", dep.routes[r]) for r in dep.live0])
    for _ in range(warm):
        fe.step_tick()

    reg = service.metrics
    window.begin()
    with TraceAnnotation("bench.window"):
        before = sf._span_totals(reg)
        c_before = _counters(reg, store.metrics)
        t0 = time.perf_counter()
        feed.open(t0)
        fe.start()
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        t1 = time.perf_counter()
        fe.ticks = fe.ticker.tick_count       # stop ticking
        after = sf._span_totals(reg)
        c_after = _counters(reg, store.metrics)
    window.end()

    stats = fe.shutdown()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)

    pubs_t = np.asarray([t for t, _ in fe.publications])
    pubs_tick = np.asarray([s.tick for _, s in fe.publications])
    window_ticks = np.arange(warm, stats.ticks)
    first = np.searchsorted(pubs_tick, window_ticks, side="left")
    tick_pub = np.where(first < pubs_t.size,
                        pubs_t[np.minimum(first, pubs_t.size - 1)], np.nan)
    in_window = (tick_pub >= t0) & (tick_pub <= t1)

    # what the program produced, read before its state is freed
    pos = {c: i for i, c in enumerate(ids)}
    route_of = {sf.route_key(r): i for i, r in enumerate(dep.routes)}
    fleet = {}
    for key in fe.snapshot.entries:
        scores = np.full(len(ids), np.inf)
        for rc in service.rank(*key):
            scores[pos[rc.config_id]] = rc.score
        fleet[route_of[key]] = scores
    epochs_ok = all(s.price_epoch == s.tick + 1 for _, s in fe.publications
                    if s.tick >= 0)
    items = sf._served_items(mix, seed, None, None, fe.publications, t0, t1,
                             pos, route_of)
    journaled = {rec["tick"]: rec["cells"] for rec in fe.shard_records(0)
                 if rec["kind"] == "profile"}
    stored, _ = store.matrix(job_ids=dep.job_ids, config_ids=ids)
    final_epoch = service.price_epoch
    n_ticks = stats.ticks
    del fe, service, store, feed
    gc.collect()

    rel_tol = spec["guarantee"]["rel_tol"]
    k = spec["serve_top_k"]

    def reference() -> inref.IngestReference:
        return inref.IngestReference(dep, start, recs, per_tick, batches)

    def check() -> Dict[str, Dict[str, float]]:
        ref = reference()
        numbers = inref.served_numbers(ref, items, k)
        numbers["fleet_score_err"] = inref.fleet_number(ref, fleet,
                                                        final_epoch)
        numbers["lost_answers"] = 0
        numbers["unpublished_ticks"] = 0
        numbers["epoch_mismatches"] = 0 if epochs_ok else 1
        numbers["unapplied_records"] = _unapplied(
            ref, n_ticks, journaled, stored, record_cells, per_tick)
        limits = {"head_score_err": rel_tol, "head_rank_err": rel_tol,
                  "fleet_score_err": rel_tol, "cost_mismatches": 0,
                  "lost_answers": 0, "unpublished_ticks": 0,
                  "epoch_mismatches": 0, "unapplied_records": 0}
        return {name: {"value": float(numbers[name]), "limit": lim}
                for name, lim in limits.items()}

    members = [dep.rows_of(dep.routes[r]) for r in dep.live0]

    def ingest_work(epochs: Sequence[int]) -> Dict[int, object]:
        changes = reference().tick_changes(epochs)
        return {e: iwork.ingest_work(ch, members)
                for e, ch in changes.items()}

    return IngestRun(
        mix="saturate", seconds=seconds, setup_s=t0 - t_start, t0=t0, t1=t1,
        tick_due=np.zeros(0), tick_pub=tick_pub, sub_due=np.zeros(0),
        sub_done=np.zeros(0), sub_shed=np.zeros(0, dtype=bool),
        lateness=np.zeros(0), spans=sf._span_window(before, after),
        attempted=int(in_window.sum()), failed=0,
        memory_peak_bytes=int(peak),
        counters={n: c_after.get(n, 0) - c_before.get(n, 0)
                  for n in COUNTERS},
        _check=check, trace_dir=window.log_dir,
        _ingest_work=ingest_work)


def _unapplied(ref: inref.IngestReference, n_ticks: int,
               journaled: Dict[int, List], stored: np.ndarray,
               record_cells, per_tick: int) -> int:
    """Records the ticks carried that the journal or the final store
    lacks: a tick whose ``profile`` record is missing or holds other
    cells counts its records; a (job, shape) whose final runtime in the
    store is not the last one written counts once."""
    n = len(record_cells)
    missing = 0
    for t in range(n_ticks):
        want = [list(c) for i in range(t * per_tick, (t + 1) * per_tick)
                for c in record_cells[i % n]]
        if journaled.get(t) != want:
            missing += per_tick
    for _, _, hours in ref.walk([n_ticks]):
        want = hours[:, ref.shape_of_col]
        wrong = ~((want == stored) | (np.isnan(want) & np.isnan(stored)))
        shapes = {(j, int(ref.shape_of_col[c]))
                  for j, c in zip(*np.nonzero(wrong))}
        missing += len(shapes)
    return missing
