"""Replay-harness benchmarks: recorded feeds + journal audit + dynamic eval.

    PYTHONPATH=src python benchmarks/replay_bench.py [--smoke]

Three claims are enforced (ISSUE 3 acceptance):

  * **record/replay round-trip**: capturing a ``SimulatedSpotFeed`` with
    ``record_feed`` and replaying it through ``RecordedPriceFeed``
    reproduces the identical tick stream, and re-recording the recording
    reproduces the CSV *bytes*;
  * **journal audit**: every decision journaled by a daemon run over the
    recorded history is bit-identical to a cold ``rank_dense`` at its
    reconstructed price epoch — any mismatch fails the process (exit 1),
    which is what lets CI gate on the audit;
  * **dynamic evaluation**: the replayed history yields a
    deviation-from-optimal report (realized vs per-epoch oracle vs
    static-price oracle) — the paper's Fig. 2 metric under moving prices.

Smoke mode replays the bundled ``examples/data/gcp_spot_prices.csv``
fixture over the paper universe; full mode additionally records and
replays a 10x larger synthetic universe.  Rows are written to
``BENCH_replay.json`` (override with ``BENCH_REPLAY_JSON``).
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from _bench_io import BenchRows
from repro.launch.compile_cache import enable_compile_cache
from repro.core import costmodel, spark_sim
from repro.core.trace import JobClass
from repro.market import (JournalReplayer, RecordedPriceFeed,
                          SelectionDaemon, SimulatedSpotFeed, record_feed,
                          synthetic_stream)
from repro.selector import (GcpVmCatalog, IdentityCatalog, PriceTable,
                            ProfilingStore, SelectionService)

ROWS = BenchRows("BENCH_REPLAY_JSON", "BENCH_replay.json")
emit = ROWS.emit
write_json = ROWS.write_json

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "examples", "data", "gcp_spot_prices.csv")


def _paper_daemon(feed) -> SelectionDaemon:
    trace = spark_sim.generate_trace(seed=0)
    store = ProfilingStore.from_trace(trace)
    catalog = GcpVmCatalog(trace.configs, costmodel.LinearPriceModel())
    service = SelectionService(catalog, store,
                               PriceTable.from_catalog(catalog))
    return SelectionDaemon(service, feed)


def _synth_service(n_jobs: int, n_cfgs: int, seed: int = 7
                   ) -> SelectionService:
    """A universe with the paper's structure: runtimes factor into
    per-class config affinity x per-job scale x mild noise, so class-mates
    actually predict a submitted job's behaviour (uncorrelated random
    runtimes would make any deviation metric measure noise, not the
    harness)."""
    rng = np.random.default_rng(seed)
    ids = [f"cfg{i}" for i in range(n_cfgs)]
    speed = {JobClass.A: rng.uniform(0.5, 3.0, n_cfgs),
             JobClass.B: rng.uniform(0.5, 3.0, n_cfgs)}
    store = ProfilingStore(config_ids=ids)
    for j in range(n_jobs):
        klass = JobClass.A if j % 2 else JobClass.B
        scale = rng.uniform(0.2, 2.0)
        for c in range(n_cfgs):
            if rng.random() < 0.2:
                continue                      # partial profiling
            hours = scale * speed[klass][c] * rng.lognormal(0.0, 0.08)
            store.add(f"job{j}", ids[c], float(hours),
                      job_class=klass, group=f"g{j % 6}")
    table = PriceTable({c: float(rng.uniform(1.0, 30.0)) for c in ids})
    return SelectionService(IdentityCatalog(ids), store, table)


def bench_record_roundtrip(n_cfgs: int = 256, ticks: int = 200,
                           seed: int = 5) -> None:
    rng = np.random.default_rng(seed)
    base = {f"c{i}": float(rng.uniform(0.5, 20.0)) for i in range(n_cfgs)}
    t0 = time.perf_counter()
    text = record_feed(SimulatedSpotFeed(base, seed=seed,
                                         change_fraction=0.05), ticks)
    us_record = (time.perf_counter() - t0) / ticks * 1e6
    t0 = time.perf_counter()
    feed = RecordedPriceFeed.loads(text)
    us_load = (time.perf_counter() - t0) * 1e6
    # replaying the replay is the identity on the bytes
    identical = record_feed(feed, ticks) == text
    # and the recording equals a fresh same-seed simulation, batch for batch
    fresh = SimulatedSpotFeed(base, seed=seed, change_fraction=0.05)
    matches = all(feed.poll(t) == fresh.poll(t) for t in range(ticks))
    emit(f"record_roundtrip_{n_cfgs}x{ticks}t", us_record,
         f"bytes={len(text)};load_us={us_load:.1f};"
         f"rerecord_byte_identical={identical};"
         f"matches_fresh_sim={matches}")
    if not (identical and matches):
        raise SystemExit("record/replay round-trip violated")


def bench_journal_audit(daemon: SelectionDaemon, n_events: int, seed: int,
                        label: str, job_ids=None) -> None:
    jobs = job_ids if job_ids is not None else daemon.service.store.job_ids
    daemon.run(synthetic_stream(jobs, n_events, seed=seed,
                                tick_fraction=0.15))
    journal = daemon.journal_dump()
    replayer = JournalReplayer(daemon.service.store, journal)
    t0 = time.perf_counter()
    audit = replayer.audit()
    dt = time.perf_counter() - t0
    emit(f"journal_audit_{label}", dt / max(1, audit.decisions) * 1e6,
         f"decisions={audit.decisions};ticks={audit.ticks};"
         f"rejected={audit.rejected};mismatches={len(audit.mismatches)};"
         f"journal_bytes={len(journal)}")
    if not audit.ok:
        for m in audit.mismatches[:5]:
            print(f"MISMATCH seq={m.seq} job={m.job_id} field={m.field} "
                  f"journaled={m.journaled!r} replayed={m.replayed!r}",
                  file=sys.stderr)
        raise SystemExit(
            f"journal audit failed: {len(audit.mismatches)} mismatches")

    t0 = time.perf_counter()
    ev = replayer.evaluate()
    dt = time.perf_counter() - t0
    emit(f"dynamic_eval_{label}", dt * 1e6,
         f"mean_deviation={ev.mean_deviation:.4f};"
         f"max_deviation={ev.max_deviation:.4f};"
         f"static_mean_deviation={ev.static_mean_deviation:.4f};"
         f"skipped={ev.skipped};"
         f"beats_static={ev.mean_deviation < ev.static_mean_deviation}")


def main(smoke: bool = False) -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    bench_record_roundtrip(64 if smoke else 256, 50 if smoke else 200)

    # the bundled fixture over the paper universe (the CI smoke)
    trace_jobs = [j.name for j in spark_sim.generate_trace(seed=0).jobs]
    daemon = _paper_daemon(RecordedPriceFeed.load(FIXTURE))
    bench_journal_audit(daemon, 400, seed=3, label="paper_fixture",
                        job_ids=trace_jobs)

    if not smoke:
        svc = _synth_service(24, 1_000)
        feed = RecordedPriceFeed.loads(record_feed(
            SimulatedSpotFeed(dict(svc.price_source.items()), seed=7,
                              change_fraction=0.01), 400))
        bench_journal_audit(SelectionDaemon(svc, feed), 3_000, seed=7,
                            label="synth_24x1000")
    write_json()


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
