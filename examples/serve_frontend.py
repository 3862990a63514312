"""Concurrent serving end-to-end: tick thread + snapshot workers + merge.

    PYTHONPATH=src python examples/serve_frontend.py --workers 4

A recorded spot market (captured from a deterministic
:class:`repro.market.SimulatedSpotFeed`) plays out on the
:class:`repro.market.ServeFrontend`'s tick thread, which owns all
repricing and publishes an immutable per-tick snapshot of every live
selection's top-k head; N workers serve submissions lock-free off the
latest snapshot while a 1 ms ``on_decision`` callback stands in for the
client-reply round-trip (DESIGN.md §11).  At the end the worker-sharded
journals are merged into one deterministic v2 journal and handed to the
unmodified :class:`repro.market.JournalReplayer` — the audit holds the
concurrent run to the same bar as the single-threaded daemon.
"""
import argparse
import time

from repro.core.trace import JobClass
from repro.market import (JournalReplayer, RecordedPriceFeed, ServeFrontend,
                          SimulatedSpotFeed, Submission, record_feed)
from repro.selector import (IdentityCatalog, PriceTable, ProfilingStore,
                            SelectionService)


def build_universe(n_jobs=12, n_cfgs=24):
    ids = [f"c{i}" for i in range(n_cfgs)]
    store = ProfilingStore(config_ids=ids)
    for j in range(n_jobs):
        klass = JobClass.A if j % 2 else JobClass.B
        for i, c in enumerate(ids):
            store.add(f"j{j}", c, 0.1 + ((j * 13 + i * 7) % 29) / 8.0,
                      job_class=klass, group=f"g{j % 4}")
    base = {c: 1.0 + (i * 11 % 17) for i, c in enumerate(ids)}
    return store, ids, base


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--submissions", type=int, default=300)
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax_batched", "jax_sharded",
                             "jax_pallas"],
                    help="ranking backend (default: numpy)")
    args = ap.parse_args()

    store, ids, base = build_universe()
    feed = RecordedPriceFeed.loads(record_feed(
        SimulatedSpotFeed(base, seed=args.seed, change_fraction=0.5),
        args.ticks))
    service = SelectionService(IdentityCatalog(ids), store,
                               PriceTable(base), backend=args.backend,
                               serve_top_k=3)

    selections = [("j1", None), ("j2", None), ("j3", None),
                  ("j4", None), ("j1", ("g2", "g3")), ("j2", ("g1",))]
    subs = [Submission(job, exclude_groups=excl)
            for job, excl in (selections[i % len(selections)]
                              for i in range(args.submissions))]

    fe = ServeFrontend(service, feed, workers=args.workers,
                       queue_capacity=len(subs) + 1,
                       on_decision=lambda d: time.sleep(0.001))
    fe.warm(subs[:len(selections)])
    print(f"serving {len(subs)} submissions across {args.workers} "
          f"workers while {args.ticks} recorded ticks play out...")
    with fe:
        t0 = time.perf_counter()
        for sub in subs:
            fe.submit(sub)
        fe.drain()
        dt = time.perf_counter() - t0
        fe.await_ticks()

    stats = fe.stats()
    print(f"\n{stats.decisions} decisions + {stats.rejected} rejections "
          f"in {dt:.2f}s ({len(subs) / dt:.0f} subs/s), "
          f"{stats.shed} shed, {stats.forwarded} forwarded")
    print(f"market: {stats.ticks} ticks, {stats.epochs} price epochs, "
          f"{stats.snapshots} snapshots published, "
          f"{stats.feed_errors} feed errors")
    print(f"accounting closed: {stats.accounted}")

    journal = fe.journal_dump()
    replayer = JournalReplayer(store, journal)
    audit = replayer.audit()
    lag = [d.price_epoch for d in replayer.decisions()]
    print(f"\nmerged journal: {len(journal.splitlines()) - 1} records, "
          f"decisions span epochs {min(lag)}..{max(lag)}")
    print(f"audit ({replayer.backend}): "
          f"{'OK' if audit.ok else 'FAILED'} — {audit.decisions} "
          f"decisions cold-re-ranked at their stamped epochs, "
          f"{len(audit.drift)} within-contract drift records")

    # the run's telemetry, read off the shared registry (DESIGN.md §12);
    # serve spans are sampled 1-in-span_sample per worker shard
    reg = fe.metrics_registry

    def us(v):
        return "      -" if v is None else f"{v * 1e6:7.0f}"

    print(f"\ntelemetry (serve spans sampled 1/{fe.span_sample}):")
    print("  span            spans   p50 us   p99 us")
    for name in ("tick.total", "serve.worker"):
        h = reg.histogram(name)
        print(f"  {name:<13} {h.count:7d}  {us(h.quantile(0.50))}  "
              f"{us(h.quantile(0.99))}")
    offered = stats.submitted + stats.shed
    print(f"  shed rate: {stats.shed / max(offered, 1):.1%} "
          f"({stats.shed}/{offered} offered), reprice kernel dispatches: "
          f"{service.reprice_dispatches}")


if __name__ == "__main__":
    main()
