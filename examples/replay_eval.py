"""Replay evaluation: deviation-from-optimal under *dynamic* prices.

    PYTHONPATH=src python examples/replay_eval.py
    PYTHONPATH=src python examples/replay_eval.py --record  # refresh fixture

How this maps to the paper
--------------------------
The paper's headline result (§III-C, Fig. 2) is an evaluation metric:
over 180 Spark executions, Flora's selections deviate less than 6% on
average from the cost-optimal cluster configuration, across a sweep of
*static* price structures.  This harness re-runs that judgment under
prices that move while jobs are being submitted:

  1. the regenerated 180-execution trace (Table I x Table II) backs a
     live ``SelectionService`` whose price source is a mutable
     ``PriceTable``;
  2. a **recorded price history** (``examples/data/gcp_spot_prices.csv``,
     a captured simulation of spot walks with a discount window and an
     eviction spike — regenerate with ``--record``) streams deltas into
     the ``SelectionDaemon`` while the paper's jobs are re-submitted;
  3. the daemon's decision journal is then **replayed**:
     ``JournalReplayer.audit`` reconstructs the price epoch of every
     decision and verifies each journaled selection is bit-identical to
     a cold ``rank_dense`` at that epoch (the reprice path's end-to-end
     consistency check);
  4. ``JournalReplayer.evaluate`` scores the history: realized cost of
     each selection vs a per-epoch oracle (sees the full runtime/price
     matrix at that epoch — the moving equivalent of the paper's
     "cost-optimal configuration") and vs a static-price oracle (picked
     once under the base prices, pays the live prices — what a
     selector that ignores the market would have done).

The gap between ``mean deviation`` and ``static-oracle deviation`` is
the value of repricing: Fig. 2's x-axis varied the price *structure*
statically; here the structure varies per epoch and Flora tracks it.
"""
import argparse
import os
import sys

from repro.core import costmodel, spark_sim
from repro.core.trace import JobClass
from repro.market import (JournalReplayer, MarketEvent, RecordedPriceFeed,
                          SelectionDaemon, SimulatedSpotFeed, record_feed,
                          synthetic_stream)
from repro.selector import (GcpVmCatalog, PriceTable, ProfilingStore,
                            SelectionService)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "gcp_spot_prices.csv")


def build_service(backend="numpy"):
    """The paper universe (Tables I x II) behind a live price table."""
    trace = spark_sim.generate_trace(seed=0)
    store = ProfilingStore.from_trace(trace)
    catalog = GcpVmCatalog(trace.configs, costmodel.LinearPriceModel())
    service = SelectionService(catalog, store,
                               PriceTable.from_catalog(catalog),
                               backend=backend)
    return trace, service


def record_fixture(service, path: str, ticks: int = 40) -> None:
    """Capture the reference simulated market to the bundled CSV."""
    base = {c: service.price_source[c] for c in service.catalog.ids()}
    sim = SimulatedSpotFeed(
        base, seed=11, change_fraction=0.25, volatility=0.08,
        events=[MarketEvent("us-central1", start_tick=8, duration=10,
                            factor=0.55, kind="discount"),
                MarketEvent("europe-west3", start_tick=20, duration=6,
                            factor=2.5, kind="eviction")])
    record_feed(sim, ticks, path)
    print(f"recorded {ticks} ticks -> {path}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prices", default=FIXTURE,
                    help="recorded-price CSV (default: bundled fixture)")
    ap.add_argument("--events", type=int, default=400)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--record", action="store_true",
                    help="regenerate the bundled fixture and exit")
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax_batched", "jax_sharded",
                             "jax_pallas"],
                    help="ranking backend (default: numpy); a fleet "
                         "backend serves the float32 device path and the "
                         "audit runs in tolerance mode (DESIGN.md §9)")
    args = ap.parse_args()

    trace, service = build_service(backend=args.backend)
    if args.record:
        record_fixture(service, args.prices)
        return 0

    feed = RecordedPriceFeed.load(args.prices)
    print(f"recorded history: {feed.ticks} ticks, "
          f"{len(feed.config_ids())} configs quoted")
    daemon = SelectionDaemon(service, feed)
    stats = daemon.run(synthetic_stream(
        [j.name for j in trace.jobs], args.events, seed=args.seed,
        tick_fraction=0.15))
    print(f"served {stats.events} events: {stats.decisions} decisions, "
          f"{stats.epochs} price epochs, {stats.deltas} deltas "
          f"({service.reprice_refreshes} incremental refreshes)")

    replayer = JournalReplayer(service.store, daemon.journal_dump())
    audit = replayer.audit()
    mode = "bit-identical" if audit.contract.bit_identical else \
        "within tolerance"
    print(f"\njournal audit ({replayer.backend} backend, "
          f"{'exact' if audit.contract.bit_identical else 'tolerance'} "
          f"mode): {audit.decisions} decisions re-ranked cold at "
          f"{audit.ticks} reconstructed epochs -> "
          f"{f'all {mode}' if audit.ok else 'MISMATCH'}")
    if audit.drift:
        scores = sum(1 for d in audit.drift if d.field == "score-drift")
        ties = sum(1 for d in audit.drift if d.field == "winner-tie")
        print(f"  float32 drift surfaced (within contract): "
              f"{scores} score drifts, {ties} near-tie winner swaps")
    if not audit.ok:
        for m in audit.mismatches[:5]:
            print(f"  seq {m.seq} job {m.job_id}: {m.field} journaled "
                  f"{m.journaled!r} != replayed {m.replayed!r}")
        return 1

    ev = replayer.evaluate()
    print(f"\ndeviation from the per-epoch cost optimum "
          f"({len(ev.outcomes)} decisions, paper's static bar: <6%):")
    print(f"  Flora (live repricing):  mean {ev.mean_deviation:7.2%}   "
          f"max {ev.max_deviation:7.2%}")
    print(f"  static-price oracle:     mean {ev.static_mean_deviation:7.2%}"
          f"   (picked once at base prices)")
    print(f"  realized ${ev.realized_total:.2f} vs oracle "
          f"${ev.oracle_total:.2f} vs static ${ev.static_total:.2f}")
    for klass in (JobClass.A, JobClass.B):
        devs = [o.deviation for o in ev.outcomes if o.job_class is klass]
        if devs:
            print(f"  class {klass.value}: mean "
                  f"{sum(devs) / len(devs):7.2%} over {len(devs)} decisions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
