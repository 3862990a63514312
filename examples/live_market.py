"""Live price market end-to-end: feed -> ticker -> daemon -> migration.

    PYTHONPATH=src python examples/live_market.py --events 400 --seed 3

A TPU mesh universe is wrapped in a mutable
:class:`repro.selector.PriceTable`; a deterministic
:class:`repro.market.SimulatedSpotFeed` (mean-reverting spot walks plus a
scheduled v5p discount window) streams price deltas into the
:class:`repro.market.SelectionDaemon`, which serves an interleaved
submission/tick stream, repricing cached rankings incrementally
(DESIGN.md §6).  At the end, the hysteresis migration advisor decides
whether a decode fleet placed at tick 0 should move under final prices.
"""
import argparse

from repro.core.costmodel import TpuPriceModel
from repro.core.tpu_flora import MeshOption, WorkloadRecord, make_service
from repro.market import (MarketEvent, SelectionDaemon, SimulatedSpotFeed,
                          should_migrate, synthetic_stream)
from repro.selector import PriceTable


def build_service(backend="numpy"):
    options = [
        MeshOption("v5e-dp256xtp1", "v5e", 256, (256, 1), ("data", "model")),
        MeshOption("v5e-dp16xtp16", "v5e", 256, (16, 16), ("data", "model")),
        MeshOption("v5p-dp16xtp16", "v5p", 256, (16, 16), ("data", "model")),
        MeshOption("v5p-dp64xtp4", "v5p", 256, (64, 4), ("data", "model")),
    ]
    speed = {"v5e-dp256xtp1": {"train_4k": 1.0, "decode_32k": 4.0},
             "v5e-dp16xtp16": {"train_4k": 1.5, "decode_32k": 1.0},
             "v5p-dp16xtp16": {"train_4k": 0.8, "decode_32k": 0.55},
             "v5p-dp64xtp4": {"train_4k": 0.7, "decode_32k": 0.9}}
    records = [WorkloadRecord(arch=a, shape=s, mesh=m, step_seconds=v)
               for a in ("lm-7b", "moe-30b")
               for m, shapes in speed.items()
               for s, v in shapes.items()]
    service = make_service(options, records, TpuPriceModel("spot"),
                           backend=backend)
    # swap the model source for a live quote table (same starting prices)
    service.set_price_source(PriceTable.from_catalog(
        service.catalog, TpuPriceModel("spot")))
    return service


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=400)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax_batched", "jax_sharded",
                             "jax_pallas"],
                    help="ranking backend (default: numpy); "
                         "jax_batched stacks every "
                         "live ranking into one batched kernel — a tick "
                         "is ONE dispatch for the whole fleet "
                         "(DESIGN.md §10)")
    ap.add_argument("--serve-top-k", type=int, default=None, metavar="K",
                    help="serve Decisions with only the top-K head of "
                         "the ranking (device-side top_k; the full "
                         "C-config sort never runs)")
    ap.add_argument("--metrics", nargs="?", const="prom", default=None,
                    choices=["prom", "json"],
                    help="dump the run's telemetry registry at exit "
                         "(DESIGN.md §12) in Prometheus text (default) "
                         "or JSON")
    args = ap.parse_args()
    if args.serve_top_k is not None and args.serve_top_k < 1:
        ap.error("--serve-top-k must be >= 1")

    service = build_service(backend=args.backend)
    service.serve_top_k = args.serve_top_k
    feed = SimulatedSpotFeed(
        dict(service.price_source.items()), seed=args.seed,
        change_fraction=0.08, volatility=0.10,
        events=[MarketEvent("europe-west3", start_tick=10, duration=25,
                            factor=0.5, kind="discount")])
    daemon = SelectionDaemon(service, feed)

    initial = service.submit("decode_32k")
    print(f"t=0 decode fleet placed on {initial.config_id} "
          f"at {initial.hourly_cost:.0f} $/h (epoch {initial.price_epoch})")

    stats = daemon.run(synthetic_stream(
        ["decode_32k", "train_4k"], args.events, seed=args.seed,
        tick_fraction=0.2))
    svc = daemon.service
    print(f"\nafter {stats.events} events: {stats.decisions} decisions, "
          f"{stats.ticks} ticks, {stats.epochs} price epochs, "
          f"{stats.deltas} deltas")
    print(f"cache: {svc.cache_hits} hits / {svc.cache_misses} misses, "
          f"{svc.reprice_refreshes} incremental refreshes in "
          f"{svc.reprice_dispatches} kernel dispatches "
          f"(epoch now {svc.price_epoch})")

    # the migration advisor below walks the ranking tail, so serve the
    # closing submission with the full list even when the tick-stream
    # Decisions were top-k heads
    service.serve_top_k = None
    final = service.submit("decode_32k")
    print(f"\ncurrent winner under live prices: {final.config_id} "
          f"at {final.hourly_cost:.0f} $/h")
    # quote savings/switch cost off the fleet's $/h under *current*
    # prices, not the rate stamped on the t=0 decision
    current_rate = service.catalog.hourly_cost(initial.config_id,
                                               service.price_source)
    advice = should_migrate(initial, final.ranking, switch_cost_hours=0.5,
                            horizon_hours=24.0,
                            current_hourly_cost=current_rate)
    verb = "MIGRATE" if advice.migrate else "STAY"
    print(f"fleet advisor: {verb} ({advice.reason})")
    if advice.migrate:
        print(f"  net saving over {advice.horizon_hours:g} h: "
              f"{advice.net_saving_usd:.2f} USD")

    journal = daemon.journal_dump().splitlines()
    print(f"\njournal: {len(journal) - 1} records "
          f"(header: {journal[0][:60]}...)")

    if args.metrics:
        # every component above (service, ticker, daemon) shares the
        # service's registry, so this is the whole run's telemetry
        print(f"\n--- metrics ({args.metrics}) ---")
        print(service.metrics.render(args.metrics), end="")


if __name__ == "__main__":
    main()
