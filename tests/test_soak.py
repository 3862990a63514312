"""Daemon soak: the repo's first end-to-end stress fixture (ISSUE 5).

Drives :class:`~repro.market.SelectionDaemon` over a *long* recorded
price history — 220 ticks of a simulated spot market with a discount
window and an eviction spike, captured through
:func:`~repro.market.record_feed` so the whole run is a pure function
of the fixture bytes — with submissions interleaved across four-plus
distinct (job class, exclusion) selections, i.e. a real fleet of live
rankings.  Five legs: the numpy backend (bit-identical audit), the
batched jax fleet backend (tolerance audit + the one-dispatch-per-tick
accounting), the batched backend serving every decision via device-side
top-k (DESIGN.md §10), and the device-sharded fleet backend
(DESIGN.md §13) with and without top-k serving — the same
one-collective-dispatch-per-tick accounting over shard_map.

Beyond "the audit passes", the soak pins the *resource* story:

  * ``JournalReplayer.audit()`` reports zero out-of-envelope drift
    (``mismatches == ()``; for numpy, zero drift records at all);
  * ``ProfilingStore.realloc_count`` stays amortized-doubling-bounded;
  * the service's reprice/cache counters stay inside pinned bounds —
    every selection cold-builds exactly once, everything else is a
    cache hit or an incremental refresh, and the batched backend spends
    exactly one kernel dispatch per price epoch regardless of fleet
    size.
"""
import math

import pytest

from repro.core.trace import JobClass
from repro.market import (JournalReplayer, MarketEvent, RecordedPriceFeed,
                          SelectionDaemon, SimulatedSpotFeed, Submission,
                          Tick, make_market, record_feed)
from repro.selector import (FLEET_BACKENDS, IdentityCatalog, PriceTable,
                            ProfilingStore, SelectionService,
                            backend_available)

N_TICKS = 220
N_JOBS = 12
N_CFGS = 24


def _soak_store():
    ids = [f"c{i}" for i in range(N_CFGS)]
    store = ProfilingStore(config_ids=ids)
    for j in range(N_JOBS):
        klass = JobClass.A if j % 2 else JobClass.B
        for i, c in enumerate(ids):
            # deterministic, positive, class-correlated runtimes
            store.add(f"j{j}", c,
                      0.1 + ((j * 13 + i * 7) % 29) / 8.0
                      + (0.5 if klass is JobClass.A and i % 3 == 0
                         else 0.0),
                      job_class=klass, group=f"g{j % 4}")
    return store, ids


#: submissions cycle through SIX distinct (class, exclusion) selections:
#: two per-class defaults (each job's own group is auto-excluded, and
#: jobs of one class share groups by construction below — j1/j3 are
#: both class A but different groups) plus explicit exclusion variants.
SOAK_SELECTIONS = [
    ("j1", None),              # class A, auto-exclude g1
    ("j2", None),              # class B, auto-exclude g2
    ("j3", None),              # class A, auto-exclude g3
    ("j4", None),              # class B, auto-exclude g0
    ("j1", ("g2", "g3")),      # class A, explicit exclusions
    ("j2", ("g1",)),           # class B, explicit exclusion
]


def _soak_stream():
    """220 ticks with submissions woven between them (~2 per 3 ticks),
    cycling the six selections."""
    s = 0
    for t in range(N_TICKS):
        yield Tick()
        if t % 3 != 2:
            job, excl = SOAK_SELECTIONS[s % len(SOAK_SELECTIONS)]
            s += 1
            yield Submission(job, exclude_groups=excl)


def _recorded_market(ids):
    """A 220-tick recorded price history with mid-stream market events,
    round-tripped through the recorded-feed CSV so the soak replays a
    fixture, not a live simulation."""
    base = {c: 1.0 + (i * 11 % 17) for i, c in enumerate(ids)}
    sim = SimulatedSpotFeed(
        base, seed=42, change_fraction=0.5, volatility=0.08,
        events=[MarketEvent("us-central1", 40, 30, 0.5, "discount"),
                MarketEvent("europe-west3", 120, 20, 3.0, "eviction")])
    text = record_feed(sim, N_TICKS)
    feed = RecordedPriceFeed.loads(text)
    assert feed.ticks == N_TICKS
    return feed, base


def _assert_soak_invariants(svc, store, daemon, stats, backend,
                            serve_top_k=None):
    """The shared soak bar: audit clean with zero out-of-envelope
    drift, store growth amortized-doubling-bounded, every selection
    cold-builds exactly once, and the fleet backends spend exactly one
    kernel dispatch per price epoch — the same invariants for the calm
    recorded market and the hostile turbulence presets."""
    # -- the audit: tolerance mode for the batched fleet, bit-identical
    #    for numpy; zero out-of-envelope drift either way
    replayer = JournalReplayer(store, daemon.journal_dump())
    assert replayer.backend == backend
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.decisions == stats.decisions
    assert audit.ticks == stats.epochs
    if backend == "numpy":
        assert audit.drift == ()          # exact backend: no drift at all
        assert audit.contract.bit_identical
    else:
        assert not audit.contract.bit_identical
    if serve_top_k:
        served = replayer.decisions()
        assert served and all(d.served_via == "top_k" for d in served)

    # -- pinned resource bounds: the soak is a stress test, not just a
    #    correctness test
    # store growth stayed amortized-doubling (same idiom as the growth
    # test in test_market.py, both axes)
    assert store.realloc_count <= \
        2 * (math.ceil(math.log2(N_JOBS)) + math.ceil(math.log2(N_CFGS))) + 4
    # every distinct selection cold-builds exactly once; every other
    # submission is a cache hit or a lazy materialization of an
    # incrementally-refreshed state
    assert svc.cache_misses == len(SOAK_SELECTIONS)
    assert svc.cache_hits == stats.submissions - len(SOAK_SELECTIONS)
    # every epoch refreshed every live state incrementally — never a
    # drop-and-rebuild (the recorded feed applies all quotes through
    # reprice, so no state can ever miss an out-of-band apply)
    assert svc.reprice_refreshes >= stats.epochs    # fleet ramps up to 6
    if backend in FLEET_BACKENDS:
        # THE batching claim: one kernel dispatch per price epoch,
        # regardless of how many live rankings the tick refreshes (the
        # very first epoch predates the fleet — the stream opens with a
        # tick before any submission has built a state — so it spends
        # zero dispatches); for jax_sharded that dispatch is the single
        # collective shard_map step across every device
        assert stats.epochs - 1 <= svc.reprice_dispatches <= stats.epochs
        assert svc._batched.dispatches == svc.reprice_dispatches
    else:
        # numpy pays one update per live state per epoch
        assert svc.reprice_dispatches >= stats.epochs
    return audit


@pytest.mark.parametrize("backend,serve_top_k", [
    ("numpy", None),
    ("jax_batched", None),
    ("jax_batched", 3),
    ("jax_sharded", None),
    ("jax_sharded", 3),
])
def test_daemon_soak_long_recorded_market(backend, serve_top_k):
    if not backend_available(backend):
        pytest.skip("jax not installed")
    store, ids = _soak_store()
    feed, base = _recorded_market(ids)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend=backend, serve_top_k=serve_top_k)
    daemon = SelectionDaemon(svc, feed)
    stats = daemon.run(_soak_stream())

    # -- the stream actually stressed what it claims to stress
    assert stats.ticks == N_TICKS
    assert stats.epochs >= 180            # near-every tick moved prices
    assert stats.rejected == 0
    assert stats.decisions == stats.submissions >= 140
    if backend in FLEET_BACKENDS:
        assert svc._batched is not None
        assert svc._batched.n_active == len(SOAK_SELECTIONS)

    _assert_soak_invariants(svc, store, daemon, stats, backend,
                            serve_top_k)


@pytest.mark.parametrize("preset_name", ["eviction_storm", "flash_crash"])
@pytest.mark.parametrize("backend", ["numpy", "jax_batched"])
def test_daemon_soak_hostile_turbulent_market(preset_name, backend):
    """ISSUE 10 satellite: the 220-tick soak under the hostile
    turbulence presets — coordinated eviction storms and flash-crash/
    overshoot regime flips are exactly the markets that punish a
    selector amortizing rankings between ticks, and the soak bar
    (clean audit, pinned realloc/cache/dispatch bounds) must hold there
    too, not just under the calm recorded market."""
    if not backend_available(backend):
        pytest.skip("jax not installed")
    store, ids = _soak_store()
    base = {c: 1.0 + (i * 11 % 17) for i, c in enumerate(ids)}
    market = make_market(preset_name, base, seed=42, ticks=N_TICKS)
    feed = RecordedPriceFeed.loads(record_feed(market.raw, N_TICKS))
    assert feed.ticks == N_TICKS
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend=backend)
    daemon = SelectionDaemon(svc, feed)
    stats = daemon.run(_soak_stream())

    assert stats.ticks == N_TICKS
    assert stats.epochs >= 180            # hostile != quiet: prices move
    assert stats.rejected == 0
    assert stats.decisions == stats.submissions >= 140
    _assert_soak_invariants(svc, store, daemon, stats, backend)


def test_soak_journal_is_deterministic():
    """The soak is a fixture: same recorded market + same stream =>
    byte-identical journal (the reproducibility bar every daemon
    benchmark already enforces, now over a 220-tick recorded
    history)."""
    store, ids = _soak_store()
    feed, base = _recorded_market(ids)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend="numpy")
    daemon = SelectionDaemon(svc, feed)
    daemon.run(_soak_stream())
    store2, ids2 = _soak_store()
    feed2, base2 = _recorded_market(ids2)
    svc2 = SelectionService(IdentityCatalog(ids2), store2,
                            PriceTable(base2), backend="numpy")
    daemon2 = SelectionDaemon(svc2, feed2)
    daemon2.run(_soak_stream())
    assert daemon.journal_dump() == daemon2.journal_dump()
