"""Differential fused-kernel parity suite (ISSUE 9).

The fused Pallas delta-rank backend
(:class:`~repro.selector.PallasBatchedRankState`, DESIGN.md §14) must
be indistinguishable — within the jax ``ScoreContract`` — from both
the XLA-delta :class:`~repro.selector.BatchedRankState` it fuses and
the cold numpy float64 rank, per tick, at the default and at tiled
``block_j``/``block_c`` layouts (the kernel runs ``interpret=True`` on
CPU).

Also home to: the dense-delta duplicate idempotency check (the fused
path carries no bucket padding — duplicates collapse by construction),
the fused reprice+top-k head checks at the k boundaries, the
jax_pallas service/daemon integration tests and the tolerance-mode
journal audit.
"""
import numpy as np
import pytest

from repro.core.trace import JobClass
from repro.selector import (BatchedRankState, NothingRankableError,
                            PallasBatchedRankState, backend_available,
                            rank_dense, score_contract)
from test_backend_parity import assert_within_contract
from test_batched_parity import (_fleet_service, _fleet_universe,
                                 _universe_with_ties)

try:        # the property half needs hypothesis; everything else runs
            # without it
    import hypothesis
    from hypothesis import given, settings, strategies as st
    from test_batched_parity import fleet_streams
    from test_rank_properties import event_markets, _event_feed
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_jax = pytest.mark.skipif(not backend_available("jax_pallas"),
                               reason="jax not installed")

CONTRACT = score_contract("jax_pallas")

#: tiling legs: the default single-C-tile layout plus genuinely tiled
#: grids (multi-tile C exercises the phase-0 min scan across tiles;
#: n_cfgs in the seeded fleets is padded to keep block_c dividing)
TILINGS = ({}, {"block_j": 4}, {"block_j": 4, "block_c": 8})


def _assert_pallas_parity(fused, batched, members, hours, mask, live,
                          ids):
    """Every member: jax_pallas == jax_batched == numpy cold, under the
    contract; plus the device top-k head is element-wise identical to
    the member's own materialized ranking head."""
    for key, rows in members.items():
        cold = rank_dense(hours[rows], mask[rows], live, ids)
        rf = fused.ranking(key)
        assert_within_contract(rf, cold, CONTRACT)
        assert_within_contract(rf, batched.ranking(key), CONTRACT)
        k = min(3, len(ids))
        assert fused.top_k(key, k) == rf[:k]


# --- deterministic differential sweeps ---------------------------------------------

@needs_jax
@pytest.mark.parametrize("tiling", TILINGS)
@pytest.mark.parametrize("seed", range(3))
def test_pallas_fleet_within_contract_seeded(seed, tiling):
    """Seeded fleets at every tiling: after each tick, each fused-kernel
    member matches the XLA batched state and the cold numpy float64
    rank under the contract — one fused dispatch per tick.  Odd seeds
    use partial (masked) universes, so the masked-cell and padded-row
    handling is live."""
    rng, hours, mask, prices, ids, members = _fleet_universe(
        seed, n_jobs=6 + seed, n_cfgs=16, partial=seed % 2 == 0)
    fused = PallasBatchedRankState(hours, mask, prices.copy(), ids,
                                   **tiling)
    batched = BatchedRankState(hours, mask, prices.copy(), ids)
    for key, rows in members.items():
        fused.add_state(key, rows=rows)
        batched.add_state(key, rows=rows)
    live = prices.copy()
    for _ in range(5):
        k = int(rng.integers(1, len(ids)))
        cols = rng.choice(len(ids), k, replace=False)
        deltas = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                  for c in cols}
        assert fused.reprice(deltas) == batched.reprice(deltas)
        for c, p in deltas.items():
            live[int(c[1:])] = p
        _assert_pallas_parity(fused, batched, members, hours, mask,
                              live, ids)
    # the accounting the bench gates on: ONE fused kernel dispatch per
    # tick, independent of the member count
    assert fused.dispatches == fused.reprices == 5
    assert fused.n_active == len(members)


@needs_jax
def test_pallas_event_market_within_contract_deterministic():
    """Discount/eviction boundary re-quote bursts through the fused
    kernel stay within contract of cold float64 ranks for every
    member."""
    from repro.market import MarketEvent, SimulatedSpotFeed
    rng, hours, mask, prices, ids, members = _fleet_universe(
        7, n_jobs=8, n_cfgs=11, partial=False)
    base = {c: float(p) for c, p in zip(ids, prices)}
    feed = SimulatedSpotFeed(
        base, seed=5, change_fraction=0.3, volatility=0.15,
        events=[MarketEvent("us-central1", 2, 4, 0.25, "discount"),
                MarketEvent("europe-west3", 5, 3, 4.0, "eviction")])
    fused = PallasBatchedRankState(hours, mask, prices.copy(), ids)
    batched = BatchedRankState(hours, mask, prices.copy(), ids)
    for key, rows in members.items():
        fused.add_state(key, rows=rows)
        batched.add_state(key, rows=rows)
    live = prices.copy()
    for t in range(10):
        batch = feed.poll(t)
        if not batch:
            continue
        deltas = {d.config_id: d.price for d in batch}
        fused.reprice(deltas)
        batched.reprice(deltas)
        for d in batch:
            live[ids.index(d.config_id)] = d.price
        _assert_pallas_parity(fused, batched, members, hours, mask,
                              live, ids)


@needs_jax
def test_pallas_duplicate_deltas_idempotent_by_construction():
    """The fused path densifies deltas into one (1, C) price vector —
    no bucket padding exists to repeat (column, price) pairs, so a
    delta batch with duplicate config ids (last wins, like every other
    backend) and its collapsed dict form produce the SAME tick,
    bit-for-bit."""
    rng, hours, mask, prices, ids, members = _fleet_universe(
        5, n_jobs=8, n_cfgs=12)
    a = PallasBatchedRankState(hours, mask, prices.copy(), ids)
    b = PallasBatchedRankState(hours, mask, prices.copy(), ids)
    for key, rows in members.items():
        a.add_state(key, rows=rows)
        b.add_state(key, rows=rows)
    dup = [(ids[2], 9.9), (ids[5], 0.4), (ids[2], 1.1), (ids[2], 0.7)]
    collapsed = {ids[2]: 0.7, ids[5]: 0.4}
    assert a.reprice(dup) == b.reprice(collapsed)
    for key in members:
        assert np.array_equal(a.scores(key), b.scores(key))
    assert np.array_equal(a.prices, b.prices)


@needs_jax
def test_pallas_states_added_retired_and_slot_reuse():
    """Members added mid-stream sync with every prior tick; retired
    members raise the typed rankable-nothing error; a retire-all /
    re-add cycle reuses the zero-masked slots without growing capacity
    (``realloc_count`` pinned), and the revived member's scores
    bit-match a cold build."""
    rng, hours, mask, prices, ids, members = _fleet_universe(
        11, n_jobs=12, n_cfgs=17, n_members=4)
    fused = PallasBatchedRankState(hours, mask, prices.copy(), ids,
                                   capacity=4)
    live = prices.copy()

    def tick():
        k = int(rng.integers(1, len(ids)))
        cols = rng.choice(len(ids), k, replace=False)
        deltas = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                  for c in cols}
        fused.reprice(deltas)
        for c, p in deltas.items():
            live[int(c[1:])] = p

    fused.add_state("all", rows=members["all"])
    tick()
    fused.add_state("m0", rows=members["m0"])       # post-tick add
    tick()
    for key in ("all", "m0"):
        cold = rank_dense(hours[members[key]], mask[members[key]], live,
                          ids)
        assert_within_contract(fused.ranking(key), cold, CONTRACT)
    # retire-all / re-add: slots reused, capacity untouched
    assert fused.realloc_count == 0
    for key in ("all", "m0"):
        fused.retire_state(key)
    assert fused.n_active == 0
    with pytest.raises(NothingRankableError, match="retired"):
        fused.ranking("m0")
    with pytest.raises(NothingRankableError, match="retired"):
        fused.top_k("m0", 1)
    with pytest.raises(ValueError, match="unknown member"):
        fused.ranking("never-registered")
    for key in ("all", "m0"):
        fused.add_state(key, rows=members[key])
    assert fused.realloc_count == 0                 # reuse, not growth
    # the revived member bit-matches a cold build at the live prices
    cold_state = PallasBatchedRankState(hours, mask, live.copy(), ids)
    cold_state.add_state("m0", rows=members["m0"])
    assert np.array_equal(fused.scores("m0"), cold_state.scores("m0"))
    # genuinely new concurrent members DO grow capacity (4 -> 8)
    for i in range(5):
        fused.add_state(f"late{i}", rows=[int(r) for r in
                                          rng.choice(12, 3,
                                                     replace=False)])
    assert fused.realloc_count == 1
    tick()
    for key in ("all", "m0"):
        cold = rank_dense(hours[members[key]], mask[members[key]], live,
                          ids)
        assert_within_contract(fused.ranking(key), cold, CONTRACT)


@needs_jax
def test_pallas_member_growth_past_the_vmem_limit_raises_cleanly():
    """At C = 10,000 a plain fused tick fits the chip's VMEM at up to 64
    member slots, a fused top-k tick only below that: a top-k tick at 64
    slots, growth to 128 and a state built at 128 each raise ValueError
    naming the limit, before anything is dispatched or changed."""
    rng = np.random.default_rng(0)
    n_jobs, n_cfgs = 8, 10_000
    hours = rng.uniform(0.5, 5.0, (n_jobs, n_cfgs))
    mask = np.ones((n_jobs, n_cfgs), bool)
    prices = rng.uniform(0.1, 3.0, n_cfgs)
    ids = list(range(n_cfgs))
    s = PallasBatchedRankState(hours, mask, prices, ids, capacity=64)
    for i in range(64):
        s.add_state(i, rows=[i % n_jobs])
    with pytest.raises(ValueError, match="16 MiB"):
        s.reprice_with_heads({0: 1.0}, k=3)
    assert s.dispatches == 0 and np.array_equal(s.prices, prices.astype(
        np.float32).astype(np.float64))
    with pytest.raises(ValueError, match="128 member slots"):
        s.add_state("overflow", rows=[0])
    assert s.realloc_count == 0 and s.n_active == 64
    with pytest.raises(ValueError, match="VMEM"):
        PallasBatchedRankState(hours, mask, prices, ids, capacity=128)


@needs_jax
def test_pallas_validates_members_and_deltas():
    rng, hours, mask, prices, ids, _ = _fleet_universe(3, n_jobs=4,
                                                       n_cfgs=6)
    s = PallasBatchedRankState(hours, mask, prices, ids,
                               job_ids=[f"j{i}" for i in range(4)])
    s.add_state("a", rows=[0, 1])
    with pytest.raises(ValueError, match="duplicate member"):
        s.add_state("a", rows=[2])
    with pytest.raises(ValueError, match="exactly one of"):
        s.add_state("b", rows=[0], jobs=["j0"])
    with pytest.raises(ValueError, match="unknown job id"):
        s.add_state("b", jobs=["ghost"])
    with pytest.raises(ValueError, match="out of range"):
        s.add_state("b", rows=[99])
    # the padded kernel rows are a tiling artifact, never addressable:
    # row 4 is the first pad row of the 8-row kernel axis and must
    # reject exactly like any other out-of-range index
    with pytest.raises(ValueError, match="out of range"):
        s.add_state("b", rows=[4])
    with pytest.raises(ValueError, match="duplicate rows"):
        s.add_state("b", rows=[1, 1])
    with pytest.raises(ValueError, match="unknown member"):
        s.retire_state("ghost")
    with pytest.raises(ValueError, match="unknown config id"):
        s.reprice({"ghost": 1.0})
    with pytest.raises(ValueError, match="non-positive"):
        s.reprice({ids[0]: -1.0})
    assert s.reprice({}) == 0


# --- the fused reprice+top-k variant -----------------------------------------------

def _k_boundary_cases(C):
    return (C - 1, C, C + 1, 10 * C)


@needs_jax
@pytest.mark.parametrize("n_cfgs", [12, 13])
def test_pallas_top_k_boundary_with_ties(n_cfgs):
    """k in {C-1, C, C+1, 10·C} on the tie universe: the fused
    backend's top-k serves exactly the head of its own materialized
    ranking, boundary ties (cloned last-three columns) resolving in
    catalog order, within contract of the numpy reference."""
    from repro.selector import RankState
    hours, mask, prices, ids = _universe_with_ties(n_cfgs=n_cfgs)
    C = len(ids)
    s = PallasBatchedRankState(hours, mask, prices, ids)
    s.add_state("all", rows=list(range(hours.shape[0])))
    ref = RankState(hours, mask, prices, ids).ranking()
    clones = [ids[C - 3], ids[C - 2], ids[C - 1]]
    for k in _k_boundary_cases(C):
        head = s.top_k("all", k)
        assert head == s.ranking("all")[:min(k, C)], k
        assert_within_contract(head, ref, score_contract("jax"))
        got = [r.config_id for r in head if r.config_id in clones]
        assert got == clones[:len(got)], (k, got)


@needs_jax
def test_pallas_fused_heads_match_ranking_after_ticks():
    """reprice_with_heads — the tick AND every member's k-head from the
    SAME single kernel launch — equals what the two-step path (reprice,
    then top_k per member) serves, at every boundary k, including after
    ticks that move row minima and clone-column ties."""
    hours, mask, prices, ids = _universe_with_ties(n_cfgs=13)
    C = len(ids)
    s = PallasBatchedRankState(hours, mask, prices, ids)
    s.add_state("all", rows=list(range(hours.shape[0])))
    s.add_state("head", rows=[0, 1])
    ticks = ({ids[3]: 0.01}, {ids[7]: 40.0, ids[1]: 0.2},
             {ids[C - 3]: 0.5, ids[C - 2]: 0.5, ids[C - 1]: 0.5})
    for deltas, k in zip(ticks, (1, C - 1, C + 1)):
        twin = PallasBatchedRankState(hours, mask, s.prices, ids)
        twin.add_state("all", rows=list(range(hours.shape[0])))
        twin.add_state("head", rows=[0, 1])
        before = s.dispatches
        moved, heads = s.reprice_with_heads(deltas, k)
        assert moved == twin.reprice(deltas)
        assert s.dispatches == before + 1       # still one per tick
        for key in ("all", "head"):
            assert heads[key] == s.ranking(key)[:min(k, C)], (key, k)
    # the empty batch degrades to plain serving with NO dispatch
    before = s.dispatches
    moved, heads = s.reprice_with_heads({}, 3)
    assert moved == 0 and s.dispatches == before
    assert heads["all"] == s.ranking("all")[:3]
    for bad in (0, -1, 1.5, True):
        with pytest.raises(ValueError, match="positive integer"):
            s.reprice_with_heads({ids[0]: 1.0}, bad)


# --- hypothesis property half ------------------------------------------------------

if HAVE_HYPOTHESIS:
    @needs_jax
    @settings(max_examples=12, deadline=None)
    @given(fleet_streams())
    def test_pallas_fleet_within_contract_property(data):
        """For any fleet and any reprice stream: jax_pallas ==
        jax_batched == numpy cold per tick under the ScoreContract."""
        jobs, cfgs, rt, prices, stream, members = data
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        pv = np.asarray([prices[c] for c in cfgs])
        fused = PallasBatchedRankState(hours, mask, pv.copy(), cfgs)
        batched = BatchedRankState(hours, mask, pv.copy(), cfgs)
        for key, rows in members.items():
            fused.add_state(key, rows=rows)
            batched.add_state(key, rows=rows)
        live = pv.copy()
        for deltas in stream:
            fused.reprice(deltas)
            batched.reprice(deltas)
            for c, p in deltas.items():
                live[cfgs.index(c)] = p
            _assert_pallas_parity(fused, batched, members, hours, mask,
                                  live, cfgs)

    @needs_jax
    @settings(max_examples=10, deadline=None)
    @given(event_markets())
    def test_pallas_event_market_within_contract_property(market):
        """Event-bearing bursts (discount/eviction boundary re-quotes)
        through the fused kernel stay within contract of the cold
        float64 rank."""
        cfgs, base, events, seed, change_fraction, n_ticks, jobs, rt = \
            market
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        live = np.asarray([base[c] for c in cfgs])
        members = {"all": list(range(len(jobs)))}
        fused = PallasBatchedRankState(hours, mask, live.copy(), cfgs)
        batched = BatchedRankState(hours, mask, live.copy(), cfgs)
        for key, rows in members.items():
            fused.add_state(key, rows=rows)
            batched.add_state(key, rows=rows)
        feed = _event_feed(base, events, seed, change_fraction)
        for t in range(n_ticks):
            batch = feed.poll(t)
            if not batch:
                continue
            deltas = {d.config_id: d.price for d in batch}
            fused.reprice(deltas)
            batched.reprice(deltas)
            for d in batch:
                live[cfgs.index(d.config_id)] = d.price
            _assert_pallas_parity(fused, batched, members, hours, mask,
                                  live, cfgs)
else:
    @pytest.mark.skip(reason="hypothesis not installed (property half "
                             "of the pallas parity suite)")
    def test_pallas_parity_properties_skipped():
        pass  # pragma: no cover


# --- service / daemon integration --------------------------------------------------

@needs_jax
def test_service_jax_pallas_backend_one_dispatch_per_tick():
    """A jax_pallas service stacks every live (class, exclusion)
    ranking into one PallasBatchedRankState: a tick refreshes the whole
    fleet in ONE fused kernel dispatch, within contract of a numpy
    reference service."""
    svc = _fleet_service("jax_pallas")
    ref = _fleet_service("numpy")
    selections = [("j1", None), ("j2", None), ("j1", ("g2",)),
                  ("j2", ("g3",))]
    for job, excl in selections:
        d = svc.submit(job, exclude_groups=excl)
        r = ref.submit(job, exclude_groups=excl)
        assert_within_contract(list(d.ranking), list(r.ranking), CONTRACT)
    assert isinstance(svc._batched, PallasBatchedRankState)
    assert svc._batched.n_active == 4
    deltas = {f"c{i}": float(0.5 + i) for i in range(0, 16, 3)}
    assert svc.reprice(deltas) == 4          # whole fleet refreshed...
    assert svc.reprice_dispatches == 1       # ...in one fused kernel
    assert svc._batched.dispatches == 1
    ref.reprice(deltas)
    for job, excl in selections:
        assert_within_contract(
            list(svc.submit(job, exclude_groups=excl).ranking),
            list(ref.submit(job, exclude_groups=excl).ranking), CONTRACT)
    svc.reprice({"c1": 9.0})
    assert svc.reprice_dispatches == 2
    # top-k serving through the service: the head IS the head
    d = svc.submit("j1", top_k=3)
    assert d.served_via == "top_k"
    assert tuple(d.ranking) == tuple(svc.submit("j1").ranking[:3])


@needs_jax
def test_pallas_service_survives_out_of_band_table_apply():
    """The PR-2 desync invariant holds for the fused fleet: an
    out-of-band PriceTable.apply drops the universe for a cold rebuild
    instead of serving quotes it never saw."""
    svc = _fleet_service("jax_pallas")
    ref = _fleet_service("numpy")
    svc.submit("j1"); ref.submit("j1")
    svc.price_source.apply({"c2": 0.333})
    ref.price_source.apply({"c2": 0.333})
    deltas = {"c5": 7.7}
    assert svc.reprice(deltas) == 0          # fleet dropped, not repriced
    ref.reprice(deltas)
    assert_within_contract(list(svc.submit("j1").ranking),
                           list(ref.submit("j1").ranking), CONTRACT)


@needs_jax
def test_pallas_daemon_journal_audits_in_tolerance_mode():
    """A jax_pallas daemon stamps its backend in the journal header and
    the unmodified JournalReplayer audits it clean in tolerance mode —
    the fused kernel inherits the jax contract, so the audit surface
    carries over with zero changes (DESIGN.md §14)."""
    from repro.market import (JournalReplayer, SelectionDaemon,
                              SimulatedSpotFeed, synthetic_stream)
    from repro.selector import IdentityCatalog, PriceTable, ProfilingStore
    from repro.selector import SelectionService
    rng = np.random.default_rng(9)
    ids = [f"c{i}" for i in range(13)]
    store = ProfilingStore(config_ids=ids)
    for j in range(8):
        klass = JobClass.A if j % 2 else JobClass.B
        for c in ids:
            store.add(f"j{j}", c, float(rng.uniform(0.1, 5.0)),
                      job_class=klass, group=f"g{j % 4}")
    base = {c: float(rng.uniform(1.0, 20.0)) for c in ids}
    table = PriceTable(dict(base))
    svc = SelectionService(IdentityCatalog(ids), store, table,
                           backend="jax_pallas", serve_top_k=3)
    feed = SimulatedSpotFeed(base, seed=4, change_fraction=0.4)
    daemon = SelectionDaemon(svc, feed)
    for event in synthetic_stream([f"j{i}" for i in range(8)], 60,
                                  seed=7, tick_fraction=0.25):
        daemon.handle(event)
    journal = daemon.journal_dump()
    replayer = JournalReplayer(store, journal)
    assert replayer.backend == "jax_pallas"
    assert not score_contract(replayer.backend).bit_identical
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.decisions > 0
