"""Differential batched-parity suite (ISSUE 5 satellites).

The batched fleet kernel (:class:`~repro.selector.BatchedRankState`,
DESIGN.md §10) must be indistinguishable — within the jax
``ScoreContract`` — from a cold numpy float64 ``rank_dense`` at the live
prices (the audit reference) for every member of random fleets of
(row-subset) member states, at every tick, including event-bearing deltas (discount/eviction boundary re-quote
bursts) and members added or retired mid-stream.  A hypothesis property
half reuses the market strategies from ``test_rank_properties``; the
seeded deterministic half runs without hypothesis.

Also home to the device-side top-k serving tests (``top_k(k)`` must be
the head of the materialized ranking, ties included, on every backend)
and the ranking-memoization counter tests (the ISSUE 5 fix: repeat
``ranking()`` calls between two ticks must not re-materialize).
"""
import numpy as np
import pytest

from repro.core.trace import JobClass
from repro.selector import (BatchedRankState, IdentityCatalog,
                            NothingRankableError, PriceTable, ProfilingStore,
                            RankState, SelectionService, backend_available,
                            rank_dense, score_contract)
from test_backend_parity import assert_within_contract, fleet_of_one

try:        # the property half needs hypothesis; everything else runs
            # without it
    import hypothesis
    from hypothesis import given, settings, strategies as st
    from test_rank_properties import (delta_streams, event_markets,
                                      _event_feed)
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_jax = pytest.mark.skipif(not backend_available("jax_batched"),
                               reason="jax not installed")

CONTRACT = score_contract("jax_batched")


def _fleet_universe(seed, n_jobs=10, n_cfgs=24, n_members=4, partial=True):
    """Random universe plus a fleet of member row subsets (every job
    row appears in at least the 'all' member)."""
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.05, 10.0, (n_jobs, n_cfgs))
    if partial:
        mask = rng.random((n_jobs, n_cfgs)) > 0.25
        mask[np.arange(n_jobs), rng.integers(0, n_cfgs, n_jobs)] = True
    else:
        mask = np.ones((n_jobs, n_cfgs), dtype=bool)
    prices = rng.uniform(0.5, 20.0, n_cfgs)
    ids = [f"c{i}" for i in range(n_cfgs)]
    members = {"all": list(range(n_jobs))}
    for m in range(n_members - 1):
        size = int(rng.integers(1, n_jobs))
        members[f"m{m}"] = sorted(
            int(i) for i in rng.choice(n_jobs, size, replace=False))
    return rng, hours, mask, prices, ids, members


def _assert_fleet_parity(batched, members, hours, mask, live, ids):
    """Every member of ``batched`` is within contract of a cold numpy
    float64 rank over its rows."""
    for key, rows in members.items():
        cold = rank_dense(hours[rows], mask[rows], live, ids)
        assert_within_contract(batched.ranking(key), cold, CONTRACT)


# --- deterministic differential sweeps (run without hypothesis) --------------------

@needs_jax
@pytest.mark.parametrize("seed", range(6))
def test_batched_fleet_within_contract_seeded(seed):
    """Seeded fleets: after every tick, each batched member matches the
    cold numpy float64 rank under the contract — one batched dispatch
    per tick for the whole fleet."""
    rng, hours, mask, prices, ids, members = _fleet_universe(
        seed, n_jobs=6 + seed, n_cfgs=12 + 4 * seed,
        partial=seed % 2 == 0)
    batched = BatchedRankState(hours, mask, prices.copy(), ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    live = prices.copy()
    for _ in range(6):
        k = int(rng.integers(1, len(ids)))
        cols = rng.choice(len(ids), k, replace=False)
        deltas = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                  for c in cols}
        batched.reprice(deltas)
        for c, p in deltas.items():
            live[int(c[1:])] = p
        _assert_fleet_parity(batched, members, hours, mask, live, ids)
    # the accounting the bench gates on: one dispatch per tick, fleet-wide
    assert batched.dispatches == batched.reprices == 6
    assert batched.n_active == len(members)


@needs_jax
def test_batched_event_market_within_contract_deterministic():
    """Discount/eviction boundary re-quote bursts through the batched
    kernel stay within contract of cold float64 ranks for every member
    (the deterministic analogue of the hypothesis event_markets
    sweep)."""
    from repro.market import MarketEvent, SimulatedSpotFeed
    rng, hours, mask, prices, ids, members = _fleet_universe(
        7, n_jobs=8, n_cfgs=10, partial=False)
    base = {c: float(p) for c, p in zip(ids, prices)}
    feed = SimulatedSpotFeed(
        base, seed=5, change_fraction=0.3, volatility=0.15,
        events=[MarketEvent("us-central1", 2, 4, 0.25, "discount"),
                MarketEvent("europe-west3", 5, 3, 4.0, "eviction")])
    batched = BatchedRankState(hours, mask, prices.copy(), ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    live = prices.copy()
    for t in range(10):
        batch = feed.poll(t)
        if not batch:
            continue
        batched.reprice({d.config_id: d.price for d in batch})
        for d in batch:
            live[ids.index(d.config_id)] = d.price
        _assert_fleet_parity(batched, members, hours, mask, live, ids)


@needs_jax
def test_states_added_and_retired_mid_stream():
    """Members added mid-stream are in sync with every tick applied so
    far; retired members stop contributing and their slots are reused;
    capacity growth past the initial slot pool preserves every live
    member's scores."""
    rng, hours, mask, prices, ids, members = _fleet_universe(
        11, n_jobs=12, n_cfgs=16, n_members=3)
    batched = BatchedRankState(hours, mask, prices.copy(), ids,
                               capacity=2)     # force growth early
    live_members = {}
    live = prices.copy()

    def tick():
        k = int(rng.integers(1, len(ids)))
        cols = rng.choice(len(ids), k, replace=False)
        deltas = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                  for c in cols}
        batched.reprice(deltas)
        for c, p in deltas.items():
            live[int(c[1:])] = p

    batched.add_state("all", rows=members["all"])
    live_members["all"] = members["all"]
    tick()
    # added after a tick: must reflect the already-applied deltas
    batched.add_state("m0", rows=members["m0"])
    live_members["m0"] = members["m0"]
    tick()
    _assert_fleet_parity(batched, live_members, hours, mask, live, ids)
    # retire one, keep ticking: survivors stay in contract
    batched.retire_state("m0")
    del live_members["m0"]
    assert "m0" not in batched
    # serving a *retired* member is a typed rankable-nothing condition
    # (ISSUE 6: the service/daemon path journals a genuine rejection) —
    # a key that was never registered stays a plain ValueError
    with pytest.raises(NothingRankableError, match="retired"):
        batched.ranking("m0")
    with pytest.raises(NothingRankableError, match="retired"):
        batched.top_k("m0", 1)
    with pytest.raises(ValueError, match="unknown member"):
        batched.ranking("never-registered")
    tick()
    _assert_fleet_parity(batched, live_members, hours, mask, live, ids)
    # grow well past the starting capacity (2), reusing retired slots
    for i in range(7):
        rows = [int(r) for r in rng.choice(12, 3, replace=False)]
        batched.add_state(f"late{i}", rows=rows)
        live_members[f"late{i}"] = rows
    tick()
    _assert_fleet_parity(batched, live_members, hours, mask, live, ids)
    assert batched.n_active == len(live_members)


@needs_jax
def test_batched_retire_all_then_readd_reuses_slots():
    """ISSUE 8 satellite: a fleet whose members are all retired and
    then re-added must reuse the zero-masked slots, not double capacity
    — ``realloc_count`` is pinned across the cycle, and the revived
    member's scores bit-match a cold build at the same prices."""
    rng, hours, mask, prices, ids, members = _fleet_universe(
        13, n_jobs=10, n_cfgs=14, n_members=4)
    b = BatchedRankState(hours, mask, prices.copy(), ids, capacity=4)
    for key, rows in members.items():
        b.add_state(key, rows=rows)
    live = prices.copy()
    deltas = {ids[2]: 0.4, ids[9]: 11.0}
    b.reprice(deltas)
    for c, p in deltas.items():
        live[int(c[1:])] = p
    assert b.realloc_count == 0
    for key in list(members):
        b.retire_state(key)
    assert b.n_active == 0
    for key, rows in members.items():
        b.add_state(key, rows=rows)
    # the whole cycle reused the freed slots: no capacity doubling
    assert b.realloc_count == 0
    assert b.n_active == len(members)
    # the revived members bit-match a cold build at the live prices
    cold = BatchedRankState(hours, mask, live.copy(), ids)
    for key, rows in members.items():
        cold.add_state(key, rows=rows)
        assert np.array_equal(b.scores(key), cold.scores(key)), key
        assert b.ranking(key) == cold.ranking(key)
    # growth still happens (and is counted) for genuinely new members:
    # 4 live + 4 new overflows the 4-slot pool exactly once (4 -> 8)
    for i in range(4):
        b.add_state(f"extra{i}", rows=[0, 1])
    assert b.realloc_count == 1


@needs_jax
def test_batched_validates_members_and_deltas():
    rng, hours, mask, prices, ids, _ = _fleet_universe(3, n_jobs=4,
                                                       n_cfgs=6)
    jobs = [f"j{i}" for i in range(4)]
    b = BatchedRankState(hours, mask, prices, ids, job_ids=jobs)
    b.add_state("a", rows=[0, 1])
    with pytest.raises(ValueError, match="duplicate member"):
        b.add_state("a", rows=[2])
    with pytest.raises(ValueError, match="exactly one of"):
        b.add_state("b", rows=[0], jobs=["j0"])
    with pytest.raises(ValueError, match="exactly one of"):
        b.add_state("b")
    with pytest.raises(ValueError, match="unknown job id"):
        b.add_state("b", jobs=["ghost"])
    with pytest.raises(ValueError, match="out of range"):
        b.add_state("b", rows=[99])
    with pytest.raises(ValueError, match="duplicate rows"):
        b.add_state("b", rows=[1, 1])
    with pytest.raises(ValueError, match="unknown member"):
        b.retire_state("ghost")
    with pytest.raises(ValueError, match="unknown member"):
        b.top_k("ghost", 1)
    with pytest.raises(ValueError, match="unknown config id"):
        b.reprice({"ghost": 1.0})
    with pytest.raises(ValueError, match="non-positive"):
        b.reprice({ids[0]: -1.0})
    assert b.reprice({}) == 0
    # jobs= addressing resolves the same rows as rows=
    b.add_state("by-jobs", jobs=["j0", "j1"])
    assert b.ranking("by-jobs") == b.ranking("a")
    from repro.selector import NothingRankableError
    with pytest.raises(NothingRankableError):
        BatchedRankState(np.zeros((0, 2)), np.zeros((0, 2), dtype=bool),
                         np.asarray([1.0, 2.0]), ["a", "b"])


# --- hypothesis property half (skips quietly when hypothesis is absent) ------------

if HAVE_HYPOTHESIS:
    @st.composite
    def fleet_streams(draw):
        """A delta-stream universe plus a fleet of member row
        subsets."""
        jobs, cfgs, rt, prices, stream = draw(delta_streams())
        n_members = draw(st.integers(1, 4))
        members = {}
        for m in range(n_members):
            rows = draw(st.lists(st.integers(0, len(jobs) - 1),
                                 min_size=1, max_size=len(jobs),
                                 unique=True))
            members[f"m{m}"] = sorted(rows)
        return jobs, cfgs, rt, prices, stream, members

    @needs_jax
    @settings(max_examples=20, deadline=None)
    @given(fleet_streams())
    def test_batched_fleet_within_contract(data):
        """For any fleet of member states and any reprice stream, every
        batched tick matches the cold numpy float64 rank within the
        contract."""
        jobs, cfgs, rt, prices, stream, members = data
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        pv = np.asarray([prices[c] for c in cfgs])
        batched = BatchedRankState(hours, mask, pv.copy(), cfgs)
        for key, rows in members.items():
            batched.add_state(key, rows=rows)
        live = pv.copy()
        for deltas in stream:
            batched.reprice(deltas)
            for c, p in deltas.items():
                live[cfgs.index(c)] = p
            _assert_fleet_parity(batched, members, hours, mask, live,
                                 cfgs)

    @needs_jax
    @settings(max_examples=15, deadline=None)
    @given(event_markets(), st.integers(1, 3))
    def test_batched_event_market_within_contract(market, n_members):
        """Event-bearing markets (discount/eviction boundary re-quote
        bursts) through the batched kernel stay within contract of the
        cold float64 rank for every member at every tick."""
        cfgs, base, events, seed, change_fraction, n_ticks, jobs, rt = \
            market
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        live = np.asarray([base[c] for c in cfgs])
        members = {f"m{m}": list(range(m % len(jobs), len(jobs)))
                   for m in range(n_members)}
        batched = BatchedRankState(hours, mask, live.copy(), cfgs)
        for key, rows in members.items():
            batched.add_state(key, rows=rows)
        feed = _event_feed(base, events, seed, change_fraction)
        for t in range(n_ticks):
            batch = feed.poll(t)
            if not batch:
                continue
            batched.reprice({d.config_id: d.price for d in batch})
            for d in batch:
                live[cfgs.index(d.config_id)] = d.price
            _assert_fleet_parity(batched, members, hours, mask, live,
                                 cfgs)

    @needs_jax
    @settings(max_examples=15, deadline=None)
    @given(fleet_streams(), st.data())
    def test_batched_add_retire_mid_stream_property(data, extra):
        """Random add/retire schedules interleaved with the stream:
        surviving members always match the cold float64 rank."""
        jobs, cfgs, rt, prices, stream, members = data
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        pv = np.asarray([prices[c] for c in cfgs])
        batched = BatchedRankState(hours, mask, pv.copy(), cfgs,
                                   capacity=1)
        pending = dict(members)
        live_members = {}
        live = pv.copy()
        for deltas in stream:
            if pending and extra.draw(st.booleans()):
                key, rows = pending.popitem()
                batched.add_state(key, rows=rows)
                live_members[key] = rows
            if len(live_members) > 1 and extra.draw(st.booleans()):
                key = extra.draw(st.sampled_from(sorted(live_members)))
                batched.retire_state(key)
                del live_members[key]
            batched.reprice(deltas)
            for c, p in deltas.items():
                live[cfgs.index(c)] = p
            _assert_fleet_parity(batched, live_members, hours, mask,
                                 live, cfgs)
else:
    @pytest.mark.skip(reason="hypothesis not installed (property half "
                             "of the batched parity suite)")
    def test_batched_parity_properties_skipped():
        pass  # pragma: no cover


# --- device-side top-k serving ------------------------------------------------------

def _universe_with_ties(n_jobs=5, n_cfgs=12, seed=2):
    """A universe whose last three profiled columns are exact clones —
    bit-equal scores on every backend, so the (score, catalog order)
    tie-break is actually exercised — plus one unprofiled column."""
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.05, 10.0, (n_jobs, n_cfgs))
    hours[:, n_cfgs - 2] = hours[:, n_cfgs - 3]
    hours[:, n_cfgs - 1] = hours[:, n_cfgs - 3]
    mask = np.ones((n_jobs, n_cfgs), dtype=bool)
    mask[:, 0] = False                               # never profiled
    prices = rng.uniform(0.5, 20.0, n_cfgs)
    prices[n_cfgs - 2] = prices[n_cfgs - 3]
    prices[n_cfgs - 1] = prices[n_cfgs - 3]
    ids = [f"c{i}" for i in range(n_cfgs)]
    return hours, mask, prices, ids


@pytest.mark.parametrize("k", [1, 3, None])          # None -> k = C
def test_numpy_top_k_is_head_of_ranking(k):
    hours, mask, prices, ids = _universe_with_ties()
    state = RankState(hours, mask, prices, ids)
    k = len(ids) if k is None else k
    assert state.top_k(k) == state.ranking()[:k]
    state.reprice({ids[3]: 0.01})
    assert state.top_k(k) == state.ranking()[:k]


@needs_jax
@pytest.mark.parametrize("k", [1, 3, None])
def test_jax_top_k_is_head_of_ranking(k):
    hours, mask, prices, ids = _universe_with_ties()
    state = fleet_of_one(hours, mask, prices, ids)
    k = len(ids) if k is None else k
    assert state.top_k("m", k) == state.ranking("m")[:k]
    state.reprice({ids[3]: 0.01, ids[7]: 40.0})
    assert state.top_k("m", k) == state.ranking("m")[:k]


@needs_jax
@pytest.mark.parametrize("k", [1, 3, None])
def test_batched_top_k_is_head_of_ranking(k):
    hours, mask, prices, ids = _universe_with_ties()
    b = BatchedRankState(hours, mask, prices, ids)
    b.add_state("all", rows=list(range(hours.shape[0])))
    b.add_state("head", rows=[0, 1])
    k = len(ids) if k is None else k
    for key in ("all", "head"):
        assert b.top_k(key, k) == b.ranking(key)[:k]
        assert b.winner(key) == b.ranking(key)[0]
    b.reprice({ids[3]: 0.01})
    for key in ("all", "head"):
        assert b.top_k(key, k) == b.ranking(key)[:k]


def test_top_k_exact_ties_resolve_in_catalog_order():
    """The cloned-column ties must come back in catalog order from both
    the sorted ranking and every top-k path (ScoreContract tie
    discipline: equal scores break by catalog position)."""
    hours, mask, prices, ids = _universe_with_ties()
    C = len(ids)
    clones = [ids[C - 3], ids[C - 2], ids[C - 1]]
    state = RankState(hours, mask, prices, ids)
    ranked_ids = [r.config_id for r in state.ranking()]
    i = ranked_ids.index(clones[0])
    assert ranked_ids[i:i + 3] == clones
    assert [r.config_id for r in state.top_k(C)][i:i + 3] == clones
    if backend_available("jax_batched"):
        jx = fleet_of_one(hours, mask, prices, ids)
        assert [r.config_id for r in jx.top_k("m", C)][i:i + 3] == clones


def test_top_k_clamps_and_validates():
    hours, mask, prices, ids = _universe_with_ties()
    state = RankState(hours, mask, prices, ids)
    assert state.top_k(len(ids) + 50) == state.ranking()
    for bad in (0, -1, 1.5, True):
        with pytest.raises(ValueError, match="positive integer"):
            state.top_k(bad)
    if backend_available("jax_batched"):
        jx = fleet_of_one(hours, mask, prices, ids)
        assert jx.top_k("m", len(ids) + 50) == jx.ranking("m")
        with pytest.raises(ValueError, match="positive integer"):
            jx.top_k("m", 0)


def test_top_k_unprofiled_configs_rank_last_with_inf():
    hours, mask, prices, ids = _universe_with_ties()
    state = RankState(hours, mask, prices, ids)
    full = state.top_k(len(ids))
    assert full[-1].config_id == ids[0]
    assert full[-1].score == float("inf")
    assert full[-1].mean_norm_cost == float("inf")


# --- ranking memoization (the ISSUE 5 freshness fix) --------------------------------

@needs_jax
def test_jax_ranking_memoized_until_next_tick():
    """The fix: a device state's ``ranking()`` used to re-materialize
    (one device→host transfer + C-object build + host sort) on *every*
    call even when no tick had been applied — now it memoizes on the
    tick count, like the numpy state."""
    hours, mask, prices, ids = _universe_with_ties()
    state = fleet_of_one(hours, mask, prices, ids)
    first = state.ranking("m")
    assert state.materializations == 1
    assert state.ranking("m") == first
    assert state.ranking("m") == first
    assert state.materializations == 1      # no re-materialization
    state.reprice({ids[2]: 0.5})
    assert state.materializations == 1      # reprice alone is lazy
    again = state.ranking("m")
    assert state.materializations == 2      # tick invalidated the memo
    assert again != first
    # the returned list is a fresh copy: callers cannot corrupt the memo
    again.reverse()
    assert state.ranking("m") == list(reversed(again))
    assert state.materializations == 2


def test_numpy_ranking_memoized_until_next_tick():
    hours, mask, prices, ids = _universe_with_ties()
    state = RankState(hours, mask, prices, ids)
    first = state.ranking()
    state.ranking()
    assert state.materializations == 1
    state.reprice({ids[2]: 0.5})
    assert state.ranking() != first
    assert state.materializations == 2


@needs_jax
def test_batched_ranking_memoized_per_member():
    hours, mask, prices, ids = _universe_with_ties()
    b = BatchedRankState(hours, mask, prices, ids)
    b.add_state("a", rows=[0, 1, 2])
    b.add_state("b", rows=[3, 4])
    b.ranking("a"); b.ranking("a"); b.ranking("b")
    assert b.materializations == 2          # one per member, not per call
    b.reprice({ids[2]: 0.5})
    b.ranking("a"); b.ranking("a")
    assert b.materializations == 3


# --- service-level fleet serving ----------------------------------------------------

def _fleet_service(backend, serve_top_k=None, n_cfgs=16, seed=1):
    rng = np.random.default_rng(seed)
    ids = [f"c{i}" for i in range(n_cfgs)]
    store = ProfilingStore(config_ids=ids)
    for j in range(8):
        klass = JobClass.A if j % 2 else JobClass.B
        for c in ids:
            store.add(f"j{j}", c, float(rng.uniform(0.1, 5.0)),
                      job_class=klass, group=f"g{j % 4}")
    table = PriceTable({c: float(rng.uniform(1.0, 20.0)) for c in ids})
    return SelectionService(IdentityCatalog(ids), store, table,
                            backend=backend, serve_top_k=serve_top_k)


@needs_jax
def test_service_jax_batched_backend_one_dispatch_per_tick():
    """A jax_batched service stacks every live (class, exclusion)
    ranking into one BatchedRankState: a tick refreshes the whole fleet
    in ONE kernel dispatch, and every served ranking stays within
    contract of a numpy reference service."""
    svc = _fleet_service("jax_batched")
    ref = _fleet_service("numpy")
    # four live selections: two classes x two exclusion variants
    selections = [("j1", None), ("j2", None), ("j1", ("g2",)),
                  ("j2", ("g3",))]
    for job, excl in selections:
        d = svc.submit(job, exclude_groups=excl)
        r = ref.submit(job, exclude_groups=excl)
        assert_within_contract(list(d.ranking), list(r.ranking), CONTRACT)
    assert svc._batched is not None and svc._batched.n_active == 4
    deltas = {f"c{i}": float(0.5 + i) for i in range(0, 16, 3)}
    assert svc.reprice(deltas) == 4          # whole fleet refreshed...
    assert svc.reprice_dispatches == 1       # ...in one dispatch
    ref.reprice(deltas)
    for job, excl in selections:
        assert_within_contract(
            list(svc.submit(job, exclude_groups=excl).ranking),
            list(ref.submit(job, exclude_groups=excl).ranking), CONTRACT)
    # second tick: still one dispatch per tick
    svc.reprice({"c1": 9.0})
    assert svc.reprice_dispatches == 2


@pytest.mark.parametrize("backend", ["numpy", "jax_batched",
                                     "jax_pallas"])
def test_service_top_k_decision_matches_full_serving(backend):
    """A top-k-served Decision carries the same winner, score and $/h
    as a full-ranking Decision from an identically-priced service — the
    head IS the head, on every backend."""
    if not backend_available(backend):
        pytest.skip("jax not installed")
    svc = _fleet_service(backend, serve_top_k=3)
    ref = _fleet_service(backend)
    d = svc.submit("j1")
    f = ref.submit("j1")
    assert d.served_via == "top_k" and f.served_via == "ranking"
    assert len(d.ranking) == 3 and len(f.ranking) == len(ref.catalog.ids())
    assert d.config_id == f.config_id
    assert d.ranking[0] == f.ranking[0]
    assert d.hourly_cost == f.hourly_cost
    assert tuple(d.ranking) == tuple(f.ranking[:3])
    # per-submission override beats the service default
    assert len(ref.submit("j1", top_k=2).ranking) == 2
    assert ref.submit("j1", top_k=2).served_via == "top_k"
    assert len(svc.submit("j1", top_k=5).ranking) == 5


def test_service_rank_head_caches_and_reprices():
    """Heads are cached per (tag, selection, k), refresh through the
    incremental path on ticks, and reuse a cached full ranking when one
    exists."""
    svc = _fleet_service("numpy")
    head, from_cache = svc.rank_head(job_class=JobClass.A, k=2)
    assert not from_cache and len(head) == 2
    again, from_cache = svc.rank_head(job_class=JobClass.A, k=2)
    assert from_cache and again == head
    # a different depth is its own cached head
    h3, from_cache = svc.rank_head(job_class=JobClass.A, k=3)
    assert from_cache                      # live state serves it
    assert h3[:2] == head
    # the full ranking's head agrees
    full = svc.rank(job_class=JobClass.A)
    assert tuple(full[:3]) == h3
    svc.reprice({"c0": 0.123})
    h_after, from_cache = svc.rank_head(job_class=JobClass.A, k=2)
    assert from_cache                      # incremental refresh, no rebuild
    assert h_after == tuple(svc.rank(job_class=JobClass.A)[:2])
    with pytest.raises(ValueError, match="positive integer"):
        svc.rank_head(job_class=JobClass.A, k=0)


def test_service_serve_top_k_validated_at_construction():
    with pytest.raises(ValueError, match="serve_top_k"):
        _fleet_service("numpy", serve_top_k=0)
    with pytest.raises(ValueError, match="serve_top_k"):
        _fleet_service("numpy", serve_top_k=-3)
    with pytest.raises(ValueError, match="serve_top_k"):
        _fleet_service("numpy", serve_top_k=True)


@needs_jax
def test_batched_service_survives_out_of_band_table_apply():
    """An out-of-band PriceTable.apply desyncs the shared batched
    universe: the next tick must drop and cold-rebuild it rather than
    serve quotes it never saw (the PR-2 review invariant, extended to
    the fleet)."""
    svc = _fleet_service("jax_batched")
    ref = _fleet_service("numpy")
    svc.submit("j1"); ref.submit("j1")
    svc.price_source.apply({"c2": 0.333})
    ref.price_source.apply({"c2": 0.333})
    deltas = {"c5": 7.7}
    assert svc.reprice(deltas) == 0          # fleet dropped, not repriced
    ref.reprice(deltas)
    assert_within_contract(list(svc.submit("j1").ranking),
                           list(ref.submit("j1").ranking), CONTRACT)


# --- regression: the fleet matmuls ask for float32 precision ---------------

def _fleet_programs():
    """Every XLA fleet program with a member-axis matmul, lowered at a
    tiny shape: (name, StableHLO text)."""
    import jax
    import jax.numpy as jnp

    from repro.selector import pallas_rank, rank, sharded

    J, C, S, B = 8, 16, 4, 8
    f32, sd = jnp.float32, jax.ShapeDtypeStruct
    universe = (sd((J, C), f32), sd((J, C), jnp.bool_))
    step, step_heads, member = rank._jax_batched_fns()
    state = (sd((C,), f32), sd((J, C), f32), sd((J,), f32),
             sd((J, C), f32), sd((S, C), f32))
    tick = (*state, *universe, sd((S, J), f32), sd((2 * B,), jnp.int32))
    yield "jax_batched step", step.lower(*tick)
    yield "jax_batched step+heads", step_heads.lower(
        *tick, sd((S, C), jnp.bool_), 3)
    yield "jax_batched member", member.lower(sd((J, C), f32),
                                             sd((J,), f32))
    _, sh_step, sh_member = sharded._sharded_fns(1)
    yield "jax_sharded step", sh_step.lower(
        *state, *universe, sd((S, J), f32), sd((1, B), jnp.int32),
        sd((1, B), f32))
    yield "jax_sharded member", sh_member.lower(sd((J, C), f32),
                                                sd((J,), f32))
    yield "jax_pallas member", pallas_rank._helper_fns()[1].lower(
        *universe, sd((1, C), f32), sd((J, 1), f32), sd((J,), f32))


@pytest.mark.skipif(not backend_available("jax_batched"),
                    reason="jax missing")
def test_fleet_matmuls_run_at_float32_precision():
    """A TPU's default float32 matmul rounds its operands to bfloat16,
    which alone breaks the 1e-4 ScoreContract on the chip: every fleet
    matmul must request HIGHEST precision (the CPU cannot show the
    rounding, so this pins the request itself)."""
    for name, lowered in _fleet_programs():
        dots = [line for line in lowered.as_text().splitlines()
                if "dot_general" in line]
        assert dots, name
        assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots), \
            (name, dots)


@pytest.mark.skipif(not backend_available("jax_batched"),
                    reason="jax missing")
def test_fleet_precision_probe_runs_small(monkeypatch):
    """``benchmarks/fleet_precision.py`` measures on the chip what
    DEFAULT precision does to the fleet's scores.  At a tiny size on the
    CPU, where both precisions multiply in float32, it reports every
    cell inside the contract."""
    import pathlib

    monkeypatch.syspath_prepend(
        str(pathlib.Path(__file__).resolve().parents[1] / "benchmarks"))
    from fleet_precision import fleet_precision

    out = fleet_precision(n_jobs=16, n_cfgs=256, n_members=3, n_ticks=4,
                          frac=0.05)
    assert sorted(out) == ["DEFAULT", "HIGHEST"]
    for worst, n_bad in out.values():
        assert n_bad == 0 and worst < 1e-6


# --- the reprice's packed operand and packed result -----------------------

#: batch sizes landing in the 8, 32, 128 and 512 buckets, and an
#: eviction storm that re-quotes every column of the catalog at once
PACKED_BATCHES = {"8": (5, 8), "32": (20, 32), "128": (100, 128),
                  "512": (400, 512), "eviction": (700, 2048)}


@needs_jax
@pytest.mark.parametrize("heads", [True, False], ids=["heads", "plain"])
@pytest.mark.parametrize("batch", sorted(PACKED_BATCHES))
def test_packed_reprice_is_bit_identical_to_the_traced_step(batch, heads):
    """A reprice whose batch crosses as one packed int32 operand and
    whose results come back as one packed readback leaves every resident
    buffer, the handoff count and the heads memo bit-identical to the
    shared traced step and top-k run on the unpacked columns and the
    device's float32 rounding of the prices, jitted as one program on
    the unpacked operands."""
    import jax
    import jax.numpy as jnp

    from repro.selector import rank

    n, bucket = PACKED_BATCHES[batch]
    _, hours, mask, prices, ids, members = _fleet_universe(
        7, n_jobs=10, n_cfgs=700)
    state = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        state.add_state(key, rows=rows)
    if heads:
        state.top_k(next(iter(members)), 3)      # the reprice takes them
    rng = np.random.default_rng(n)
    cols = rng.choice(len(ids), n, replace=False).astype(np.int32)
    new = rng.uniform(0.05, 20.0, n)
    # prices float32 cannot hold exactly, one of them a new row minimum
    new[:4] = [0.1, 3.3333, 1 / 3, 1e-3]
    before = [jnp.array(b) for b in (
        state.d_prices, state.d_cost, state.d_row_best, state.d_norm,
        state.d_scores)]
    pad_cols = np.full(bucket, cols[0], dtype=np.int32)
    pad_cols[:n] = cols
    pad_new = np.full(bucket, new[0])
    pad_new[:n] = new
    want = jax.jit(rank._batched_step)(
        *before, state.d_hours, state.d_mask, state.d_row_masks,
        jnp.asarray(pad_cols), jnp.asarray(pad_new, dtype=jnp.float32))
    moved = state.reprice({ids[c]: float(p) for c, p in zip(cols, new)})
    got = (state.d_prices, state.d_cost, state.d_row_best, state.d_norm,
           state.d_scores)
    for g, w in zip(got, want[:5]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert moved == int(want[5]) and moved > 0
    idx, vals = rank._masked_top_k(want[4], state._d_finite, 3)
    memo = state._head_memo[2].get(3)
    if heads:
        np.testing.assert_array_equal(memo[0], np.asarray(idx))
        np.testing.assert_array_equal(
            memo[1], np.asarray(vals).astype(np.float64))
    else:
        assert memo is None
