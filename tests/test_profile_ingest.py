"""Profile records that arrive while the selector serves.

Test-job executions land in the store under load (Flora, arXiv
2502.21046, §II-III): ``ProfilingStore.add_cells`` writes a batch with
one version bump, ``BatchedRankState.ingest`` (and the Pallas fleet,
which inherits it) folds the cells into the resident fleet in one
dispatch, ``SelectionService.ingest`` keeps the fleet and its members
across the store versions it absorbs, and ``ServeFrontend.add_profiles``
applies a tick's records before its prices and journals them for
``JournalReplayer``.  Every fleet is held to a cold build from the final
store (within the ``ScoreContract``) and to the numpy ``rank_dense``
reference; the backends that keep the cold rebuild are held to their
counted fallback.
"""
import json

import numpy as np
import pytest

from repro.core.trace import JobClass
from repro.market import (JournalReplayer, RecordedPriceFeed, ServeFrontend,
                          SimulatedSpotFeed, Submission, record_feed)
from repro.obs import MetricsRegistry
from repro.selector import (BatchedRankState, IdentityCatalog,
                            PallasBatchedRankState, PriceTable,
                            ProfilingStore, SelectionService,
                            backend_available, rank_dense, score_contract)
from test_backend_parity import assert_within_contract

needs_jax = pytest.mark.skipif(not backend_available("jax_batched"),
                               reason="jax not installed")

FLEETS = {"jax_batched": BatchedRankState,
          "jax_pallas": PallasBatchedRankState}


# --- the store's batch insert ------------------------------------------------------

def test_add_cells_is_one_version_and_overwrites():
    store = ProfilingStore(config_ids=["c0", "c1"])
    store.add("j0", "c0", 1.0)
    v = store.version
    written = store.add_cells([("j0", "c0", 2.0), ("j0", "c1", 3),
                               ("j0", "c1", 4.0), ("j1", "c0", 5.0)])
    assert store.version == v + 1
    assert written == (("j0", "c0", 2.0), ("j0", "c1", 3.0),
                       ("j0", "c1", 4.0), ("j1", "c0", 5.0))
    assert store.runtime_hours("j0", "c1") == 4.0      # last write wins
    assert store.job_ids == ["j0", "j1"]
    assert store.meta("j1").job_class is None
    assert store.metrics.counter("store.cells_ingested").value == 4
    assert store.add_cells([]) == () and store.version == v + 1


@pytest.mark.parametrize("bad", [0.0, -1.0, float("inf"), float("nan")])
def test_add_cells_checks_every_runtime_before_writing(bad):
    store = ProfilingStore(config_ids=["c0"])
    store.add("j0", "c0", 1.0)
    v = store.version
    with pytest.raises(ValueError, match="runtime"):
        store.add_cells([("j0", "c0", 2.0), ("j1", "c0", bad)])
    assert store.version == v and store.runtime_hours("j0", "c0") == 1.0
    assert store.job_ids == ["j0"]


def test_store_copy_is_independent():
    store = ProfilingStore(config_ids=["c0"])
    store.add("j0", "c0", 1.0, job_class=JobClass.A, group="g")
    twin = store.copy()
    twin.add_cells([("j0", "c0", 9.0), ("j1", "c0", 2.0)])
    assert store.runtime_hours("j0", "c0") == 1.0 and len(store) == 1
    assert twin.version == store.version + 1
    assert twin.meta("j0") == store.meta("j0")


# --- the fleet's ingest step --------------------------------------------------------

def _universe(seed, n_jobs=8, n_cfgs=24):
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.1, 5.0, (n_jobs, n_cfgs))
    mask = rng.random((n_jobs, n_cfgs)) < 0.5
    mask[np.arange(n_jobs), rng.integers(0, n_cfgs, n_jobs)] = True
    prices = rng.uniform(1.0, 10.0, n_cfgs)
    ids = [f"c{i}" for i in range(n_cfgs)]
    jobs = [f"j{i}" for i in range(n_jobs)]
    members = {"all": list(range(n_jobs)), "low": [0, 1, 2],
               "high": [5, 6, 7], "one": [3]}
    return rng, np.where(mask, hours, np.nan), mask, prices, ids, jobs, \
        members


def _fleet(backend, hours, mask, prices, ids, jobs, members, **kw):
    state = FLEETS[backend](hours, mask, prices, ids, job_ids=jobs, **kw)
    for key, rows in members.items():
        state.add_state(key, rows=rows)
    return state


def _assert_matches_cold(state, backend, hours, mask, prices, ids, jobs,
                         members, k=3):
    """Every member within contract of a cold fleet from the same cells
    and of the numpy float64 reference; heads are the heads of both."""
    contract = score_contract(backend)
    cold = _fleet(backend, hours, mask, prices, ids, jobs, members)
    for key, rows in members.items():
        ref = rank_dense(np.nan_to_num(hours[rows]), mask[rows], prices,
                         ids)
        got = state.ranking(key)
        assert_within_contract(got, ref, contract)
        assert_within_contract(got, cold.ranking(key), contract)
        assert [r.config_id for r in state.top_k(key, k)] == \
            [r.config_id for r in got[:k]]
        np.testing.assert_array_equal(
            state.counts(key), mask[rows].sum(axis=0))


def _cells(job, cols, hours, ids):
    return [(job, ids[c], float(h)) for c, h in zip(cols, hours)]


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mixed_ingests_and_reprices_match_a_cold_build(backend, seed):
    rng, hours, mask, prices, ids, jobs, members = _universe(seed)
    state = _fleet(backend, hours, mask, prices, ids, jobs, members)
    for step in range(12):
        r = int(rng.integers(len(jobs)))
        cols = rng.choice(len(ids), int(rng.integers(1, 6)), replace=False)
        new = rng.uniform(0.05, 6.0, cols.size)
        assert state.ingest(_cells(jobs[r], cols, new, ids)) == cols.size
        hours[r, cols], mask[r, cols] = new, True
        quoted = rng.choice(len(ids), 3, replace=False)
        fresh = rng.uniform(1.0, 10.0, 3)
        state.reprice({ids[c]: float(p) for c, p in zip(quoted, fresh)})
        prices[quoted] = fresh
    _assert_matches_cold(state, backend, hours, mask, prices, ids, jobs,
                         members)


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
def test_a_rerun_that_moves_the_row_minimum(backend):
    rng, hours, mask, prices, ids, jobs, members = _universe(3)
    state = _fleet(backend, hours, mask, prices, ids, jobs, members)
    row = 2
    cost = np.where(mask[row], hours[row] * prices, np.inf)
    cheapest, dearest = int(np.argmin(cost)), \
        int(np.argmax(np.where(mask[row], cost, -np.inf)))
    # the cheapest cell re-runs far slower: the minimum moves elsewhere
    state.ingest([(jobs[row], ids[cheapest], hours[row, cheapest] * 50)])
    hours[row, cheapest] *= 50
    # a re-run of the dearest cell undercuts every other cell
    state.ingest([(jobs[row], ids[dearest], 1e-3)])
    hours[row, dearest] = 1e-3
    _assert_matches_cold(state, backend, hours, mask, prices, ids, jobs,
                         members)


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
def test_a_new_cell_makes_a_config_finite_for_one_member_only(backend):
    rng, hours, mask, prices, ids, jobs, members = _universe(4)
    mask[:, 5] = False
    hours[:, 5] = np.nan
    state = _fleet(backend, hours, mask, prices, ids, jobs, members)
    assert state.counts("one")[5] == 0
    assert np.isinf([r.score for r in state.ranking("one")
                     if r.config_id == "c5"][0])
    state.ingest([("j3", "c5", 1e-4)])          # only "one" and "all"
    hours[3, 5], mask[3, 5] = 1e-4, True
    assert state.top_k("one", 1)[0].config_id == "c5"
    assert state.counts("one")[5] == 1 and state.counts("all")[5] == 1
    assert state.counts("low")[5] == 0 and state.counts("high")[5] == 0
    assert np.isinf([r.score for r in state.ranking("low")
                     if r.config_id == "c5"][0])
    _assert_matches_cold(state, backend, hours, mask, prices, ids, jobs,
                         members)


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
def test_an_ingest_between_two_heads_of_one_tick(backend):
    rng, hours, mask, prices, ids, jobs, members = _universe(5)
    reg = MetricsRegistry()
    state = _fleet(backend, hours, mask, prices, ids, jobs, members,
                   metrics=reg)
    before = state.top_k("low", 3)
    assert state.top_k("high", 3) and state.ranking("low")
    state.ingest([("j1", "c7", 1e-4)])        # row 1's minimum moves
    hours[1, 7], mask[1, 7] = 1e-4, True
    after = state.top_k("low", 3)
    assert after != before
    assert state.ranking("low")[:3] == after          # memo dropped
    assert reg.counter("rank.head_batches").value == 2
    assert reg.counter("rank.ingest_batches").value == 1
    _assert_matches_cold(state, backend, hours, mask, prices, ids, jobs,
                         members)


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
def test_capacity_doubles_after_an_ingest(backend):
    rng, hours, mask, prices, ids, jobs, members = _universe(6)
    state = _fleet(backend, hours, mask, prices, ids, jobs, members,
                   capacity=4)
    state.ingest(_cells("j6", [0, 1, 2], [0.2, 0.3, 0.4], ids))
    hours[6, :3], mask[6, :3] = [0.2, 0.3, 0.4], True
    extra = {f"x{i}": [i, (i + 3) % 8] for i in range(5)}
    for key, rows in extra.items():
        state.add_state(key, rows=rows)
    assert state.realloc_count == 2
    state.ingest(_cells("j2", [3, 4], [0.5, 0.6], ids))
    hours[2, 3:5], mask[2, 3:5] = [0.5, 0.6], True
    _assert_matches_cold(state, backend, hours, mask, prices, ids, jobs,
                         {**members, **extra})


@needs_jax
def test_ingest_validates_before_it_touches_the_fleet():
    rng, hours, mask, prices, ids, jobs, members = _universe(7)
    state = _fleet("jax_batched", hours, mask, prices, ids, jobs, members)
    counts = state.counts("all")
    with pytest.raises(ValueError, match="unknown"):
        state.ingest([("j0", "c0", 1.0), ("nobody", "c0", 1.0)])
    with pytest.raises(ValueError, match="runtime"):
        state.ingest([("j0", "c1", 1.0), ("j0", "c0", -1.0)])
    np.testing.assert_array_equal(state.counts("all"), counts)
    assert state.ingest([]) == 0


# --- the service: fleets survive, fallbacks are counted ------------------------------

def _service(backend, metrics=None):
    ids = [f"c{i}" for i in range(12)]
    store = ProfilingStore(config_ids=ids)
    rng = np.random.default_rng(11)
    for j in range(6):
        klass = JobClass.A if j % 2 else JobClass.B
        for i, c in enumerate(ids):
            if (i + j) % 3:             # partly profiled
                store.add(f"j{j}", c, float(rng.uniform(0.2, 4.0)),
                          job_class=klass, group=f"g{j % 3}")
    base = {c: 1.0 + i for i, c in enumerate(ids)}
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend=backend, serve_top_k=3, metrics=metrics)
    return svc, store, ids


SELECTIONS = [(JobClass.A, ("g0",)), (JobClass.B, ("g1",)), (None, ())]


def _cold_heads(store, svc, ids, k=3):
    prices = svc.catalog.price_vector(svc.price_source)
    out = {}
    for klass, excl in SELECTIONS:
        jobs = store.select_jobs(job_class=klass, exclude_groups=excl)
        hours, mask = store.matrix(job_ids=jobs, config_ids=ids)
        out[(klass, excl)] = rank_dense(np.nan_to_num(hours), mask, prices,
                                        ids)
    return out


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
def test_service_ingest_keeps_the_fleet_warm(backend):
    reg = MetricsRegistry()
    svc, store, ids = _service(backend, reg)
    for sel in SELECTIONS:
        svc.rank_head(*sel, k=3)
    fleet = svc._batched
    builds = reg.histogram("rank.build").count
    assert reg.counter("rank.cold_rebuilds").value == 1
    rng = np.random.default_rng(0)
    for tick in range(6):
        svc.ingest([(f"j{tick % 6}", f"c{c}", float(rng.uniform(0.1, 3)))
                    for c in rng.choice(12, 3, replace=False)])
        svc.reprice({"c1": float(rng.uniform(1, 5)),
                     "c7": float(rng.uniform(1, 5))})
        for sel in SELECTIONS:
            svc.rank_head(*sel, k=3)
    assert svc._batched is fleet and fleet.n_active == len(SELECTIONS)
    assert reg.histogram("rank.build").count == builds
    assert reg.counter("rank.cold_rebuilds").value == 1
    assert reg.counter("rank.ingest_batches").value == 6
    assert reg.histogram("ingest.apply").count == 6
    assert reg.histogram("ingest.dispatch").count == 6
    contract = score_contract(backend)
    for sel, cold in _cold_heads(store, svc, ids).items():
        assert_within_contract(svc.rank(*sel), cold, contract)


@needs_jax
def test_cells_outside_the_catalog_are_stored_and_rank_nowhere():
    svc, store, ids = _service("jax_batched")
    svc.rank_head(None, (), k=3)
    fleet = svc._batched
    assert svc.ingest([("j0", "elsewhere", 0.01), ("j0", "c0", 0.5)]) == 2
    assert svc._batched is fleet and store.has("j0", "elsewhere")
    for sel, cold in _cold_heads(store, svc, ids).items():
        assert_within_contract(svc.rank(*sel), cold,
                               score_contract("jax_batched"))


@pytest.mark.parametrize("backend", ["jax_batched", "jax_sharded",
                                     "numpy"])
def test_the_cold_path_is_an_explicit_counted_fallback(backend):
    """A new job row on the fleet backends, and any ingest on
    ``jax_sharded`` and numpy, drop the live states
    (``service.ingest_fallbacks``); the rebuild that follows serves the
    new store (``rank.cold_rebuilds`` on the fleet backends)."""
    if not backend_available(backend):
        pytest.skip("jax not installed")
    reg = MetricsRegistry()
    svc, store, ids = _service(backend, reg)
    fallbacks = reg.counter("service.ingest_fallbacks")
    rebuilds = reg.counter("rank.cold_rebuilds")

    def serve():
        return {sel: svc.rank_head(*sel, k=3)[0] for sel in SELECTIONS}

    serve()
    fleet = backend != "numpy"
    assert rebuilds.value == (1 if fleet else 0)
    svc.ingest([("j1", "c0", 0.05)])             # a known job's cell
    cold_known = backend not in FLEETS
    assert fallbacks.value == (1 if cold_known else 0)
    serve()
    assert rebuilds.value == (2 if cold_known and fleet else
                              1 if fleet else 0)
    svc.ingest([("j9", "c3", 0.5)])              # a new row
    assert fallbacks.value == (2 if cold_known else 1)
    assert svc._batched is None and not svc._states
    heads = serve()
    assert rebuilds.value == (3 if cold_known and fleet else
                              2 if fleet else 0)
    contract = score_contract(backend)
    for sel, cold in _cold_heads(store, svc, ids).items():
        assert contract.winner_matches(heads[sel][0].config_id, cold)
        assert_within_contract(svc.rank(*sel), cold, contract)


# --- the front end and the journal ---------------------------------------------------

def _recorded(base, n_ticks, seed=3):
    sim = SimulatedSpotFeed(base, seed=seed, change_fraction=0.5)
    return RecordedPriceFeed.loads(record_feed(sim, n_ticks))


class _RecordsFeed:
    """A recorded feed that hands one execution record (a few cells of
    one job) to the front end as each tick is polled, the way an
    execution lands while the market moves."""

    def __init__(self, inner, fe_ref, records):
        self.inner, self.ticks = inner, inner.ticks
        self.fe_ref, self.records = fe_ref, records

    def config_ids(self):
        return self.inner.config_ids()

    def poll(self, tick):
        batch = self.inner.poll(tick)
        self.fe_ref[0].add_profiles(self.records[tick % len(self.records)])
        return batch


def _records(ids, n=7, seed=5):
    rng = np.random.default_rng(seed)
    return [[(f"j{int(rng.integers(6))}", ids[c],
              float(rng.uniform(0.05, 4.0)))
             for c in rng.choice(len(ids), 3, replace=False)]
            for _ in range(n)]


def _served(backend, n_ticks=8, threaded=False):
    svc, store, ids = _service(backend)
    pristine = store.copy()
    fe_ref = [None]
    base = {c: p for c, p in zip(ids, svc.catalog.price_vector(
        svc.price_source))}
    feed = _RecordsFeed(_recorded(base, n_ticks), fe_ref, _records(ids))
    fe = ServeFrontend(svc, feed, workers=2, top_k=3)
    fe_ref[0] = fe
    subs = [Submission("j1"), Submission("j2"), Submission("j4"),
            Submission("j0", exclude_groups=())]
    fe.warm(subs)
    if threaded:
        fe.start()
        for i in range(60):
            fe.submit(subs[i % len(subs)])
        fe.await_ticks(n_ticks, timeout=60)
        fe.drain(timeout=60)
        fe.shutdown()
    else:
        for t in range(n_ticks):
            for s in subs:
                fe.submit(s)
            fe.serve_queued()
            fe.step_tick()
        fe.close()
    return fe, svc, store, pristine


def test_numpy_frontend_with_arrivals_audits_bit_exact():
    fe, svc, store, pristine = _served("numpy")
    text = fe.journal_dump()
    records = JournalReplayer(pristine, text).records
    profiles = [r for r in records if r["kind"] == "profile"]
    ticks = [r for r in records if r["kind"] == "tick"]
    assert len(profiles) == len(ticks) == 8
    # each tick's records come before its prices, at the epoch before it
    for p, t in zip(profiles, ticks):
        assert p["tick"] == t["tick"] and p["seq"] < t["seq"]
        assert p["price_epoch"] == t["price_epoch"] - 1
    audit = JournalReplayer(pristine, text).audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.decisions > 0 and audit.drift == ()
    # the replayer's store is left as it was given
    assert pristine.version < store.version


def test_the_audit_needs_the_profile_records():
    """Without its ``profile`` records the same journal no longer audits:
    the decisions after an arrival were served from the new cells."""
    fe, svc, store, pristine = _served("numpy")
    lines = fe.journal_dump().splitlines()
    stripped = "\n".join(ln for ln in lines
                         if json.loads(ln).get("kind") != "profile")
    assert not JournalReplayer(pristine, stripped + "\n").audit().ok
    # nor does the final store stand in for the records
    assert not JournalReplayer(store, "\n".join(lines) + "\n").audit().ok


@needs_jax
@pytest.mark.parametrize("backend", sorted(FLEETS))
def test_fleet_frontend_with_arrivals_audits_within_contract(backend):
    fe, svc, store, pristine = _served(backend, threaded=True)
    audit = JournalReplayer(pristine, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.contract == score_contract(backend)
    assert svc.metrics.counter("rank.cold_rebuilds").value == 1
    assert svc.metrics.histogram("rank.build").count == 4   # the warm-up
    assert svc.metrics.counter("rank.ingest_batches").value == 8


def test_a_failed_poll_keeps_the_records_for_the_retry():
    svc, store, ids = _service("numpy")

    class Flaky:
        ticks = 3

        def __init__(self):
            self.failed = False

        def poll(self, tick):
            if tick == 1 and not self.failed:
                self.failed = True
                raise ConnectionError("outage")
            return ()

    fe = ServeFrontend(svc, Flaky(), workers=1)
    fe.add_profiles([("j0", "c0", 0.3)])
    assert fe.step_tick() == "tick" and store.runtime_hours("j0",
                                                            "c0") == 0.3
    fe.add_profiles([("j0", "c0", 0.4)])
    assert fe.step_tick() == "feed-error"
    assert store.runtime_hours("j0", "c0") == 0.3     # not yet applied
    assert fe.step_tick() == "tick"
    assert store.runtime_hours("j0", "c0") == 0.4
    with pytest.raises(ValueError, match="runtime"):
        fe.add_profiles([("j0", "c0", 0.0)])
    kinds = [json.loads(ln)["kind"]
             for ln in fe.journal_dump().splitlines()[1:]]
    assert kinds.count("profile") == 2 and kinds.count("feed-error") == 1


def test_records_from_many_producer_threads_all_land():
    """Producers hand records to a running front end from their own
    threads, with a short switch interval; every cell lands in the
    store and the journal, and the journal still audits bit-exact."""
    import sys
    import threading

    svc, store, ids = _service("numpy")
    pristine = store.copy()
    base = {c: p for c, p in zip(ids, svc.catalog.price_vector(
        svc.price_source))}
    fe = ServeFrontend(svc, _recorded(base, 20), workers=2, top_k=3,
                       ticks=10 ** 6)
    fe.warm([Submission("j1"), Submission("j2")])
    # each producer writes its own job's cells, each cell several times
    writes = [[(f"j{p}", ids[i % 12], 0.1 + 0.01 * (p * 50 + i))
               for i in range(50)] for p in range(4)]

    def produce(mine):
        for cell in mine:
            fe.add_profiles([cell])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        fe.start()
        threads = [threading.Thread(target=produce, args=(mine,))
                   for mine in writes]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
            assert not t.is_alive()
        for i in range(40):
            fe.submit(Submission("j1" if i % 2 else "j2"))
        fe.await_ticks(fe.ticker.tick_count + 3, timeout=30)
        fe.drain(timeout=30)
        fe.shutdown()
    finally:
        sys.setswitchinterval(interval)
    journaled = [tuple(c) for ln in fe.journal_dump().splitlines()[1:]
                 for c in json.loads(ln).get("cells", [])]
    assert sorted(journaled) == sorted(c for mine in writes for c in mine)
    # a producer's later write of a cell wins over its earlier one
    for mine in writes:
        for job, config, hours in mine[-12:]:
            assert store.runtime_hours(job, config) == hours
    assert JournalReplayer(pristine, fe.journal_dump()).audit().ok
