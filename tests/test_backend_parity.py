"""Differential backend-parity suite (ISSUE 4 satellites).

Hypothesis-driven: random universes and event-bearing reprice streams
(the discount/eviction strategies from ``test_rank_properties``) assert
that the float32 device path — cold jax ``rank_dense`` and the jitted
delta step of a one-member :class:`~repro.selector.BatchedRankState` —
picks the same winner as the numpy float64 backend (or one tied within
tolerance) and keeps every score inside the
:class:`~repro.selector.ScoreContract` envelope.

Also home to the no-jax degradation test: the selector core must import
and rank with jax uninstalled, and the jax-family backends must fail
with the typed, skippable :class:`~repro.selector.BackendUnavailableError`.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.selector import (BackendUnavailableError, BatchedRankState,
                            RankState, ScoreContract, SelectionService,
                            backend_available, rank_dense, score_contract)

try:        # the property half needs hypothesis; the differential
            # smoke/edge tests below run without it
    import hypothesis
    from hypothesis import given, settings, strategies as st
    from test_rank_properties import (delta_streams, event_markets,
                                      _event_feed, runtime_tables)
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_jax = pytest.mark.skipif(not backend_available("jax_batched"),
                               reason="jax not installed")

CONTRACT = score_contract("jax_batched")


def assert_within_contract(candidate, reference,
                           contract: ScoreContract = CONTRACT):
    """``candidate`` ranking honors ``contract`` against ``reference``:
    same winner (or tied within tolerance) and every per-config score
    inside the rel/abs envelope."""
    assert [r.config_id for r in candidate] != []
    ref_score = {r.config_id: r.score for r in reference}
    assert contract.winner_matches(candidate[0].config_id, reference), (
        candidate[0], reference[0])
    for r in candidate:
        assert contract.scores_match(r.score, ref_score[r.config_id]), (
            r, ref_score[r.config_id])


def fleet_of_one(hours, mask, prices, ids, job_ids=None):
    """A :class:`BatchedRankState` holding one member, ``"m"``, over every
    job row: the incremental float32 ranking of the whole universe,
    served as ``ranking("m")``, ``top_k("m", k)`` and ``winner("m")``."""
    state = BatchedRankState(hours, mask, prices, ids, job_ids=job_ids)
    state.add_state("m", rows=range(np.asarray(hours).shape[0]))
    return state


# --- the contract itself -----------------------------------------------------------

def test_score_contracts_shape():
    exact = score_contract("numpy")
    assert exact.bit_identical and exact.rel_tol == exact.abs_tol == 0.0
    tol = score_contract("jax")
    assert not tol.bit_identical and tol.rel_tol > 0
    with pytest.raises(ValueError, match="unknown backend"):
        score_contract("bogus")


def test_contract_score_matching():
    exact, tol = score_contract("numpy"), score_contract("jax")
    assert exact.scores_match(1.0, 1.0)
    assert not exact.scores_match(1.0, np.nextafter(1.0, 2.0))
    assert tol.scores_match(1.0, 1.0 + 0.5 * tol.rel_tol)
    assert not tol.scores_match(1.0, 1.0 + 10 * tol.rel_tol)
    # unprofiled configs score +inf on every backend; inf ties inf
    assert exact.scores_match(float("inf"), float("inf"))
    assert tol.scores_match(float("inf"), float("inf"))


def test_contract_winner_matching():
    from repro.selector import RankedConfig
    tol = score_contract("jax")
    ranking = [RankedConfig("a", 2.0, 1.0),
               RankedConfig("b", 2.0 + 0.1 * tol.rel_tol, 1.0),
               RankedConfig("c", 3.0, 1.5)]
    assert tol.winner_matches("a", ranking)
    assert tol.winner_matches("b", ranking)          # tied within tol
    assert not tol.winner_matches("c", ranking)      # genuinely worse
    assert not tol.winner_matches("ghost", ranking)
    exact = score_contract("numpy")
    assert exact.winner_matches("a", ranking)
    assert not exact.winner_matches("b", ranking)    # ties need bits


# --- deterministic differential sweeps (run without hypothesis) --------------------

def _random_universe(seed, n_jobs, n_cfgs, partial=False):
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.01, 100.0, (n_jobs, n_cfgs))
    if partial:
        mask = rng.random((n_jobs, n_cfgs)) > 0.25
        mask[np.arange(n_jobs), rng.integers(0, n_cfgs, n_jobs)] = True
    else:
        mask = np.ones((n_jobs, n_cfgs), dtype=bool)
    prices = rng.uniform(0.1, 50.0, n_cfgs)
    ids = [f"c{i}" for i in range(n_cfgs)]
    return rng, hours, mask, prices, ids


@needs_jax
@pytest.mark.parametrize("seed", range(8))
def test_cold_jax_within_contract_of_numpy_seeded(seed):
    """Seeded differential sweep (runs with or without hypothesis):
    cold jax ranks within contract of cold numpy on random universes,
    dense and partially profiled."""
    _, hours, mask, prices, ids = _random_universe(seed, 4 + seed % 5,
                                                   3 + seed,
                                                   partial=seed % 2 == 1)
    ref = rank_dense(hours, mask, prices, ids)
    jx = rank_dense(hours, mask, prices, ids, backend="jax")
    assert_within_contract(jx, ref)


@needs_jax
@pytest.mark.parametrize("seed", range(6))
def test_jax_delta_stream_within_contract_seeded(seed):
    """Seeded reprice streams: after every tick the jitted delta path
    agrees with the float64 incremental reference AND with a cold jax
    rank at the live prices, under the contract."""
    rng, hours, mask, prices, ids = _random_universe(
        100 + seed, 5, 12 + 4 * seed, partial=seed % 2 == 0)
    jx = fleet_of_one(hours, mask, prices.copy(), ids)
    ref = RankState(hours, mask, prices.copy(), ids)
    live = prices.copy()
    for _ in range(6):
        k = int(rng.integers(1, len(ids)))
        cols = rng.choice(len(ids), k, replace=False)
        deltas = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                  for c in cols}
        jx.reprice(deltas)
        ref.reprice(deltas)
        for c, p in deltas.items():
            live[int(c[1:])] = p
        assert_within_contract(jx.ranking("m"), ref.ranking())
        assert_within_contract(
            jx.ranking("m"),
            rank_dense(hours, mask, live, ids, backend="jax"))


@needs_jax
def test_event_market_jax_reprice_within_contract_deterministic():
    """Discount/eviction boundary re-quote bursts through the jax delta
    path stay within contract of the cold float64 rank at every tick
    (the deterministic analogue of the hypothesis event_markets sweep)."""
    from repro.market import MarketEvent, SimulatedSpotFeed
    rng, hours, mask, prices, ids = _random_universe(7, 4, 10)
    base = {c: float(p) for c, p in zip(ids, prices)}
    feed = SimulatedSpotFeed(
        base, seed=5, change_fraction=0.3, volatility=0.15,
        events=[MarketEvent("us-central1", 2, 4, 0.25, "discount"),
                MarketEvent("europe-west3", 5, 3, 4.0, "eviction")])
    state = fleet_of_one(hours, mask, prices.copy(), ids)
    live = prices.copy()
    for t in range(10):
        batch = feed.poll(t)
        if not batch:
            continue
        state.reprice({d.config_id: d.price for d in batch})
        for d in batch:
            live[ids.index(d.config_id)] = d.price
        assert_within_contract(state.ranking("m"),
                               rank_dense(hours, mask, live, ids))


# --- hypothesis property half (skips quietly when hypothesis is absent) ------------

if HAVE_HYPOTHESIS:
    @needs_jax
    @settings(max_examples=25, deadline=None)
    @given(runtime_tables())
    def test_cold_jax_within_contract_of_numpy(table):
        jobs, cfgs, rt, prices = table
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        pv = np.asarray([prices[c] for c in cfgs])
        ref = rank_dense(hours, mask, pv, cfgs, job_ids=jobs)
        jx = rank_dense(hours, mask, pv, cfgs, job_ids=jobs,
                        backend="jax")
        assert_within_contract(jx, ref)

    @needs_jax
    @settings(max_examples=20, deadline=None)
    @given(delta_streams())
    def test_jax_delta_stream_within_contract_of_numpy(data):
        """After every tick of any reprice stream, the jitted delta
        path agrees with the float64 incremental reference under the
        contract."""
        jobs, cfgs, rt, prices, stream = data
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        pv = np.asarray([prices[c] for c in cfgs])
        jx = fleet_of_one(hours, mask, pv, cfgs, job_ids=jobs)
        ref = RankState(hours, mask, pv.copy(), cfgs, job_ids=jobs)
        for deltas in stream:
            jx.reprice(deltas)
            ref.reprice(deltas)
            assert_within_contract(jx.ranking("m"), ref.ranking())

    @needs_jax
    @settings(max_examples=20, deadline=None)
    @given(delta_streams())
    def test_jax_delta_path_within_contract_of_jax_cold(data):
        """The jitted delta-update kernel vs a cold jax rank at the
        same prices: both float32, so the only divergence is the delta
        path's accumulated drift — it must stay inside the contract
        too."""
        jobs, cfgs, rt, prices, stream = data
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        live = np.asarray([prices[c] for c in cfgs])
        jx = fleet_of_one(hours, mask, live.copy(), cfgs, job_ids=jobs)
        for deltas in stream:
            jx.reprice(deltas)
            for c, p in deltas.items():
                live[cfgs.index(c)] = p
            cold = rank_dense(hours, mask, live, cfgs, job_ids=jobs,
                              backend="jax")
            assert_within_contract(jx.ranking("m"), cold)

    @needs_jax
    @settings(max_examples=20, deadline=None)
    @given(event_markets())
    def test_event_market_jax_reprice_within_contract(market):
        """Event-bearing markets (discount/eviction boundary re-quote
        bursts) through the jax delta path stay within contract of the
        cold float64 rank at every tick."""
        cfgs, base, events, seed, change_fraction, n_ticks, jobs, rt = \
            market
        hours = np.asarray([[rt[(j, c)] for c in cfgs] for j in jobs])
        mask = np.ones_like(hours, dtype=bool)
        live = np.asarray([base[c] for c in cfgs])
        state = fleet_of_one(hours, mask, live.copy(), cfgs, job_ids=jobs)
        feed = _event_feed(base, events, seed, change_fraction)
        for t in range(n_ticks):
            batch = feed.poll(t)
            if not batch:
                continue
            state.reprice({d.config_id: d.price for d in batch})
            for d in batch:
                live[cfgs.index(d.config_id)] = d.price
            assert_within_contract(state.ranking("m"),
                                   rank_dense(hours, mask, live, cfgs,
                                              job_ids=jobs))

    @needs_jax
    @settings(max_examples=10, deadline=None)
    @given(event_markets(), st.integers(0, 2 ** 16))
    def test_event_market_jax_daemon_audits_within_tolerance(market,
                                                             stream_seed):
        """End-to-end: a fleet-backed daemon over any event-bearing
        market journals decisions the tolerance audit confirms against
        cold float64 re-ranks."""
        from repro.core.trace import JobClass
        from repro.market import JournalReplayer, SelectionDaemon, \
            synthetic_stream
        from repro.selector import (IdentityCatalog, PriceTable,
                                    ProfilingStore)
        cfgs, base, events, seed, change_fraction, n_ticks, _, _ = market
        store = ProfilingStore(config_ids=cfgs)
        for j in range(4):
            for i, c in enumerate(cfgs):
                store.add(f"j{j}", c, 0.1 + ((j * 7 + i * 3) % 11) / 5.0,
                          job_class=JobClass.A if j % 2 else JobClass.B)
        svc = SelectionService(IdentityCatalog(cfgs), store,
                               PriceTable(base), backend="jax_batched")
        daemon = SelectionDaemon(svc, _event_feed(base, events, seed,
                                                  change_fraction))
        daemon.run(synthetic_stream(store.job_ids, 25, seed=stream_seed,
                                    tick_fraction=0.4))
        audit = JournalReplayer(store, daemon.journal_dump()).audit()
        assert audit.ok, audit.mismatches[:3]
        assert audit.contract == CONTRACT
        assert audit.decisions == daemon.stats.decisions
else:
    @pytest.mark.skip(reason="hypothesis not installed (property half "
                             "of the parity suite)")
    def test_backend_parity_properties_skipped():
        pass  # pragma: no cover


# --- mask / unprofiled coverage ----------------------------------------------------

@needs_jax
def test_jax_state_partial_mask_and_unprofiled_columns():
    """Unprofiled columns score +inf on both backends, and partially
    masked universes reprice within contract (masked cells never leak
    into row minima)."""
    rng = np.random.default_rng(3)
    J, C = 6, 40
    hours = rng.uniform(0.05, 10.0, (J, C))
    mask = rng.random((J, C)) > 0.4
    mask[np.arange(J) % J, rng.integers(0, C - 1, J)] = True
    mask[:, C - 1] = False                      # never profiled
    prices = rng.uniform(0.5, 20.0, C)
    ids = [f"c{i}" for i in range(C)]
    jx = fleet_of_one(hours, mask, prices.copy(), ids)
    ref = RankState(hours, mask, prices.copy(), ids)
    live = prices.copy()
    for t in range(8):
        cols = rng.choice(C, 5, replace=False)
        batch = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                 for c in cols}
        jx.reprice(batch)
        ref.reprice(batch)
        for c, p in batch.items():
            live[int(c[1:])] = p
        assert_within_contract(jx.ranking("m"), ref.ranking())
        unprofiled = [r for r in jx.ranking("m")
                      if r.config_id == ids[C - 1]]
        assert unprofiled[0].score == float("inf")
        # the device-side winner peek agrees with the materialized list
        assert jx.winner("m") == jx.ranking("m")[0]


@needs_jax
def test_jax_state_validates_like_numpy():
    hours = np.asarray([[1.0, 2.0]])
    mask = np.ones_like(hours, dtype=bool)
    with pytest.raises(ValueError, match="shape mismatch"):
        fleet_of_one(hours, mask, np.asarray([1.0]), ["a", "b"])
    with pytest.raises(ValueError, match="duplicate config ids"):
        fleet_of_one(hours, mask, np.asarray([1.0, 2.0]), ["a", "a"])
    state = fleet_of_one(hours, mask, np.asarray([1.0, 2.0]), ["a", "b"])
    with pytest.raises(ValueError, match="unknown config id"):
        state.reprice({"ghost": 1.0})
    with pytest.raises(ValueError, match="non-positive"):
        state.reprice({"a": -1.0})
    assert state.reprice({}) == 0
    from repro.selector import NothingRankableError
    with pytest.raises(NothingRankableError):
        fleet_of_one(np.zeros((0, 2)), np.zeros((0, 2), dtype=bool),
                     np.asarray([1.0, 2.0]), ["a", "b"])


@needs_jax
def test_jax_delta_bucket_padding_is_idempotent():
    """Batch sizes that straddle the power-of-4 padding buckets (k=1,
    7, 8, 9, 32, all-C) all land within contract — the padded duplicate
    (column, price) pairs must be invisible."""
    rng = np.random.default_rng(11)
    J, C = 5, 64
    hours = rng.uniform(0.05, 10.0, (J, C))
    mask = np.ones((J, C), dtype=bool)
    prices = rng.uniform(0.5, 20.0, C)
    ids = [f"c{i}" for i in range(C)]
    jx = fleet_of_one(hours, mask, prices.copy(), ids)
    live = prices.copy()
    for k in (1, 7, 8, 9, 32, C):
        cols = rng.choice(C, k, replace=False)
        batch = {ids[c]: float(live[c] * rng.uniform(0.5, 2.0))
                 for c in cols}
        jx.reprice(batch)
        for c, p in batch.items():
            live[int(c[1:])] = p
        assert_within_contract(jx.ranking("m"),
                               rank_dense(hours, mask, live, ids))


# --- service-level backend knob ----------------------------------------------------

@needs_jax
def test_service_backend_knob_serves_jax_states():
    from repro.core.trace import JobClass
    from repro.selector import IdentityCatalog, PriceTable, ProfilingStore
    rng = np.random.default_rng(1)
    ids = [f"c{i}" for i in range(16)]
    store = ProfilingStore(config_ids=ids)
    for j in range(4):
        for c in ids:
            store.add(f"j{j}", c, float(rng.uniform(0.1, 5.0)),
                      job_class=JobClass.A if j % 2 else JobClass.B)
    table = PriceTable({c: float(rng.uniform(1.0, 20.0)) for c in ids})
    svc = SelectionService(IdentityCatalog(ids), store, table,
                           backend="jax_batched")
    ref = SelectionService(IdentityCatalog(ids), store,
                           PriceTable(dict(table.items())),
                           backend="numpy")
    d1 = svc.submit("j1")
    d2 = ref.submit("j1")
    assert_within_contract(list(d1.ranking), list(d2.ranking))
    # ticks run the fleet's delta kernel through service.reprice
    deltas = {ids[0]: 0.7, ids[5]: 9.0}
    assert svc.reprice(deltas) == 1       # the one live member refreshed
    ref.reprice(deltas)
    assert_within_contract(list(svc.submit("j1").ranking),
                           list(ref.submit("j1").ranking))
    assert svc.reprice_refreshes == 1


@pytest.mark.parametrize("backend", ["torch", "jax"])
def test_service_rejects_unknown_backend_at_construction(backend):
    """A misspelled backend, or the retired per-state ``"jax"``, fails
    when the service is built, not on the first submit — wiring a
    never-rankable service into a daemon should be impossible — and the
    message names what a service can be built with."""
    from repro.selector import IdentityCatalog, PriceTable, ProfilingStore
    store = ProfilingStore(config_ids=["a"])
    store.add("j", "a", 1.0)
    with pytest.raises(ValueError, match=r"unknown backend .*'numpy', "
                       r"'jax_batched', 'jax_sharded', 'jax_pallas'"):
        SelectionService(IdentityCatalog(["a"]), store,
                         PriceTable({"a": 1.0}), backend=backend)


def test_default_backend_resolves_env(monkeypatch):
    """A service built without ``backend=`` serves numpy whatever the
    environment holds: the device path is the caller's choice."""
    from repro.selector import IdentityCatalog, PriceTable, ProfilingStore
    monkeypatch.setenv("FLORA_RANK_BACKEND", "jax_batched")
    store = ProfilingStore(config_ids=["a", "b"])
    store.add("j", "a", 1.0)
    store.add("j", "b", 2.0)
    svc = SelectionService(IdentityCatalog(["a", "b"]), store,
                           PriceTable({"a": 1.0, "b": 1.0}))
    assert svc.backend == "numpy"
    assert svc.submit("j").config_id == "a"
    assert svc._batched is None and len(svc._states) == 1


# --- graceful degradation with jax uninstalled (satellite fix) ---------------------

NO_JAX_PROBE = textwrap.dedent("""
    import sys
    # simulate an environment without jax: a None entry makes any
    # "import jax" raise ImportError before site-packages is consulted
    sys.modules["jax"] = None
    sys.modules["jax.numpy"] = None

    from repro.selector import (BackendUnavailableError, BatchedRankState,
                                PallasBatchedRankState, SelectionService,
                                IdentityCatalog, PriceTable,
                                ProfilingStore, rank_dense)
    import repro.selector.rank as rank
    assert not rank._HAVE_JAX

    import numpy as np
    hours = np.asarray([[1.0, 2.0], [2.0, 1.0]])
    mask = np.ones_like(hours, dtype=bool)
    prices = np.asarray([3.0, 4.0])

    # the numpy path is fully functional
    ranked = rank_dense(hours, mask, prices, ["a", "b"])
    assert ranked[0].config_id == "a"
    store = ProfilingStore(config_ids=["a", "b"])
    store.add("j0", "a", 1.0); store.add("j0", "b", 2.0)
    svc = SelectionService(IdentityCatalog(["a", "b"]), store,
                           PriceTable({"a": 3.0, "b": 4.0}))
    assert svc.submit("j0").config_id == "a"

    # the jax backend fails with the *typed* skippable error everywhere
    for attempt in (
        lambda: rank_dense(hours, mask, prices, ["a", "b"],
                           backend="jax"),
        lambda: BatchedRankState(hours, mask, prices, ["a", "b"]),
        lambda: PallasBatchedRankState(hours, mask, prices, ["a", "b"]),
        lambda: SelectionService(IdentityCatalog(["a", "b"]), store,
                                 PriceTable({"a": 3.0, "b": 4.0}),
                                 backend="jax_batched"),
        lambda: SelectionService(IdentityCatalog(["a", "b"]), store,
                                 PriceTable({"a": 3.0, "b": 4.0}),
                                 backend="jax_pallas"),
    ):
        try:
            attempt()
        except BackendUnavailableError:
            pass
        else:
            raise AssertionError("expected BackendUnavailableError")
    print("NO-JAX-OK")
""")


def test_selector_core_works_with_jax_uninstalled():
    """Satellite (ISSUE 4): with jax unimportable (sys.modules guard in
    a fresh interpreter, so this process's jax state is untouched), the
    selector imports, ranks and serves on numpy, and the jax-family
    backends raise the typed ``BackendUnavailableError`` — previously an
    untyped ``RuntimeError``."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(repo_root, "src")}
    result = subprocess.run(
        [sys.executable, "-c", NO_JAX_PROBE],
        capture_output=True, text=True, env=env, cwd=repo_root)
    assert result.returncode == 0, result.stderr
    assert "NO-JAX-OK" in result.stdout


def test_backend_unavailable_error_is_typed():
    assert issubclass(BackendUnavailableError, RuntimeError)
    assert not issubclass(BackendUnavailableError, ValueError)
