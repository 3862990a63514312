"""Live-market benchmarks: incremental reprice + the selection daemon.

    PYTHONPATH=src python benchmarks/market_bench.py

Gated claims (the script exits nonzero if one regresses, which is the
CI gate):

  * incremental ``RankState.reprice`` beats a full ``rank_dense`` by >=5x
    at 10k configs with <=1% of prices changed per tick, with rankings
    **bit-identical** to the cold path (exact float equality, not approx).
    The gated comparison is the per-tick update (what ``SelectionService``
    pays per tick — rankings materialize lazily on the next submission);
    the ``+materialize`` row reports the tick+first-submission end-to-end
    cost, where building/sorting the C ``RankedConfig`` objects dominates
    *both* paths equally and compresses the ratio;
  * one batched dispatch reprices a whole fleet of >=8 live rankings
    (``reprice_batched_*`` rows: ``one_dispatch_per_tick`` +
    ``within_contract`` gates, DESIGN.md §10);
  * the fused Pallas delta-rank kernel (``jax_pallas``, DESIGN.md §14)
    reprices the fleet in ONE ``pallas_call`` per tick within the same
    contract, head-to-head against the XLA delta path
    (``reprice_pallas_*`` rows: ``one_dispatch_per_tick`` +
    ``within_contract`` gates; the speed column is informational — on
    CPU the kernel runs ``interpret=True``);
  * device-side top-k serving returns the head of the materialized
    ranking (``topk_serve_*`` rows: the ``head_matches`` gate; the
    ``end_to_end_speedup`` column against a full C-config host sort is
    informational);
  * the device-sharded fleet (``jax_sharded``, DESIGN.md §13) spends one
    *collective* shard_map dispatch per tick and, on 8 devices at 100k
    configs, beats the single-device batched fleet
    (``reprice_sharded_*`` rows: ``one_dispatch_per_tick`` +
    ``within_contract`` + ``beats_single_device`` gates; the 8-device
    row needs ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on
    a CPU host and emits ``skipped=...`` elsewhere);
  * ``SelectionDaemon`` sustains a 10k-event mixed submission/tick stream
    deterministically — the same seed yields a byte-identical journal.

Prints ``name,us_per_call,derived`` CSV rows and writes the same rows as
machine-readable ``BENCH_market.json`` (override the path with the
``BENCH_MARKET_JSON`` env var) so CI can track the perf trajectory.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from _bench_io import BenchRows, Gates, check_gates
from repro.launch.compile_cache import enable_compile_cache
from repro.core.trace import JobClass
from repro.market import SelectionDaemon, SimulatedSpotFeed, synthetic_stream
from repro.selector import (BatchedRankState, IdentityCatalog,
                            PallasBatchedRankState, PriceTable,
                            ProfilingStore, RankState, SelectionService,
                            backend_available, rank_dense, score_contract)

ROWS = BenchRows("BENCH_MARKET_JSON", "BENCH_market.json")
emit = ROWS.emit
write_json = ROWS.write_json

#: gated claims that failed this run; main() exits nonzero on any.
GATES = Gates()
gate = GATES.gate


# --- incremental reprice vs full rank_dense ----------------------------------

def _universe(n_jobs: int, n_cfgs: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.05, 10.0, size=(n_jobs, n_cfgs))
    mask = rng.random((n_jobs, n_cfgs)) > 0.15        # partial profiling
    mask[np.arange(n_jobs), rng.integers(0, n_cfgs, n_jobs)] = True
    prices = rng.uniform(0.5, 20.0, size=n_cfgs)
    ids = [f"c{i}" for i in range(n_cfgs)]
    return hours, mask, prices, ids, rng


def _delta_batches(ids, prices, rng, n_ticks: int, frac: float):
    batches = []
    for _ in range(n_ticks):
        k = max(1, int(len(ids) * frac))
        cols = rng.choice(len(ids), k, replace=False)
        batches.append({ids[c]: float(prices[c] * rng.uniform(0.5, 2.0))
                        for c in cols})
    return batches


def bench_reprice(n_jobs: int, n_cfgs: int, frac: float,
                  n_ticks: int = 10) -> None:
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)

    # identity sweep (untimed): every tick bit-identical to the cold path
    state = RankState(hours, mask, prices, ids)
    live = prices.copy()
    identical = True
    for batch in batches:
        state.reprice(batch)
        for cid, p in batch.items():
            live[int(cid[1:])] = p
        cold = rank_dense(hours, mask, live, ids)
        inc = state.ranking()
        if [(r.config_id, r.score, r.mean_norm_cost) for r in cold] != \
                [(r.config_id, r.score, r.mean_norm_cost) for r in inc]:
            identical = False
            break

    # timed: the per-tick update (the service's tick cost; rankings
    # materialize lazily) vs a cold rank_dense per tick
    state = RankState(hours, mask, prices, ids)
    t0 = time.perf_counter()
    for batch in batches:
        state.reprice(batch)
    us_reprice = (time.perf_counter() - t0) / n_ticks * 1e6
    t0 = time.perf_counter()
    for _ in batches:
        rank_dense(hours, mask, state.prices, ids)
    us_full = (time.perf_counter() - t0) / n_ticks * 1e6
    # end-to-end tick+submission: both paths build the RankedConfig list
    state = RankState(hours, mask, prices, ids)
    t0 = time.perf_counter()
    for batch in batches:
        state.reprice(batch)
        state.ranking()
    us_e2e = (time.perf_counter() - t0) / n_ticks * 1e6

    speedup = us_full / us_reprice
    emit(f"reprice_{n_jobs}x{n_cfgs}_{frac:.0%}", us_reprice,
         f"cells={n_jobs * n_cfgs};full_rank_us={us_full:.1f};"
         f"speedup={speedup:.1f}x;target_5x={speedup >= 5.0};"
         f"bit_identical={identical}")
    emit(f"reprice_{n_jobs}x{n_cfgs}_{frac:.0%}+materialize", us_e2e,
         f"full_rank_us={us_full:.1f};"
         f"end_to_end_speedup={us_full / us_e2e:.1f}x;"
         f"materialize_us={us_e2e - us_reprice:.1f}")


# --- batched fleet repricing + device-side top-k serving ----------------------

def _fleet_members(n_jobs: int, n_states: int, rng) -> "dict[str, list]":
    """Deterministic member row subsets (each a 30-90% slice of the job
    axis) standing in for live (class, exclusion) selections."""
    members = {}
    for s in range(n_states):
        size = max(2, int(n_jobs * rng.uniform(0.3, 0.9)))
        members[f"s{s}"] = sorted(
            int(i) for i in rng.choice(n_jobs, size, replace=False))
    return members


def _within_contract_vs_refs(batched, refs, members, contract) -> bool:
    """Vectorized contract check of every member against its float64
    incremental reference: all score accumulators inside the rel/abs
    envelope, and the batched winner's *cold* score tied to the cold
    best within the contract (the winner_matches discipline without
    materializing 10k RankedConfigs per member per tick)."""
    for key in members:
        ref = refs[key]
        b = batched.scores(key)
        r = ref.scores
        if not np.all(np.abs(b - r) <= contract.abs_tol
                      + contract.rel_tol * np.maximum(np.abs(b),
                                                      np.abs(r))):
            return False
        cold = np.where(ref.counts > 0, r, np.inf)
        w = batched.top_k(key, 1)[0]
        w_pos = batched.config_ids.index(w.config_id)
        if not contract.scores_match(float(cold[w_pos]),
                                     float(cold.min())):
            return False
    return True


def bench_reprice_batched(n_jobs: int, n_cfgs: int, frac: float,
                          n_states: int = 8, n_ticks: int = 10) -> None:
    """ISSUE 5 acceptance: one batched dispatch per tick reprices a
    fleet of >=8 live rankings, within the jax_batched
    ``ScoreContract`` of per-member float64 references.  Gated:
    ``one_dispatch_per_tick`` + ``within_contract``."""
    name = f"reprice_batched_{n_jobs}x{n_cfgs}" + (
        "" if n_states == 8 else f"_{n_states}states")
    if not backend_available("jax_batched"):
        emit(name, 0.0, "skipped=jax_unavailable")
        return
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_states, rng)
    contract = score_contract("jax_batched")

    # contract sweep (untimed): every member, every tick, vs the
    # float64 incremental references
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    refs = {key: RankState(hours[rows], mask[rows], prices.copy(), ids)
            for key, rows in members.items()}
    within = True
    for batch in batches:
        batched.reprice(batch)
        for ref in refs.values():
            ref.reprice(batch)
        if not _within_contract_vs_refs(batched, refs, members, contract):
            within = False
            break

    # timed: the whole fleet per tick, one batched dispatch (warm the
    # jit first so compile time is not billed)
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    batched.reprice(batches[0])
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    t0 = time.perf_counter()
    for batch in batches:
        batched.reprice(batch)
    us_batched = (time.perf_counter() - t0) / n_ticks * 1e6
    one_dispatch = batched.dispatches == n_ticks and \
        batched.n_active == n_states

    emit(name, us_batched,
         f"cells={n_jobs * n_cfgs};states={n_states};"
         f"dispatches_per_tick={batched.dispatches / n_ticks:.2f};"
         f"one_dispatch_per_tick={one_dispatch};"
         f"within_contract={within};"
         f"contract=rel{contract.rel_tol:g}/abs{contract.abs_tol:g}")
    gate(name, f"one dispatch per tick for >= {n_states} live states",
         one_dispatch)
    gate(name, "within_contract", within)


def bench_reprice_pallas(n_jobs: int, n_cfgs: int, frac: float,
                         n_states: int = 8, n_ticks: int = 10) -> None:
    """ISSUE 9 acceptance: the fused Pallas delta-rank kernel
    (``jax_pallas``, DESIGN.md §14) reprices the fleet in ONE
    ``pallas_call`` per tick, within the jax ``ScoreContract`` of
    per-member float64 references and head-to-head against the XLA
    delta path.  Gated: ``one_dispatch_per_tick`` + ``within_contract``
    (the speed column is informational — on CPU the kernel runs
    ``interpret=True``, so the honest perf reading needs TPU)."""
    name = f"reprice_pallas_{n_jobs}x{n_cfgs}" + (
        "" if n_states == 8 else f"_{n_states}states")
    if not backend_available("jax_pallas"):
        emit(name, 0.0, "skipped=jax_unavailable")
        return
    from repro.kernels.ops import _interpret
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_states, rng)
    contract = score_contract("jax_pallas")

    # contract sweep (untimed): every member, every tick, vs the
    # float64 incremental references
    fused = PallasBatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        fused.add_state(key, rows=rows)
    refs = {key: RankState(hours[rows], mask[rows], prices.copy(), ids)
            for key, rows in members.items()}
    within = True
    for batch in batches:
        fused.reprice(batch)
        for ref in refs.values():
            ref.reprice(batch)
        if not _within_contract_vs_refs(fused, refs, members, contract):
            within = False
            break

    # timed head-to-head vs the XLA delta path (warm both jits first)
    fused = PallasBatchedRankState(hours, mask, prices, ids)
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        fused.add_state(key, rows=rows)
        batched.add_state(key, rows=rows)
    fused.reprice(batches[0])
    batched.reprice(batches[0])
    fused = PallasBatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        fused.add_state(key, rows=rows)
    t0 = time.perf_counter()
    for batch in batches:
        fused.reprice(batch)
    us_fused = (time.perf_counter() - t0) / n_ticks * 1e6
    one_dispatch = fused.dispatches == n_ticks and \
        fused.n_active == n_states
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    t0 = time.perf_counter()
    for batch in batches:
        batched.reprice(batch)
    us_xla = (time.perf_counter() - t0) / n_ticks * 1e6

    emit(name, us_fused,
         f"cells={n_jobs * n_cfgs};states={n_states};"
         f"dispatches_per_tick={fused.dispatches / n_ticks:.2f};"
         f"one_dispatch_per_tick={one_dispatch};"
         f"xla_delta_us={us_xla:.1f};"
         f"vs_xla_delta={us_xla / us_fused:.2f}x;"
         f"interpret={_interpret()};"
         f"within_contract={within};"
         f"contract=rel{contract.rel_tol:g}/abs{contract.abs_tol:g}")
    gate(name, f"one fused dispatch per tick for >= {n_states} live "
               f"states", one_dispatch)
    gate(name, "within_contract", within)


def bench_reprice_sharded(n_jobs: int, n_cfgs: int, frac: float,
                          n_states: int = 8, n_ticks: int = 10,
                          n_devices: "int | None" = None,
                          gate_speedup: bool = False) -> None:
    """ISSUE 8 acceptance: the device-sharded fleet (the C axis split
    over a 1-D mesh, DESIGN.md §13) spends one *collective* shard_map
    dispatch per tick for the whole fleet and, on 8 devices at >=100k
    configs, beats the single-device batched fleet per tick — within
    the jax_sharded ``ScoreContract`` of per-member float64 references.
    Gated: ``one_dispatch_per_tick`` + ``within_contract`` (+
    ``beats_single_device`` when ``gate_speedup``); rows needing more
    devices than the host exposes emit ``skipped=...`` instead of
    gating, so the claim is enforced only on the CI leg that forces an
    8-device host platform."""
    if not backend_available("jax_sharded"):
        emit(f"reprice_sharded_{n_devices or 1}x{n_cfgs}", 0.0,
             "skipped=jax_unavailable")
        return
    import jax

    from repro.selector import ShardedBatchedRankState
    avail = jax.device_count()
    n_dev = avail if n_devices is None else n_devices
    name = f"reprice_sharded_{n_dev}x{n_cfgs}"
    if n_dev > avail:
        emit(name, 0.0, f"skipped=needs_{n_dev}_devices_have_{avail}")
        return
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_states, rng)
    contract = score_contract("jax_sharded")

    # contract sweep (untimed): every member vs its float64 incremental
    # reference; the 100k row trims the sweep to 3 ticks so the smoke
    # budget pays for the timed comparison, not the float64 re-ranks
    sweep = batches if n_cfgs < 100_000 else batches[:3]
    sharded = ShardedBatchedRankState(hours, mask, prices, ids,
                                      devices=n_dev)
    for key, rows in members.items():
        sharded.add_state(key, rows=rows)
    refs = {key: RankState(hours[rows], mask[rows], prices.copy(), ids)
            for key, rows in members.items()}
    within = True
    for batch in sweep:
        sharded.reprice(batch)
        for ref in refs.values():
            ref.reprice(batch)
        if not _within_contract_vs_refs(sharded, refs, members, contract):
            within = False
            break

    # timed: one collective sharded dispatch per tick vs the
    # single-device batched fleet (warm both jit caches first so
    # compile time is billed to neither side)
    sharded = ShardedBatchedRankState(hours, mask, prices, ids,
                                      devices=n_dev)
    for key, rows in members.items():
        sharded.add_state(key, rows=rows)
    sharded.reprice(batches[0])
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    batched.reprice(batches[0])

    sharded = ShardedBatchedRankState(hours, mask, prices, ids,
                                      devices=n_dev)
    for key, rows in members.items():
        sharded.add_state(key, rows=rows)
    t0 = time.perf_counter()
    for batch in batches:
        sharded.reprice(batch)
    us_sharded = (time.perf_counter() - t0) / n_ticks * 1e6
    one_dispatch = sharded.dispatches == n_ticks and \
        sharded.n_active == n_states
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
    t0 = time.perf_counter()
    for batch in batches:
        batched.reprice(batch)
    us_single = (time.perf_counter() - t0) / n_ticks * 1e6

    speedup = us_single / us_sharded
    emit(name, us_sharded,
         f"cells={n_jobs * n_cfgs};states={n_states};devices={n_dev};"
         f"dispatches_per_tick={sharded.dispatches / n_ticks:.2f};"
         f"one_dispatch_per_tick={one_dispatch};"
         f"single_device_us={us_single:.1f};"
         f"speedup_vs_single_device={speedup:.2f}x;"
         f"beats_single_device={us_single > us_sharded};"
         f"within_contract={within};"
         f"contract=rel{contract.rel_tol:g}/abs{contract.abs_tol:g}")
    gate(name, "one collective dispatch per tick for the whole fleet",
         one_dispatch)
    gate(name, "within_contract", within)
    if gate_speedup:
        gate(name, f"{n_dev}-device sharded beats single-device batched "
                   f"at {n_cfgs} configs (got {speedup:.2f}x)",
             us_single > us_sharded)


def bench_topk_serve(n_jobs: int, n_cfgs: int, frac: float,
                     n_states: int = 8, k: int = 3,
                     n_ticks: int = 10) -> None:
    """Serving a tick + the head of one ranking via device-side
    ``top_k``, beside the same tick + that ranking materialized in full
    (a C-config host build and sort on the next submission), both on one
    batched fleet.  Gated: the served head is the ranking's head; the
    ratio is informational (CPU wall clock is not device speed)."""
    name = f"topk_serve_{n_jobs}x{n_cfgs}"
    if not backend_available("jax_batched"):
        emit(name, 0.0, "skipped=jax_unavailable")
        return
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_states, rng)
    served = next(iter(members))

    def fleet():
        batched = BatchedRankState(hours, mask, prices, ids)
        for key, rows in members.items():
            batched.add_state(key, rows=rows)
        return batched

    def timed(serve) -> float:
        # warm the jits on a throwaway fleet so compile time is billed
        # to neither side
        warm = fleet()
        warm.reprice(batches[0])
        serve(warm)
        batched = fleet()
        t0 = time.perf_counter()
        for batch in batches:
            batched.reprice(batch)
            serve(batched)
        return (time.perf_counter() - t0) / n_ticks * 1e6

    us_materialize = timed(lambda b: b.ranking(served))
    us_topk = timed(lambda b: b.top_k(served, k))
    # head sanity (untimed): the served head IS the ranking's head
    batched = fleet()
    batched.reprice(batches[0])
    head_ok = batched.top_k(served, k) == batched.ranking(served)[:k]

    speedup = us_materialize / us_topk
    emit(name, us_topk,
         f"cells={n_jobs * n_cfgs};states={n_states};k={k};"
         f"materialize_us={us_materialize:.1f};"
         f"end_to_end_speedup={speedup:.1f}x;head_matches={head_ok}")
    gate(name, "top_k head matches materialized ranking", head_ok)


# --- the 10k-event daemon stream ---------------------------------------------

def _daemon(n_jobs: int = 24, n_cfgs: int = 128, seed: int = 7
            ) -> SelectionDaemon:
    rng = np.random.default_rng(seed)
    ids = [f"cfg{i}" for i in range(n_cfgs)]
    store = ProfilingStore(config_ids=ids)
    for j in range(n_jobs):
        klass = JobClass.A if j % 2 else JobClass.B
        for c in range(n_cfgs):
            if rng.random() < 0.2:
                continue                      # partial profiling
            store.add(f"job{j}", ids[c], float(rng.uniform(0.1, 5.0)),
                      job_class=klass, group=f"g{j % 6}")
    table = PriceTable({c: float(rng.uniform(1.0, 30.0)) for c in ids})
    service = SelectionService(IdentityCatalog(ids), store, table)
    feed = SimulatedSpotFeed(dict(table.items()), seed=seed,
                             change_fraction=0.01)
    return SelectionDaemon(service, feed)


def bench_daemon(n_events: int = 10_000, seed: int = 7) -> None:
    daemon = _daemon(seed=seed)
    jobs = daemon.service.store.job_ids
    t0 = time.perf_counter()
    stats = daemon.run(synthetic_stream(jobs, n_events, seed=seed))
    dt = time.perf_counter() - t0
    # determinism: a fresh universe + the same seed => byte-identical journal
    again = _daemon(seed=seed)
    again.run(synthetic_stream(jobs, n_events, seed=seed))
    deterministic = again.journal_dump() == daemon.journal_dump()
    svc = daemon.service
    hit_rate = svc.cache_hits / max(1, svc.cache_hits + svc.cache_misses)
    emit(f"daemon_{n_events}ev", dt / n_events * 1e6,
         f"events_per_s={n_events / dt:.0f};decisions={stats.decisions};"
         f"ticks={stats.ticks};epochs={stats.epochs};"
         f"deltas={stats.deltas};cache_hit_rate={hit_rate:.3f};"
         f"incremental_refreshes={svc.reprice_refreshes};"
         f"deterministic={deterministic}")


def main(smoke: bool = False) -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    bench_reprice(64, 1_000, 0.01)
    bench_reprice(64, 10_000, 0.01)
    # the ISSUE 5/8/9 acceptance rows run in smoke mode too: CI gates
    # them (the pallas row's universe is sized for interpret mode on
    # CPU — the kernel replays its grid step-by-step there)
    bench_reprice_batched(64, 10_000, 0.01)
    bench_reprice_pallas(64, 2_000, 0.01)
    bench_topk_serve(64, 10_000, 0.01)
    # always-run small sharded row over whatever devices the host has,
    # plus the gated ISSUE 8 row (8 devices x 100k configs; emits a
    # skipped row — no gate — on hosts without 8 devices)
    bench_reprice_sharded(64, 10_000, 0.01)
    bench_reprice_sharded(64, 100_000, 0.01, n_devices=8,
                          gate_speedup=True)
    if not smoke:
        bench_reprice(64, 10_000, 0.001)
        bench_reprice(256, 10_000, 0.01)
        bench_reprice_batched(64, 10_000, 0.001, n_states=16)
        bench_reprice_pallas(64, 2_000, 0.001, n_states=16)
        bench_reprice_sharded(64, 10_000, 0.001, n_states=16)
    bench_daemon(2_000 if smoke else 10_000)
    write_json()
    check_gates(GATES.failures)


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
