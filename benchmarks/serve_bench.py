"""Concurrent serving benchmark: the front-end vs the single-thread daemon.

    PYTHONPATH=src python benchmarks/serve_bench.py

The claim under test (ISSUE 6 acceptance — the script exits nonzero when
a gated claim regresses, which is the CI gate): with per-decision reply
latency on the serving path (the client round-trip a real deployment
pays; modeled as a 1 ms ``on_decision`` sleep), the snapshot-serving
front-end at 4 workers sustains **>=3x** the submission throughput of
the single-threaded :class:`~repro.market.SelectionDaemon` over the
*same recorded market*, and worker scaling from 1 to 4 stays near-linear
(parallel efficiency >= 0.7).  Both are honest under the GIL because the
hot path is latency-bound, not compute-bound: workers overlap their
reply waits while the tick thread keeps repricing.

Correctness is gated alongside throughput, not assumed: every front-end
leg must account for all submissions (zero shed at benchmark capacity,
accepted = journaled) and its merged journal must pass
``JournalReplayer.audit`` — byte-exact on numpy; within the
ScoreContract on the jax_batched leg (skipped when jax is absent).

Prints ``name,us_per_call,derived`` CSV rows and writes the same rows as
machine-readable ``BENCH_serve.json`` (override the path with the
``BENCH_SERVE_JSON`` env var) so CI can track the perf trajectory.
"""
from __future__ import annotations

import sys
import time

from _bench_io import BenchRows, Gates, check_gates
from repro.launch.compile_cache import enable_compile_cache
from repro.core.trace import JobClass
from repro.market import (JournalReplayer, RecordedPriceFeed,
                          SelectionDaemon, ServeFrontend, SimulatedSpotFeed,
                          Submission, Tick, record_feed)
from repro.selector import (IdentityCatalog, PriceTable, ProfilingStore,
                            SelectionService, backend_available)

ROWS = BenchRows("BENCH_SERVE_JSON", "BENCH_serve.json")
emit = ROWS.emit
write_json = ROWS.write_json

#: gated claims that failed this run; main() exits nonzero on any.
GATES = Gates()
gate = GATES.gate

#: modeled client-reply latency per served decision (seconds).
LATENCY = 0.001

N_JOBS = 12
N_CFGS = 24

#: six distinct (class, exclusion) selections — the live fleet.
SELECTIONS = [
    ("j1", None), ("j2", None), ("j3", None), ("j4", None),
    ("j1", ("g2", "g3")), ("j2", ("g1",)),
]


# --- the shared recorded market + submission load -----------------------------

def _universe():
    ids = [f"c{i}" for i in range(N_CFGS)]
    store = ProfilingStore(config_ids=ids)
    for j in range(N_JOBS):
        klass = JobClass.A if j % 2 else JobClass.B
        for i, c in enumerate(ids):
            store.add(f"j{j}", c,
                      0.1 + ((j * 13 + i * 7) % 29) / 8.0
                      + (0.5 if klass is JobClass.A and i % 3 == 0
                         else 0.0),
                      job_class=klass, group=f"g{j % 4}")
    base = {c: 1.0 + (i * 11 % 17) for i, c in enumerate(ids)}
    return store, ids, base


def _market_text(base, n_ticks: int) -> str:
    sim = SimulatedSpotFeed(base, seed=42, change_fraction=0.5,
                            volatility=0.08)
    return record_feed(sim, n_ticks)


def _submissions(n: int) -> "list[Submission]":
    return [Submission(job, exclude_groups=excl)
            for job, excl in (SELECTIONS[i % len(SELECTIONS)]
                              for i in range(n))]


def _service(store, ids, base, backend="numpy",
             serve_top_k=None) -> SelectionService:
    return SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                            backend=backend, serve_top_k=serve_top_k)


# --- the single-threaded baseline ---------------------------------------------

def bench_daemon(store, ids, base, market: str, subs, n_ticks: int) -> float:
    """One thread serializes everything: ticks, decisions, and the
    per-decision reply wait.  Returns submissions/second."""
    svc = _service(store, ids, base)
    daemon = SelectionDaemon(svc, RecordedPriceFeed.loads(market))
    every = max(1, len(subs) // n_ticks)
    t0 = time.perf_counter()
    ticked = 0
    for i, sub in enumerate(subs):
        if ticked < n_ticks and i % every == 0:
            daemon.handle(Tick())
            ticked += 1
        decision = daemon.handle(sub)
        if decision is not None:
            time.sleep(LATENCY)              # the inline client reply
    while ticked < n_ticks:
        daemon.handle(Tick())
        ticked += 1
    dt = time.perf_counter() - t0
    audit = JournalReplayer(store, daemon.journal_dump()).audit()
    tput = len(subs) / dt
    emit("serve_daemon_1thread", dt / len(subs) * 1e6,
         f"subs={len(subs)};ticks={n_ticks};tput_per_s={tput:.0f};"
         f"latency_ms={LATENCY * 1e3:g};audit_ok={audit.ok}")
    gate("serve_daemon_1thread", "journal audits clean", audit.ok)
    return tput


# --- the front-end legs -------------------------------------------------------

def bench_frontend(store, ids, base, market: str, subs, workers: int,
                   backend: str = "numpy", baseline_tput: float = 0.0,
                   tput_1w: float = 0.0) -> float:
    """N workers overlap their reply waits off the latest snapshot while
    the tick thread replays the recorded market.  Returns
    submissions/second over the submit->drain window."""
    name = f"serve_frontend_{workers}w" + (
        "" if backend == "numpy" else f"_{backend}")
    if not backend_available(backend):
        emit(name, 0.0, "skipped=jax_unavailable")
        return 0.0
    svc = _service(store, ids, base, backend=backend,
                   serve_top_k=3 if backend == "jax_batched" else None)
    feed = RecordedPriceFeed.loads(market)
    fe = ServeFrontend(svc, feed, workers=workers,
                       queue_capacity=len(subs) + 1,
                       on_decision=lambda d: time.sleep(LATENCY))
    fe.warm(subs[:len(SELECTIONS)])
    with fe:
        t0 = time.perf_counter()
        for sub in subs:
            fe.submit(sub)
        fe.drain(timeout=120.0)
        dt = time.perf_counter() - t0
        fe.await_ticks(timeout=60.0)         # let the market finish
    stats = fe.stats()
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    accounted = stats.accounted and stats.shed == 0 \
        and stats.decisions == len(subs)
    tput = len(subs) / dt
    derived = (f"subs={len(subs)};workers={workers};"
               f"tput_per_s={tput:.0f};"
               f"speedup_vs_daemon={tput / baseline_tput:.2f}x;"
               f"accounted={accounted};audit_ok={audit.ok}")
    if tput_1w:
        eff = tput / (workers * tput_1w)
        derived += f";scaling_efficiency={eff:.2f}"
    emit(name, dt / len(subs) * 1e6, derived)
    gate(name, "all submissions accounted (zero shed, all journaled)",
         accounted)
    gate(name, "merged journal audits clean", audit.ok)
    return tput


def main(smoke: bool = False) -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    n_subs, n_ticks = (240, 60) if smoke else (600, 220)
    store, ids, base = _universe()
    market = _market_text(base, n_ticks)
    subs = _submissions(n_subs)

    daemon_tput = bench_daemon(store, ids, base, market, subs, n_ticks)
    tput_1w = bench_frontend(store, ids, base, market, subs, 1,
                             baseline_tput=daemon_tput)
    bench_frontend(store, ids, base, market, subs, 2,
                   baseline_tput=daemon_tput, tput_1w=tput_1w)
    tput_4w = bench_frontend(store, ids, base, market, subs, 4,
                             baseline_tput=daemon_tput, tput_1w=tput_1w)

    # THE gated claims: >=3x the single-threaded daemon at 4 workers,
    # near-linear 1->4 worker scaling (the reply waits overlap; the
    # snapshot hot path adds no serialization of its own)
    speedup = tput_4w / daemon_tput if daemon_tput else 0.0
    gate("serve_frontend_4w",
         f"throughput >= 3x single-threaded daemon (got {speedup:.2f}x)",
         speedup >= 3.0)
    efficiency = tput_4w / (4 * tput_1w) if tput_1w else 0.0
    gate("serve_frontend_4w",
         f"1->4 worker scaling efficiency >= 0.7 (got {efficiency:.2f})",
         efficiency >= 0.7)

    # the batched-fleet leg: same shape, tolerance-audited (DESIGN.md §10)
    bench_frontend(store, ids, base, market, subs, 4,
                   backend="jax_batched", baseline_tput=daemon_tput,
                   tput_1w=tput_1w)

    write_json()
    check_gates(GATES.failures)


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
