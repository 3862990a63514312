"""Tests for the concurrent serving front-end (repro.market.frontend).

Covers the ISSUE 6 acceptance surface: the tick-owned snapshot publish /
lock-free worker serving split, bounded-queue backpressure with explicit
shed and drain accounting, the typed feed-error path (serve off the last
good snapshot, retry with capped backoff), retirement + revival through
the control path, and the deterministic shard merge — pinned by a golden
journal and checked end-to-end by ``JournalReplayer.audit`` (numpy:
bit-identical; jax_batched: the ScoreContract envelope).

The inline stepping API (``step_tick``/``serve_queued``/``close``) drives
the same code paths without threads, which is what makes the golden and
the hypothesis interleave property deterministic; the threaded tests then
pin that real concurrency (workers from ``FLORA_SERVE_WORKERS``, default
2) preserves the same accounting and audit guarantees.

Regenerate the golden journal after a *deliberate* schema change with

    PYTHONPATH=src python tests/test_frontend.py --regen-golden

and add a migration note to DESIGN.md §8 in the same commit.
"""
import os
import threading
import time

import pytest

from hyputil import HAVE_HYPOTHESIS, given, settings, st
from repro.core.trace import JobClass
from repro.market import (FeedError, JournalReplayer, RecordedPriceFeed,
                          SelectionDaemon, ServeFrontend, SimulatedSpotFeed,
                          Submission, merge_shards, record_feed)
from repro.selector import (IdentityCatalog, NothingRankableError, PriceTable,
                            ProfilingStore, SelectionService,
                            backend_available)
from test_soak import SOAK_SELECTIONS, _recorded_market, _soak_store

if HAVE_HYPOTHESIS:
    from test_rank_properties import _event_feed, event_markets
else:                                       # decoration-time stand-ins only
    def event_markets():
        return None

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
GOLDEN_FRONTEND = os.path.join(
    FIXTURES, "decision_journal_v2_frontend.golden.jsonl")

#: the CI front-end leg scales this up (FLORA_SERVE_WORKERS=4).
N_WORKERS = int(os.environ.get("FLORA_SERVE_WORKERS", "2"))


# --- shared universe ------------------------------------------------------------

def _universe():
    """Small fully-profiled identity universe: 6 jobs (classes A/B,
    groups g0-g2) x 8 configs, deterministic runtimes."""
    ids = [f"c{i}" for i in range(8)]
    store = ProfilingStore(config_ids=ids)
    for j in range(6):
        klass = JobClass.A if j % 2 else JobClass.B
        for i, c in enumerate(ids):
            store.add(f"j{j}", c, 0.2 + ((j * 5 + i * 3) % 13) / 4.0,
                      job_class=klass, group=f"g{j % 3}")
    base = {c: 1.0 + i for i, c in enumerate(ids)}
    return store, ids, base


def _recorded(base, n_ticks=12, seed=9):
    sim = SimulatedSpotFeed(base, seed=seed, change_fraction=0.5)
    return RecordedPriceFeed.loads(record_feed(sim, n_ticks))


def _frontend(backend="numpy", n_ticks=12, feed=None, **kw):
    store, ids, base = _universe()
    if feed is None:
        feed = _recorded(base, n_ticks=n_ticks)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend=backend,
                           serve_top_k=kw.pop("serve_top_k", None))
    return ServeFrontend(svc, feed, **kw), store


#: a selection whose exclusions empty the class: genuinely unrankable,
#: so its published rejection can never go stale (price-independent).
UNRANKABLE = Submission("j1", exclude_groups=("g0", "g1", "g2"))


class _FlakyFeed:
    """Recorded feed whose poll raises ``times`` times at each tick in
    ``fail_ticks`` — the transient-outage shape the typed feed-error
    path exists for.  Deterministic: same wrapper, same failures."""

    def __init__(self, inner, fail_ticks, times=2):
        self.inner = inner
        self.ticks = inner.ticks
        self._remaining = {t: times for t in fail_ticks}

    def config_ids(self):
        return self.inner.config_ids()

    def poll(self, tick):
        if self._remaining.get(tick, 0) > 0:
            self._remaining[tick] -= 1
            raise ConnectionError(f"transient market outage at {tick}")
        return self.inner.poll(tick)


# --- the golden journal (inline mode = deterministic concurrency) ----------------

def golden_frontend():
    """The pinned run: 2 workers, 5 recorded ticks with one transient
    feed failure, worker decisions + a worker-served rejection + a
    forwarded (control-path) decision interleaved across ticks."""
    store, ids, base = _universe()
    feed = _FlakyFeed(_recorded(base, n_ticks=5), fail_ticks=(2,), times=1)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend="numpy")
    return ServeFrontend(svc, feed, workers=2, top_k=2), store


def run_golden(fe):
    fe.warm([Submission("j1"), Submission("j2"), UNRANKABLE])
    fe.submit(Submission("j1"))
    fe.submit("j2")                      # bare job ids wrap to Submissions
    fe.step_tick()                       # tick 0
    fe.serve_queued()                    # two worker decisions at epoch 0
    fe.submit(UNRANKABLE)                # worker-served rejection
    fe.submit(Submission("j3"))          # unwarmed: forwarded to control
    fe.step_tick()                       # tick 1
    fe.serve_queued()
    assert fe.step_tick() == "feed-error"    # tick 2 fails once...
    assert fe.step_tick() == "tick"          # ...and the retry lands it
    fe.submit(Submission("j1"))
    fe.step_tick()                       # tick 3
    fe.serve_queued()
    fe.step_tick()                       # tick 4
    return fe.close()


def test_frontend_journal_golden_file():
    """Pins the merged front-end journal byte-for-byte: record shapes
    shared with the daemon, the additive worker/snapshot_tick/tick
    stamps, the feed-error record, and the (tick, worker, seq) merge
    order.  If this fails you changed the journal schema — follow the
    regen + DESIGN.md §8 discipline in the module docstring."""
    fe, _ = golden_frontend()
    stats = run_golden(fe)
    assert stats.accounted and stats.feed_errors == 1
    with open(GOLDEN_FRONTEND) as f:
        assert fe.journal_dump() == f.read()


def test_inline_run_is_deterministic_and_audit_clean():
    """Same submissions + same interleave => byte-identical merged
    journal (the golden's reproducibility bar), and the unmodified
    JournalReplayer audits it bit-identical — workers, forwards, the
    rejection and the feed error included."""
    fe1, store = golden_frontend()
    stats = run_golden(fe1)
    fe2, _ = golden_frontend()
    run_golden(fe2)
    assert fe1.journal_dump() == fe2.journal_dump()

    assert stats.decisions == 4 and stats.rejected == 1
    assert stats.forwarded == 1 and stats.shed == 0
    assert stats.ticks == 5 and stats.snapshots > 0
    replayer = JournalReplayer(store, fe1.journal_dump())
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.decisions == stats.decisions
    assert audit.rejected == stats.rejected
    assert audit.feed_errors == stats.feed_errors == 1
    assert audit.contract.bit_identical and audit.drift == ()
    # every decision surfaces its serving shard and snapshot epoch
    decisions = replayer.decisions()
    assert {d.worker for d in decisions} <= {0, 1, 2}
    assert all(d.snapshot_tick is not None for d in decisions)
    assert any(d.worker and d.worker > 0 for d in decisions)     # workers
    assert any(d.worker == 0 for d in decisions)                 # control


def test_merged_journal_parses_as_v2():
    fe, _ = golden_frontend()
    run_golden(fe)
    header, records = SelectionDaemon.loads_journal(fe.journal_dump())
    assert header["backend"] == "numpy"
    kinds = [r["kind"] for r in records]
    assert {"tick", "decision", "rejected", "feed-error"} <= set(kinds)
    assert [r["seq"] for r in records] == list(range(1, len(records) + 1))
    for r in records:
        assert "worker" in r
        assert ("snapshot_tick" in r) == (r["kind"] in ("decision",
                                                        "rejected"))
        assert ("tick" in r) == (r["kind"] in ("tick", "feed-error",
                                               "metrics"))


# --- merge_shards: the total order -----------------------------------------------

def test_merge_shards_total_order_and_seq():
    """The merge sorts by (tick, worker, per-shard position) and
    renumbers seq: tick-thread records first within a tick, worker
    decisions between the tick records of their stamped epochs, and the
    result independent of the shard-list order (thread scheduling)."""
    header = '{"format": "test-header"}'
    tick0 = {"kind": "tick", "seq": 0, "worker": 0, "tick": 0}
    tick1 = {"kind": "tick", "seq": 0, "worker": 0, "tick": 1}
    d_w1_t0 = {"kind": "decision", "seq": 0, "worker": 1,
               "snapshot_tick": 0, "job": "a"}
    d_w1_t1 = {"kind": "decision", "seq": 0, "worker": 1,
               "snapshot_tick": 1, "job": "b"}
    d_w2_t0 = {"kind": "decision", "seq": 0, "worker": 2,
               "snapshot_tick": 0, "job": "c"}
    shards = [[tick0, tick1], [d_w1_t0, d_w1_t1], [d_w2_t0]]
    merged = merge_shards(header, shards)
    lines = merged.splitlines()
    assert lines[0] == header
    import json
    recs = [json.loads(ln) for ln in lines[1:]]
    assert [r["seq"] for r in recs] == [1, 2, 3, 4, 5]
    assert [(r["kind"], r["worker"]) for r in recs] == [
        ("tick", 0), ("decision", 1), ("decision", 2),   # epoch of tick 0
        ("tick", 0), ("decision", 1)]                    # epoch of tick 1
    # shard order (scheduling accident) cannot change the merged bytes
    assert merge_shards(header, list(reversed(shards))) == merged
    # seq renumbering never mutates the caller's shard records
    assert tick0["seq"] == 0


def test_merge_shards_tolerates_degenerate_shards():
    """Satellite (ISSUE 8): a worker that journaled zero records hands
    the merge an empty shard — the total order and contiguous seq
    renumbering must survive any number of empty shards in any
    position, and the all-empty merge is the header-only journal."""
    import json
    header = '{"format": "test-header"}'
    tick0 = {"kind": "tick", "seq": 0, "worker": 0, "tick": 0}
    d_w2_t0 = {"kind": "decision", "seq": 0, "worker": 2,
               "snapshot_tick": 0, "job": "a"}
    busy = [[tick0], [d_w2_t0]]
    merged = merge_shards(header, busy)
    # empty shards are inert: same bytes wherever they appear
    assert merge_shards(header, [[], *busy]) == merged
    assert merge_shards(header, [[tick0], [], [d_w2_t0], []]) == merged
    recs = [json.loads(ln) for ln in merged.splitlines()[1:]]
    assert [r["seq"] for r in recs] == [1, 2]
    # every shard empty (a frontend that served nothing): header only
    assert merge_shards(header, [[], [], []]) == header + "\n"
    assert merge_shards(header, []) == header + "\n"


def test_zero_record_worker_shard_still_audits_clean():
    """Satellite (ISSUE 8), end-to-end: with no warm-up every queued
    submission misses the snapshot and forwards to the control path, so
    *both* worker shards journal zero records; a second wave sheds 100%
    against the capacity-1 queues.  The merged journal must still be
    total-ordered with contiguous seq and pass the unmodified
    ``JournalReplayer.audit``."""
    fe, store = _frontend(workers=2, queue_capacity=1, n_ticks=4)
    assert fe.step_tick() == "tick"              # tick 0 lands
    assert fe.submit(Submission("j1"))           # -> worker 1
    assert fe.submit(Submission("j2"))           # -> worker 2
    assert fe.submit(Submission("j1")) is False  # w1 at capacity: shed
    assert fe.submit(Submission("j2")) is False  # w2 at capacity: shed
    fe.serve_queued()                # both miss the snapshot -> forward
    fe.step_tick()                   # control path serves both
    stats = fe.close()
    assert stats.forwarded == 2 and stats.decisions == 2
    assert stats.shed == 2 and stats.accounted
    _, records = SelectionDaemon.loads_journal(fe.journal_dump())
    served = [r for r in records if r["kind"] in ("decision", "rejected")]
    assert len(served) == 2
    assert all(r["worker"] == 0 for r in records)   # worker shards empty
    assert [r["seq"] for r in records] == list(range(1, len(records) + 1))
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.decisions == 2


# --- parameter validation + submit-after-close -----------------------------------

@pytest.mark.parametrize("kw", [
    {"workers": 0}, {"workers": -1}, {"workers": True},
    {"queue_capacity": 0}, {"top_k": 0}, {"top_k": True},
])
def test_frontend_rejects_bad_params(kw):
    store, ids, base = _universe()
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base))
    with pytest.raises(ValueError):
        ServeFrontend(svc, _recorded(base), **kw)


def test_submit_after_close_is_shed():
    fe, _ = _frontend(workers=1)
    fe.submit(Submission("j1"))
    fe.close()
    assert fe.submit(Submission("j2")) is False
    stats = fe.stats()
    assert stats.shed == 1 and stats.submitted == 1 and stats.accounted


def test_close_refuses_started_frontend():
    fe, _ = _frontend(workers=1)
    fe.start()
    try:
        with pytest.raises(RuntimeError, match="shutdown"):
            fe.close()
    finally:
        fe.shutdown()


def test_backoff_delay_is_capped_exponential():
    fe, _ = _frontend(backoff_base=0.01, backoff_cap=0.5)
    assert fe.backoff_delay(1) == pytest.approx(0.01)
    assert fe.backoff_delay(2) == pytest.approx(0.02)
    assert fe.backoff_delay(4) == pytest.approx(0.08)
    assert fe.backoff_delay(50) == 0.5          # capped, no overflow


# --- satellite: burst past queue capacity ----------------------------------------

def test_burst_ten_x_capacity_sheds_drains_and_accounts():
    """Submitting a burst of 10x the total queue capacity against slow
    consumers must shed (submit returns False) rather than deadlock or
    buffer unboundedly, drain cleanly, and account for every submission
    in the merged journal: accepted = journaled decisions, refused =
    counted shed, nothing lost, audit still clean."""
    capacity = 4
    fe, store = _frontend(workers=2, queue_capacity=capacity,
                          on_decision=lambda d: time.sleep(0.002))
    fe.warm([Submission("j1"), Submission("j2")])
    burst = [Submission("j1" if i % 2 else "j2")
             for i in range(10 * 2 * capacity)]
    accepted = []
    with fe:
        def produce(subs):
            accepted.append(sum(fe.submit(s) for s in subs))

        producers = [threading.Thread(target=produce,
                                      args=(burst[i::2],))
                     for i in range(2)]
        for t in producers:
            t.start()
        for t in producers:
            t.join()
        fe.drain(timeout=30.0)           # TimeoutError here = deadlock
        fe.await_ticks(timeout=30.0)
    stats = fe.stats()
    assert stats.submitted == sum(accepted)
    assert stats.submitted + stats.shed == len(burst)
    assert stats.shed > 0                # the burst actually overflowed
    assert stats.submitted > 0           # ...but wasn't refused outright
    assert stats.accounted and stats.rejected == 0
    # the merged journal carries exactly the accepted submissions
    _, records = SelectionDaemon.loads_journal(fe.journal_dump())
    served = [r for r in records if r["kind"] in ("decision", "rejected")]
    assert len(served) == stats.submitted
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.decisions == stats.decisions


# --- satellite: typed feed-error path --------------------------------------------

def test_threaded_flaky_feed_keeps_serving_and_audits():
    """A feed that dies transiently mid-run: the tick thread journals
    typed ``feed-error`` records, keeps serving off the last good
    snapshot, retries the failed tick with backoff until the market
    completes — and the merged journal still audits clean."""
    store, ids, base = _universe()
    feed = _FlakyFeed(_recorded(base, n_ticks=12), fail_ticks=(3, 7),
                      times=2)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base))
    fe = ServeFrontend(svc, feed, workers=N_WORKERS,
                       backoff_base=0.001, backoff_cap=0.01)
    fe.warm([Submission("j1"), Submission("j2")])
    with fe:
        for i in range(30):
            assert fe.submit(Submission("j1" if i % 2 else "j2"))
            time.sleep(0.001)
        fe.await_ticks(timeout=30.0)     # all 12 ticks despite failures
        fe.drain(timeout=30.0)
    stats = fe.stats()
    assert stats.ticks == 12
    assert stats.feed_errors == 4        # two outages, two retries each
    assert stats.accounted and stats.decisions == 30
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.feed_errors == 4
    assert audit.decisions == 30


def test_feed_error_backoff_state_resets_on_good_tick():
    store, ids, base = _universe()
    feed = _FlakyFeed(_recorded(base, n_ticks=4), fail_ticks=(1,), times=3)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base))
    fe = ServeFrontend(svc, feed, workers=1, backoff_base=0.01)
    assert fe.step_tick() == "tick"              # tick 0
    epoch_before = svc.price_epoch
    delays = []
    while fe.step_tick() == "feed-error":        # tick 1 fails 3x
        delays.append(fe.backoff_delay())
        assert svc.price_epoch == epoch_before   # prices stayed put
    assert delays == [pytest.approx(0.01), pytest.approx(0.02),
                      pytest.approx(0.04)]       # doubling per failure
    assert fe.backoff_delay() == pytest.approx(0.01)   # reset on success
    assert fe.ticker.tick_count == 2             # tick 1 landed on retry
    fe.close()


def test_feed_error_failures_reset_across_fail_recover_fail():
    """Satellite (ISSUE 8): the consecutive-failures counter that feeds
    both the journaled ``failures`` field and the backoff delay restarts
    from base after the *first* successful poll — a second outage
    journals failures 1,2 again (never 3,4), and the healthy feed never
    inherits the inflated delay."""
    import json
    store, ids, base = _universe()
    feed = _FlakyFeed(_recorded(base, n_ticks=5), fail_ticks=(1, 3),
                      times=2)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base))
    fe = ServeFrontend(svc, feed, workers=1, backoff_base=0.01)
    statuses = []
    while fe.ticker.tick_count < 5:
        statuses.append(fe.step_tick())
        if statuses[-1] == "tick":
            # first good poll after an outage: delay back at base
            assert fe.backoff_delay() == pytest.approx(0.01)
    assert statuses.count("feed-error") == 4     # two outages, 2x each
    assert statuses.count("tick") == 5
    fe.close()
    records = [json.loads(ln)
               for ln in fe.journal_dump().splitlines()[1:]]
    errs = [r for r in records if r["kind"] == "feed-error"]
    assert [e["failures"] for e in errs] == [1, 2, 1, 2]
    assert [e["tick"] for e in errs] == [1, 1, 3, 3]
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.feed_errors == 4


# --- satellite: retirement + revival through the control path --------------------

def test_retired_selection_revives_through_control_path():
    """Retiring a live selection drops it from the snapshot and the
    service; the next submission forwards to the control path, which
    re-registers and serves it fresh — the journal shows a decision
    (never a spurious rejection), so the audit stays clean."""
    fe, store = _frontend(workers=1, n_ticks=6)
    fe.warm([Submission("j1")])
    fe.submit(Submission("j1"))
    fe.step_tick()
    fe.serve_queued()
    assert (JobClass.A, ("g1",)) in fe.snapshot.entries

    fe.retire_selection(JobClass.A, ("g1",))
    fe.step_tick()                       # control drain applies it
    assert (JobClass.A, ("g1",)) not in fe.snapshot.entries

    fe.submit(Submission("j1"))          # post-retirement: forwarded...
    fe.serve_queued()
    fe.step_tick()                       # ...revived via control path
    assert (JobClass.A, ("g1",)) in fe.snapshot.entries
    fe.submit(Submission("j1"))          # ...and worker-served again
    fe.serve_queued()
    stats = fe.close()
    assert stats.decisions == 3 and stats.rejected == 0
    assert stats.forwarded == 1
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:5]


def test_service_retire_selection_drops_caches_and_reports():
    store, ids, base = _universe()
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base))
    svc.submit("j1")
    svc.submit("j1")
    assert svc.cache_misses == 1 and svc.cache_hits == 1
    assert svc.retire_selection(JobClass.A, ("g1",)) is True
    assert svc.retire_selection(JobClass.A, ("g1",)) is False   # idempotent
    svc.submit("j1")                     # revival = a fresh cold build
    assert svc.cache_misses == 2


def test_batched_retired_member_raises_typed_not_raw():
    """Satellite: on the batched backend a retired member surfaces as
    NothingRankableError — a typed rejection the serving layers journal
    — never a raw KeyError or a silently-masked-slot score; and a later
    submit for the same selection revives it."""
    if not backend_available("jax_batched"):
        pytest.skip("jax not installed")
    store, ids, base = _universe()
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend="jax_batched")
    d1 = svc.submit("j1")
    base_key = (JobClass.A, ("g1",))     # fleet members: the selection
    assert svc._batched is not None and base_key in svc._batched
    assert svc.retire_selection(JobClass.A, ("g1",)) is True
    with pytest.raises(NothingRankableError, match="retired"):
        svc._batched.ranking(base_key)
    with pytest.raises(NothingRankableError, match="retired"):
        svc._batched.top_k(base_key, 1)
    d2 = svc.submit("j1")                # revival, same winner
    assert d2.config_id == d1.config_id


def test_unrankable_selection_serves_snapshot_rejections():
    """A warmed-but-unrankable selection publishes a ``head=None``
    snapshot entry: workers journal the rejection without a service
    call, and the audit confirms it as genuine (cold rank also finds
    nothing)."""
    fe, store = _frontend(workers=1, n_ticks=4)
    fe.warm([UNRANKABLE])
    route = (JobClass.A, ("g0", "g1", "g2"))
    assert fe.snapshot.entries[route].head is None
    fe.submit(UNRANKABLE)
    fe.step_tick()
    fe.serve_queued()
    stats = fe.close()
    assert stats.rejected == 1 and stats.decisions == 0
    assert stats.forwarded == 0          # served straight off the snapshot
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok and audit.rejected == 1


# --- satellite: hypothesis interleave property -----------------------------------

@settings(max_examples=15, deadline=None)
@given(event_markets(), st.lists(st.integers(0, 7), min_size=5,
                                 max_size=40))
def test_any_interleave_audits_bit_identical(market, program):
    """For any event-bearing market and any interleave of ticks, worker
    serves and submissions, every journaled decision's score matches a
    cold re-rank at its stamped epoch — ``JournalReplayer.audit`` in
    numpy bit-identity mode over the merged journal — and every
    accepted submission is accounted."""
    cfgs, base, events, seed, change_fraction, n_ticks, jobs, rt = market
    store = ProfilingStore(config_ids=cfgs)
    for idx, j in enumerate(jobs):
        for c in cfgs:
            store.add(j, c, rt[(j, c)],
                      job_class=JobClass.A if idx % 2 else JobClass.B)
    svc = SelectionService(IdentityCatalog(cfgs), store, PriceTable(base))
    fe = ServeFrontend(svc, _event_feed(base, events, seed,
                                        change_fraction),
                       workers=2, ticks=n_ticks)
    for op in program:
        if op == 0:
            fe.step_tick()
        elif op == 1:
            fe.serve_queued()
        else:
            fe.submit(Submission(jobs[op % len(jobs)]))
    stats = fe.close()
    assert stats.accounted and stats.shed == 0
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.decisions == stats.decisions
    assert audit.contract.bit_identical and audit.drift == ()


# --- the threaded soak: real concurrency over the 220-tick recorded market -------

@pytest.mark.parametrize("backend", ["numpy", "jax_batched"])
def test_threaded_soak_recorded_market(backend):
    """The front-end run the CI leg soaks: N workers serving the six
    soak selections off live snapshots while the 220-tick recorded
    market plays out on the tick thread — zero shed, every submission
    accounted, the merged journal audit-clean (numpy bit-identical,
    jax_batched within the ScoreContract), and the batched backend
    still spending one kernel dispatch per price epoch."""
    if not backend_available(backend):
        pytest.skip("jax not installed")
    store, ids = _soak_store()
    feed, base = _recorded_market(ids)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend=backend, serve_top_k=3)
    fe = ServeFrontend(svc, feed, workers=N_WORKERS, queue_capacity=512,
                       tick_interval=0.001)
    warmup = [Submission(job, exclude_groups=excl)
              for job, excl in SOAK_SELECTIONS]
    assert fe.warm(warmup) == len(SOAK_SELECTIONS)
    n_subs = 150
    with fe:
        for i in range(n_subs):
            job, excl = SOAK_SELECTIONS[i % len(SOAK_SELECTIONS)]
            assert fe.submit(Submission(job, exclude_groups=excl))
            time.sleep(0.001)
        fe.await_ticks(timeout=60.0)
        fe.drain(timeout=30.0)
    stats = fe.stats()
    assert stats.ticks == 220 and stats.epochs >= 180
    assert stats.shed == 0 and stats.accounted
    assert stats.decisions == n_subs and stats.rejected == 0
    assert stats.forwarded == 0          # warm() pre-registered the fleet

    replayer = JournalReplayer(store, fe.journal_dump())
    assert replayer.backend == backend
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.decisions == n_subs
    decisions = replayer.decisions()
    assert all(d.worker and d.worker > 0 for d in decisions)
    assert all(d.snapshot_tick is not None for d in decisions)
    if backend == "numpy":
        assert audit.contract.bit_identical and audit.drift == ()
    else:
        assert svc._batched is not None
        assert svc._batched.n_active == len(SOAK_SELECTIONS)
        # THE batching claim survives the concurrent front-end: one
        # kernel dispatch per price epoch for the whole fleet
        assert stats.epochs - 1 <= svc.reprice_dispatches <= stats.epochs
        assert all(d.served_via == "top_k" for d in decisions)


class _GatedFeed:
    """Recorded feed whose poll blocks until its tick is released —
    lets a test hold the threaded tick loop to a scripted schedule."""

    def __init__(self, inner):
        self.inner = inner
        self.ticks = inner.ticks
        self._allowed = 0
        self._cv = threading.Condition()

    def config_ids(self):
        return self.inner.config_ids()

    def allow(self, upto):
        with self._cv:
            self._allowed = upto
            self._cv.notify_all()

    def poll(self, tick):
        with self._cv:
            assert self._cv.wait_for(lambda: self._allowed > tick,
                                     timeout=30.0)
        return self.inner.poll(tick)


def _wait_snapshot(fe, tick, timeout=30.0):
    deadline = time.monotonic() + timeout
    while fe.snapshot.tick < tick:
        if time.monotonic() > deadline:
            raise TimeoutError(f"snapshot never reached tick {tick}")
        time.sleep(0.001)


def test_threaded_journal_equals_inline_journal_same_interleave():
    """Thread scheduling cannot leak into the merged bytes: a threaded
    run whose workers see the exact same (submission, snapshot-epoch)
    pairs as an inline run merges to the identical journal.  The feed
    is gated so each threaded batch drains against a pinned snapshot
    before the next tick is released."""
    n_ticks = 4

    def run(threaded):
        store, ids, base = _universe()
        # change_fraction=1.0: every tick moves prices, so every tick
        # republishes and the snapshot wait below always terminates
        sim = SimulatedSpotFeed(base, seed=9, change_fraction=1.0)
        gate = _GatedFeed(
            RecordedPriceFeed.loads(record_feed(sim, n_ticks)))
        if not threaded:
            gate.allow(n_ticks)
        svc = SelectionService(IdentityCatalog(ids), store,
                               PriceTable(base))
        fe = ServeFrontend(svc, gate, workers=2)
        fe.warm([Submission("j1"), Submission("j2")])
        if threaded:
            fe.start()
        for t in range(n_ticks):
            for s in ("j1", "j2", "j1"):
                fe.submit(Submission(s))
            if threaded:
                fe.drain(timeout=30.0)   # batch served at pinned epoch
                gate.allow(t + 1)        # release tick t...
                _wait_snapshot(fe, t)    # ...and wait for its snapshot
            else:
                fe.serve_queued()
                fe.step_tick()
        if threaded:
            fe.shutdown()
        else:
            fe.close()
        return fe.journal_dump()

    assert run(threaded=True) == run(threaded=False)


if __name__ == "__main__":
    import sys
    if "--regen-golden" in sys.argv:
        fe, _ = golden_frontend()
        run_golden(fe)
        fe.save_journal(GOLDEN_FRONTEND)
        print(f"wrote {GOLDEN_FRONTEND}")
    else:
        print(__doc__)
