"""Tests for the replay harness (repro.market.replay + dynamic evaluation).

Covers the ISSUE 3 acceptance surface: recorded-feed CSV round-trips and
malformed-input handling, the v2 decision-journal schema (golden file),
JournalReplayer's bit-identical audit (including tamper detection and the
out-of-band-mutation case it exists to catch), and the
deviation-from-optimal report under dynamic prices.

Regenerate the golden journal after a *deliberate* schema change with

    PYTHONPATH=src python tests/test_replay.py --regen-golden

and add a migration note to DESIGN.md §8 in the same commit.
"""
import json
import os

import numpy as np
import pytest

from repro.core.costmodel import TpuPriceModel
from repro.core.evaluate import dynamic_evaluation
from repro.core.tpu_flora import MeshOption, WorkloadRecord, make_service
from repro.core.trace import JobClass
from repro.market import (JournalReplayer, MarketEvent, PriceFeed,
                          RecordedPriceFeed, SelectionDaemon,
                          SimulatedSpotFeed, Submission, Tick, record_feed)
from repro.market.daemon import JOURNAL_VERSION
from repro.selector import (IdentityCatalog, PriceTable, ProfilingStore,
                            SelectionService)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
GOLDEN_JOURNAL = os.path.join(FIXTURES, "decision_journal_v2.golden.jsonl")
#: written by the retired per-state "jax" backend; read-only now (no
#: service can write it), kept so old journals stay auditable
GOLDEN_JOURNAL_JAX = os.path.join(FIXTURES,
                                  "decision_journal_v2_jax.golden.jsonl")
GOLDEN_JOURNAL_JAX_BATCHED = os.path.join(
    FIXTURES, "decision_journal_v2_jax_batched.golden.jsonl")
GOLDEN_JOURNAL_TOPK = os.path.join(
    FIXTURES, "decision_journal_v2_topk.golden.jsonl")
PRICE_FIXTURE = os.path.join(os.path.dirname(FIXTURES), "..", "examples",
                             "data", "gcp_spot_prices.csv")


# --- shared universes -------------------------------------------------------------

MESH_OPTIONS = [
    MeshOption("dp256xtp1", "v5e", 256, (256, 1), ("data", "model")),
    MeshOption("dp16xtp16", "v5e", 256, (16, 16), ("data", "model")),
    MeshOption("v5p-dp16xtp16", "v5p", 256, (16, 16), ("data", "model")),
]
SPEED = {"dp256xtp1": {"train_4k": 1.0, "decode_32k": 4.0},
         "dp16xtp16": {"train_4k": 1.5, "decode_32k": 1.0},
         "v5p-dp16xtp16": {"train_4k": 0.8, "decode_32k": 0.55}}


def live_service(backend="numpy") -> SelectionService:
    recs = [WorkloadRecord(arch=a, shape=s, mesh=m, step_seconds=v)
            for a in ("a1", "a2")
            for m, shapes in SPEED.items() for s, v in shapes.items()]
    svc = make_service(MESH_OPTIONS, recs, TpuPriceModel("ondemand"),
                       backend=backend)
    svc.set_price_source(PriceTable.from_catalog(svc.catalog,
                                                 TpuPriceModel("ondemand")))
    return svc


def synth_service(n_jobs=6, n_cfgs=12, seed=0,
                  backend="numpy") -> SelectionService:
    """Identity-catalog universe with correlated per-class runtimes."""
    rng = np.random.default_rng(seed)
    ids = [f"c{i}" for i in range(n_cfgs)]
    speed = {JobClass.A: rng.uniform(0.5, 3.0, n_cfgs),
             JobClass.B: rng.uniform(0.5, 3.0, n_cfgs)}
    store = ProfilingStore(config_ids=ids)
    for j in range(n_jobs):
        klass = JobClass.A if j % 2 else JobClass.B
        scale = rng.uniform(0.2, 2.0)
        for c in range(n_cfgs):
            store.add(f"j{j}", ids[c],
                      float(scale * speed[klass][c]
                            * rng.lognormal(0.0, 0.05)),
                      job_class=klass, group=None)
    table = PriceTable({c: float(rng.uniform(1.0, 20.0)) for c in ids})
    return SelectionService(IdentityCatalog(ids), store, table,
                            backend=backend)


# --- recorded feed: round-trip ----------------------------------------------------

def sim_feed(seed=5, **kw):
    base = {"a": 2.0, "b": 5.5, "c": 0.75, 7: 12.0}   # int id round-trips too
    kw.setdefault("change_fraction", 0.5)
    return SimulatedSpotFeed(base, seed=seed, **kw)


def test_record_feed_roundtrip_identical_stream():
    """record_feed(sim) -> RecordedPriceFeed reproduces the identical tick
    stream: prices (exact floats), ordering, id types, event boundaries."""
    events = [MarketEvent("us-central1", 3, 4, factor=0.5, kind="discount"),
              MarketEvent("europe-west3", 8, 2, factor=3.0,
                          kind="eviction")]
    text = record_feed(sim_feed(events=events), 15)
    replay = RecordedPriceFeed.loads(text)
    assert isinstance(replay, PriceFeed)
    assert replay.ticks == 15
    fresh = sim_feed(events=events)
    for t in range(15):
        assert replay.poll(t) == fresh.poll(t)
    # ids keep their types through the JSON encoding
    assert {type(c) for c in replay.config_ids()} <= {str, int}
    assert any(isinstance(c, int) for c in replay.config_ids())


def test_record_feed_rerecord_is_byte_identical():
    text = record_feed(sim_feed(), 12)
    again = record_feed(RecordedPriceFeed.loads(text), 12)
    assert again == text


def test_record_feed_mid_stream_start_stays_loadable():
    """Regression: the ticks= header records the horizon (last tick + 1),
    so a recording that starts mid-stream loads and replays at its
    absolute tick indices."""
    source = sim_feed()
    for t in range(5):
        source.poll(t)                        # advance past the prefix
    tail = record_feed(source, 5, start=5)        # ticks 5-9
    feed = RecordedPriceFeed.loads(tail)
    assert feed.ticks == 10
    assert feed.poll(0) == () and feed.poll(4) == ()
    fresh = sim_feed()
    for t in range(5):
        fresh.poll(t)
    for t in range(5, 10):
        assert feed.poll(t) == fresh.poll(t)
    # re-recording over the full horizon reproduces the bytes (the
    # leading quiet ticks emit no rows)
    assert record_feed(feed, 10) == tail


def test_recorded_feed_quiet_past_the_recording():
    feed = RecordedPriceFeed.loads(record_feed(sim_feed(), 5))
    assert feed.poll(5) == () and feed.poll(999) == ()
    assert len(list(feed.stream())) == 5


def test_recorded_feed_drives_daemon_deterministically():
    """The same recording yields byte-identical journals — the
    reproducible-fixture contract that motivates recording at all."""
    from repro.market import synthetic_stream
    text = record_feed(SimulatedSpotFeed(
        {c: 10.0 + i for i, c in
         enumerate(f"c{i}" for i in range(12))}, seed=3,
        change_fraction=0.4), 20)

    def run():
        svc = synth_service()
        daemon = SelectionDaemon(svc, RecordedPriceFeed.loads(text))
        daemon.run(synthetic_stream([f"j{i}" for i in range(6)], 120,
                                    seed=1, tick_fraction=0.2))
        return daemon.journal_dump()

    assert run() == run()


# --- recorded feed: malformed input -----------------------------------------------

def good_csv():
    return record_feed(sim_feed(), 4)


@pytest.mark.parametrize("mutate,match", [
    (lambda t: t.replace("# repro.market.recorded-price-feed",
                         "# something-else"), "not a recorded price feed"),
    (lambda t: t.replace(" v1 ", " v9 "), "version"),
    (lambda t: "\n".join(["no magic"] + t.splitlines()[1:]),
     "not a recorded price feed"),
    (lambda t: t.replace("tick,config_id,price", "a,b,c"),
     "expected header"),
])
def test_malformed_feed_headers_raise(mutate, match):
    with pytest.raises(ValueError, match=match):
        RecordedPriceFeed.loads(mutate(good_csv()))


def row(csv_row: str) -> str:
    head = good_csv().splitlines()[:2]
    return "\n".join(head + [csv_row]) + "\n"


@pytest.mark.parametrize("bad,match", [
    ('0,7', "expected 3 fields"),
    ('0,7,1.0,extra', "expected 3 fields"),
    ('x,7,1.0', "not an integer"),
    ('-1,7,1.0', "negative tick"),
    ('0,7,zzz', "not a number"),
    ('0,7,-3.0', "non-positive"),
    ('0,7,0.0', "non-positive"),
    ('0,7,inf', "non-finite"),
    ('0,not-json,1.0', "not valid JSON"),
    ('0,"[1, 2]",1.0', "not hashable"),
])
def test_malformed_feed_rows_raise_with_line_numbers(bad, match):
    """A malformed row must raise, naming its line — never silently skip."""
    with pytest.raises(ValueError, match=match) as e:
        RecordedPriceFeed.loads(row(bad))
    assert "line 3" in str(e.value)


def test_out_of_order_ticks_raise():
    head = good_csv().splitlines()[:2]
    text = "\n".join(head + ["5,7,1.0", "2,7,2.0"]) + "\n"
    with pytest.raises(ValueError, match="out of order") as e:
        RecordedPriceFeed.loads(text)
    assert "line 4" in str(e.value)


def test_empty_feed_file_raises_with_line_number():
    """Satellite (ISSUE 4): an empty file is a malformed recording, not
    an empty market — it must raise, naming line 1."""
    with pytest.raises(ValueError, match="line 1.*empty"):
        RecordedPriceFeed.loads("")


@pytest.mark.parametrize("truncated", [
    '0,"c0",',          # cut mid-price (trailing comma survives)
    '0,"c0',            # cut mid-id (unterminated quote)
    '0,',               # cut after the tick
])
def test_truncated_final_row_raises_with_line_number(truncated):
    """Satellite (ISSUE 4): a recording cut off mid-row (partial write,
    truncated download) must raise at its line, never load the prefix
    silently."""
    with pytest.raises(ValueError) as e:
        RecordedPriceFeed.loads(row(truncated))
    assert "line 3" in str(e.value)


def test_duplicate_tick_quote_raises_with_line_number():
    """Satellite (ISSUE 4): two quotes for one config at one tick are
    ambiguous (which is 'the' epoch price depends on application order)
    — the load must refuse, naming the duplicate's line."""
    head = good_csv().splitlines()[:2]
    text = "\n".join(head + ['2,7,1.0', '2,8,2.0', '2,7,3.0']) + "\n"
    with pytest.raises(ValueError, match="duplicate quote") as e:
        RecordedPriceFeed.loads(text)
    assert "line 5" in str(e.value)
    # the same duplicate is rejected at construction time too
    from repro.market import PriceDelta
    with pytest.raises(ValueError, match="duplicate quote"):
        RecordedPriceFeed({0: [PriceDelta("a", 1.0), PriceDelta("a", 2.0)]})
    # distinct configs in one tick batch stay legal (that IS a batch)
    feed = RecordedPriceFeed.loads(
        "\n".join(head + ['2,7,1.0', '2,8,2.0']) + "\n")
    assert len(feed.poll(2)) == 2


# --- journal schema v2: golden files ----------------------------------------------

def golden_daemon(backend="numpy", serve_top_k=None) -> SelectionDaemon:
    # the goldens pin one journal layout per backend
    svc = live_service(backend=backend)
    svc.serve_top_k = serve_top_k
    feed = SimulatedSpotFeed(dict(svc.price_source.items()), seed=6,
                             change_fraction=0.6)
    return SelectionDaemon(svc, feed)


GOLDEN_STREAM = [
    Submission("decode_32k"), Tick(), Submission("train_4k"), Tick(),
    Submission("decode_32k"),
    Submission("decode_32k", exclude_groups=("a1", "a2")),   # rejection
    Tick(), Submission("train_4k"),
]


def test_journal_schema_golden_file():
    """Pins the versioned-JSONL journal layout byte-for-byte.  If this
    fails you changed the journal schema: bump JOURNAL_VERSION, add a
    migration note to DESIGN.md §8, and regenerate the golden with
    ``PYTHONPATH=src python tests/test_replay.py --regen-golden`` — all
    in the same commit."""
    daemon = golden_daemon()
    daemon.run(GOLDEN_STREAM)
    with open(GOLDEN_JOURNAL) as f:
        assert daemon.journal_dump() == f.read()


def test_journal_golden_file_jax_backend():
    """The journal layout of a device-backed daemon
    serving full rankings (``jax_batched``) is pinned alongside the
    numpy golden.  The header stamps ``"backend": "jax_batched"`` so
    replays know the tolerance audit mode applies.

    The pin mirrors the backend's own contract: every record is
    compared field-for-field exactly — kinds, seqs, winners, $/h,
    epochs, deltas, the header — *except* the float32-derived ``score``
    values, which are held to the jax ``ScoreContract`` instead of
    their bytes (pyproject pins only ``jax>=0.4``; float32 XLA
    reductions have no cross-release byte-stability guarantee, unlike
    the float64 numpy golden).  Regenerate together with the numpy
    golden (same command, same commit discipline)."""
    pytest.importorskip("jax")
    from repro.selector import score_contract
    daemon = golden_daemon(backend="jax_batched")
    daemon.run(GOLDEN_STREAM)
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    assert header["backend"] == "jax_batched"
    assert not any("served_via" in r for r in records)
    with open(GOLDEN_JOURNAL_JAX_BATCHED) as f:
        g_header, g_records = SelectionDaemon.loads_journal(f.read())
    assert header == g_header
    assert len(records) == len(g_records)
    contract = score_contract("jax_batched")
    for rec, golden in zip(records, g_records):
        assert {k: v for k, v in rec.items() if k != "score"} == \
            {k: v for k, v in golden.items() if k != "score"}
        assert ("score" in rec) == ("score" in golden)
        if "score" in golden:
            assert contract.scores_match(rec["score"], golden["score"])


def test_retired_per_state_jax_journal_still_audits_clean():
    """Journals written by the retired per-state writer stamp
    ``"backend": "jax"``.  No service can write one any more, but the
    committed golden replays and audits clean in tolerance mode under
    its stamped contract."""
    from repro.selector import score_contract
    store = live_service().store
    replayer = JournalReplayer.load(store, GOLDEN_JOURNAL_JAX)
    assert replayer.backend == "jax"
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.contract == score_contract("jax")
    assert not audit.contract.bit_identical
    assert audit.decisions > 0 and audit.ticks > 0


def test_journal_golden_file_topk_serving():
    """Satellite (ISSUE 5): the journal layout of a batched daemon
    serving every decision via device-side top-k (DESIGN.md §10) is
    pinned alongside the other goldens.  The header stamps
    ``"backend": "jax_batched"`` and decision records carry the
    additive ``"served_via": "top_k"`` field (absent on full-ranking
    journals, so the full-ranking goldens keep their bytes).

    Pinned with the jax golden's discipline: every field exact except
    the float32-derived ``score``, held to the ScoreContract instead
    of its bytes.  Regenerate together with the other goldens
    (``--regen-golden``, same commit discipline)."""
    pytest.importorskip("jax")
    from repro.selector import score_contract
    daemon = golden_daemon(backend="jax_batched", serve_top_k=2)
    daemon.run(GOLDEN_STREAM)
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    assert header["backend"] == "jax_batched"
    assert all(r["served_via"] == "top_k" for r in records
               if r["kind"] == "decision")
    with open(GOLDEN_JOURNAL_TOPK) as f:
        g_header, g_records = SelectionDaemon.loads_journal(f.read())
    assert header == g_header
    assert len(records) == len(g_records)
    contract = score_contract("jax_batched")
    for rec, golden in zip(records, g_records):
        assert {k: v for k, v in rec.items() if k != "score"} == \
            {k: v for k, v in golden.items() if k != "score"}
        assert ("score" in rec) == ("score" in golden)
        if "score" in golden:
            assert contract.scores_match(rec["score"], golden["score"])


def test_topk_served_decision_journals_identical_fields():
    """Satellite (ISSUE 5): a top-k-served Decision journals the same
    winner/score/$-per-hour fields as a full-ranking decision — the
    journal record is byte-identical on the numpy backend except for
    the additive ``served_via`` stamp.  (Head serving changes how much
    ranking tail the Decision carries, never what it decides.)"""
    full = golden_daemon()
    full.run(GOLDEN_STREAM)
    topk = golden_daemon(serve_top_k=1)
    topk.run(GOLDEN_STREAM)
    _, full_recs = SelectionDaemon.loads_journal(full.journal_dump())
    _, topk_recs = SelectionDaemon.loads_journal(topk.journal_dump())
    assert len(full_recs) == len(topk_recs)
    decisions = 0
    for f, t in zip(full_recs, topk_recs):
        if f["kind"] != "decision":
            assert f == t
            continue
        decisions += 1
        assert t.pop("served_via") == "top_k"
        assert "served_via" not in f
        assert f == t            # winner, score, $/h, epoch: identical
    assert decisions > 0
    # the replay layer surfaces the stamp (defaulting absent to full)
    store_svc = golden_daemon(serve_top_k=1)
    store_svc.run(GOLDEN_STREAM)
    rep = JournalReplayer(store_svc.service.store,
                          store_svc.journal_dump())
    assert all(d.served_via == "top_k" for d in rep.decisions())
    assert rep.audit().ok
    rep_full = JournalReplayer(full.service.store, full.journal_dump())
    assert all(d.served_via == "ranking" for d in rep_full.decisions())


def test_journal_v2_is_self_contained():
    daemon = golden_daemon()
    daemon.run(GOLDEN_STREAM)
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    assert header["version"] == JOURNAL_VERSION == 2
    assert header["backend"] == "numpy"
    assert [c for c, _ in header["prices"]] == header["catalog"]
    assert all(p > 0 for _, p in header["prices"])
    for rec in records:
        if rec["kind"] == "tick":
            assert len(rec["applied"]) == rec["deltas"] > 0
        elif rec["kind"] == "decision":
            assert rec["score"] > 0
            assert isinstance(rec["exclude_groups"], list)
        elif rec["kind"] == "rejected":
            assert rec["job_class"] in ("A", "B", None)
            assert isinstance(rec["exclude_groups"], list)


def test_v1_journals_rejected_with_migration_pointer():
    old = json.dumps({"format": "repro.market.decision-journal",
                      "version": 1, "catalog": []})
    with pytest.raises(ValueError, match="DESIGN.md"):
        SelectionDaemon.loads_journal(old + "\n")


# --- JournalReplayer: the consistency audit ---------------------------------------

def run_daemon(svc=None, n_events=200, seed=2, change_fraction=0.3,
               events=()):
    svc = svc or synth_service()
    feed = SimulatedSpotFeed(dict(svc.price_source.items()), seed=seed,
                             change_fraction=change_fraction,
                             events=list(events))
    daemon = SelectionDaemon(svc, feed)
    from repro.market import synthetic_stream
    daemon.run(synthetic_stream(svc.store.job_ids, n_events, seed=seed,
                                tick_fraction=0.25))
    return daemon


def test_audit_passes_on_clean_run():
    daemon = run_daemon(events=[MarketEvent("us-central1", 2, 5, 0.5),
                                MarketEvent("asia-east1", 10, 4, 2.0,
                                            "eviction")])
    audit = JournalReplayer(daemon.service.store,
                            daemon.journal_dump()).audit()
    assert audit.ok
    assert audit.decisions == daemon.stats.decisions > 0
    assert audit.ticks == daemon.stats.epochs > 0
    assert audit.rejected == daemon.stats.rejected


def test_audit_detects_tampered_selection():
    daemon = run_daemon()
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    victim = next(r for r in records if r["kind"] == "decision")
    other = next(c for c in header["catalog"] if c != victim["config"])
    victim["config"] = other
    audit = JournalReplayer(daemon.service.store, (header, records)).audit()
    assert not audit.ok
    fields = {m.field for m in audit.mismatches}
    assert "config" in fields
    assert all(m.seq == victim["seq"] for m in audit.mismatches)


def test_audit_detects_single_ulp_score_drift():
    # a float64 ulp is only a mismatch under the numpy bit-identity
    # contract
    daemon = run_daemon(svc=synth_service(backend="numpy"))
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    victim = next(r for r in records if r["kind"] == "decision")
    victim["score"] = np.nextafter(victim["score"], np.inf)
    audit = JournalReplayer(daemon.service.store, (header, records)).audit()
    assert [m.field for m in audit.mismatches] == ["score"]


def test_tolerance_audit_surfaces_drift_and_bounds_it():
    """A device-backed journal audits in tolerance
    mode — float32 score divergence from the cold float64 re-rank is
    surfaced as ``drift`` (not a failure) while anything beyond the
    ScoreContract still fails the audit."""
    pytest.importorskip("jax")
    from repro.selector import score_contract
    daemon = run_daemon(svc=synth_service(backend="jax_batched"))
    replayer = JournalReplayer(daemon.service.store, daemon.journal_dump())
    assert replayer.backend == "jax_batched"
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.contract == score_contract("jax_batched")
    # float32 scores against float64 cold re-ranks: drift is expected
    # and must be *surfaced*, not silently absorbed
    assert any(d.field == "score-drift" for d in audit.drift)
    for d in audit.drift:
        if d.field == "score-drift":
            assert audit.contract.scores_match(d.journaled, d.replayed)
            assert d.journaled != d.replayed
    # beyond-contract tamper still fails, tolerance notwithstanding
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    victim = next(r for r in records if r["kind"] == "decision")
    victim["score"] *= 1.01           # 1% >> rel_tol
    bad = JournalReplayer(daemon.service.store, (header, records)).audit()
    assert not bad.ok
    assert any(m.field == "score" for m in bad.mismatches)


def test_audit_detects_dropped_tick_deltas():
    """Drop, from a tick record, the re-quote of a config that a later
    decision selected: the reconstructed quote then disagrees with the
    journaled $/h (the feed only emits *changed* prices, so the removed
    delta necessarily differs from the price before it)."""
    daemon = run_daemon()
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    tampered = False
    for i, rec in enumerate(records):
        if tampered or rec["kind"] != "decision":
            continue
        for tick in reversed(records[:i]):      # latest tick before it
            if tick["kind"] == "tick" and any(
                    c == rec["config"] for c, _ in tick["applied"]):
                tick["applied"] = [(c, p) for c, p in tick["applied"]
                                   if c != rec["config"]]
                tampered = True
                break
    assert tampered, "stream never repriced a selected config"
    audit = JournalReplayer(daemon.service.store, (header, records)).audit()
    assert not audit.ok


def test_audit_catches_out_of_band_price_mutation():
    """The audit's raison d'etre: a price applied to the table *behind
    the journal's back* makes later journaled decisions unexplainable
    from the journal alone — the replay must flag them, not absorb
    them."""
    svc = synth_service()
    feed = SimulatedSpotFeed(dict(svc.price_source.items()), seed=4,
                             change_fraction=0.5)
    daemon = SelectionDaemon(svc, feed)
    daemon.handle(Submission("j1"))
    daemon.handle(Tick())
    svc.price_source.apply({"c0": 0.0001})        # out-of-band, unjournaled
    daemon.handle(Submission("j1"))               # decided at secret prices
    audit = JournalReplayer(svc.store, daemon.journal_dump()).audit()
    assert not audit.ok


def test_audit_flags_spurious_rejections():
    """A journaled rejection for a (class, exclusions) that cold-ranks to
    a valid winner means the daemon silently served nothing for a
    rankable job — the audit must flag it, not count it as routine."""
    daemon = run_daemon()
    header, records = SelectionDaemon.loads_journal(daemon.journal_dump())
    victim = next(r for r in records if r["kind"] == "decision")
    fake = {"kind": "rejected", "seq": victim["seq"], "job": victim["job"],
            "job_class": victim["job_class"],
            "exclude_groups": victim["exclude_groups"],
            "price_epoch": victim["price_epoch"]}
    records[records.index(victim)] = fake
    audit = JournalReplayer(daemon.service.store, (header, records)).audit()
    assert not audit.ok
    assert any(m.field == "rejected" for m in audit.mismatches)
    # a genuine rejection (exclusions empty the class) still audits clean
    svc = live_service()
    feed = SimulatedSpotFeed(dict(svc.price_source.items()), seed=1,
                             change_fraction=0.3)
    d2 = SelectionDaemon(svc, feed)
    d2.handle(Submission("decode_32k", exclude_groups=("a1", "a2")))
    d2.handle(Submission("decode_32k"))
    audit2 = JournalReplayer(svc.store, d2.journal_dump()).audit()
    assert audit2.ok and audit2.rejected == 1 and audit2.decisions == 1


def test_record_feed_rejects_unloadable_quotes_at_capture_time():
    """A feed emitting a non-finite quote must fail the capture, not
    produce a CSV that every later load rejects."""
    class BadFeed:
        def poll(self, tick):
            from repro.market import PriceDelta
            return (PriceDelta("a", float("inf")),)

    with pytest.raises(ValueError, match="non-finite"):
        record_feed(BadFeed(), 1)
    from repro.market import PriceDelta
    with pytest.raises(ValueError, match="non-finite"):
        RecordedPriceFeed({0: [PriceDelta("a", float("nan"))]})


def test_audit_catches_drifted_trace():
    daemon = run_daemon()
    store = daemon.service.store
    # post-hoc re-profile: c0 becomes j0's runaway best, renormalizing
    # every class-B score the journaled decisions were computed from
    store.add("j0", "c0", 1e-6, job_class=JobClass.B)
    audit = JournalReplayer(store, daemon.journal_dump()).audit()
    assert not audit.ok


def test_replayer_requires_self_contained_journal():
    with pytest.raises(ValueError, match="price snapshot"):
        JournalReplayer(ProfilingStore(), ({"catalog": []}, []))


def test_replayed_decisions_reconstruct_epochs():
    daemon = run_daemon()
    replayer = JournalReplayer(daemon.service.store, daemon.journal_dump())
    decisions = replayer.decisions()
    assert len(decisions) == daemon.stats.decisions
    epochs = [d.price_epoch for d in decisions]
    assert epochs == sorted(epochs)
    # the last decision's reconstructed prices equal the live table
    final = decisions[-1].prices
    for c in daemon.service.catalog.ids():
        assert final[c] == daemon.service.price_source[c]


# --- dynamic evaluation -----------------------------------------------------------

class _D:
    """Duck-typed ReplayedDecision for hand-built evaluation checks."""

    def __init__(self, seq, job_id, config_id, prices, price_epoch=0,
                 job_class=None):
        self.seq, self.job_id, self.config_id = seq, job_id, config_id
        self.prices, self.price_epoch = prices, price_epoch
        self.job_class = job_class


def test_dynamic_evaluation_hand_computed():
    store = ProfilingStore(config_ids=["x", "y"])
    store.add("j", "x", 1.0)                     # 1 h on x
    store.add("j", "y", 3.0)                     # 3 h on y
    base = {"x": 10.0, "y": 2.0}                 # static oracle: y (6 < 10)
    moved = {"x": 4.0, "y": 2.0}                 # epoch oracle: x (4 < 6)
    ev = dynamic_evaluation(store, [_D(1, "j", "x", moved)], ["x", "y"],
                            base)
    (o,) = ev.outcomes
    assert o.realized_cost == 4.0
    assert o.oracle_config == "x" and o.oracle_cost == 4.0
    assert o.static_config == "y" and o.static_cost == 6.0
    assert o.deviation == 0.0
    assert o.static_deviation == pytest.approx(0.5)
    assert ev.mean_deviation == 0.0
    assert ev.static_mean_deviation == pytest.approx(0.5)
    assert ev.summary()["decisions"] == 1


def test_dynamic_evaluation_skips_unprofiled_selections():
    store = ProfilingStore(config_ids=["x", "y"])
    store.add("j", "x", 1.0)                     # y never profiled for j
    ev = dynamic_evaluation(
        store, [_D(1, "j", "y", {"x": 1.0, "y": 1.0})], ["x", "y"],
        {"x": 1.0, "y": 1.0})
    assert ev.outcomes == () and ev.skipped == 1


def test_dynamic_evaluation_skips_never_profiled_jobs():
    """Regression: a journaled decision for a job the store has never
    seen (the selector's green-field use case — ranked purely from
    class-mates) must count as skipped, not KeyError."""
    store = ProfilingStore(config_ids=["x"])
    store.add("j", "x", 1.0)
    ev = dynamic_evaluation(
        store, [_D(1, "ghost-job", "x", {"x": 1.0})], ["x"], {"x": 1.0})
    assert ev.outcomes == () and ev.skipped == 1


def test_evaluate_handles_green_field_submissions_end_to_end():
    """Same regression through the real pipeline: a daemon serving a
    submission that is not a profiled job (classified by annotation)
    journals a decision; audit passes and evaluate skips it."""
    svc = synth_service()
    feed = SimulatedSpotFeed(dict(svc.price_source.items()), seed=9,
                             change_fraction=0.5)
    daemon = SelectionDaemon(svc, feed)
    daemon.handle(Submission("never-profiled", annotation=JobClass.A))
    daemon.handle(Tick())
    daemon.handle(Submission("j1"))
    replayer = JournalReplayer(svc.store, daemon.journal_dump())
    assert replayer.audit().ok
    ev = replayer.evaluate()
    assert ev.skipped == 1 and len(ev.outcomes) == 1


def test_deviation_never_negative():
    daemon = run_daemon(n_events=300)
    ev = JournalReplayer(daemon.service.store,
                         daemon.journal_dump()).evaluate()
    assert ev.outcomes
    for o in ev.outcomes:
        assert o.deviation >= 0.0 and o.static_deviation >= 0.0
    assert ev.max_deviation >= ev.mean_deviation >= 0.0


# --- the bundled fixture (acceptance + CI smoke) ----------------------------------

def test_bundled_fixture_replay_end_to_end():
    """ISSUE 3 acceptance: on the bundled recorded-price fixture, the
    journal audit confirms every decision bit-identical to a cold re-rank
    at its epoch, and the harness reports deviation-from-optimal under
    dynamic prices (with live repricing beating the static-price
    oracle)."""
    from repro.core import costmodel, spark_sim
    from repro.market import synthetic_stream
    from repro.selector import GcpVmCatalog
    trace = spark_sim.generate_trace(seed=0)
    store = ProfilingStore.from_trace(trace)
    catalog = GcpVmCatalog(trace.configs, costmodel.LinearPriceModel())
    svc = SelectionService(catalog, store, PriceTable.from_catalog(catalog))
    feed = RecordedPriceFeed.load(PRICE_FIXTURE)
    assert feed.ticks == 40
    daemon = SelectionDaemon(svc, feed)
    daemon.run(synthetic_stream([j.name for j in trace.jobs], 400, seed=3,
                                tick_fraction=0.15))
    replayer = JournalReplayer(store, daemon.journal_dump())
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.decisions > 100 and audit.ticks > 10
    ev = replayer.evaluate()
    assert 0.0 <= ev.mean_deviation < 0.25
    assert ev.mean_deviation < ev.static_mean_deviation
    assert ev.skipped == 0


def test_bundled_fixture_jax_daemon_audits_in_tolerance_mode():
    """A *device-backed* daemon serving full
    rankings (``jax_batched``) over the same bundled fixture journals
    decisions the tolerance audit confirms against
    cold float64 re-ranks — same winners (or contract-tied), scores
    within the ScoreContract, float32 drift surfaced rather than
    silently absorbed — and the dynamic evaluation still beats the
    static-price oracle."""
    pytest.importorskip("jax")
    from repro.core import costmodel, spark_sim
    from repro.market import synthetic_stream
    from repro.selector import GcpVmCatalog, score_contract
    trace = spark_sim.generate_trace(seed=0)
    store = ProfilingStore.from_trace(trace)
    catalog = GcpVmCatalog(trace.configs, costmodel.LinearPriceModel())
    svc = SelectionService(catalog, store, PriceTable.from_catalog(catalog),
                           backend="jax_batched")
    daemon = SelectionDaemon(svc, RecordedPriceFeed.load(PRICE_FIXTURE))
    daemon.run(synthetic_stream([j.name for j in trace.jobs], 400, seed=3,
                                tick_fraction=0.15))
    replayer = JournalReplayer(store, daemon.journal_dump())
    assert replayer.backend == "jax_batched"
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.contract == score_contract("jax_batched")
    assert audit.decisions > 100 and audit.ticks > 10
    assert all(d.served_via == "ranking" for d in replayer.decisions())
    ev = replayer.evaluate()
    assert ev.summary()["backend"] == "jax_batched"
    assert 0.0 <= ev.mean_deviation < 0.25
    assert ev.mean_deviation < ev.static_mean_deviation


def test_bundled_fixture_batched_topk_daemon_audits_in_tolerance_mode():
    """ISSUE 5 acceptance: a *batched-fleet* daemon serving every
    decision via device-side top-k over the bundled paper-universe
    fixture journals decisions the tolerance audit confirms against
    cold float64 re-ranks — one kernel dispatch per price epoch for the
    whole fleet, heads only, and the dynamic evaluation still beats the
    static-price oracle."""
    pytest.importorskip("jax")
    from repro.core import costmodel, spark_sim
    from repro.market import synthetic_stream
    from repro.selector import GcpVmCatalog, score_contract
    trace = spark_sim.generate_trace(seed=0)
    store = ProfilingStore.from_trace(trace)
    catalog = GcpVmCatalog(trace.configs, costmodel.LinearPriceModel())
    svc = SelectionService(catalog, store, PriceTable.from_catalog(catalog),
                           backend="jax_batched", serve_top_k=1)
    daemon = SelectionDaemon(svc, RecordedPriceFeed.load(PRICE_FIXTURE))
    daemon.run(synthetic_stream([j.name for j in trace.jobs], 400, seed=3,
                                tick_fraction=0.15))
    replayer = JournalReplayer(store, daemon.journal_dump())
    assert replayer.backend == "jax_batched"
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.contract == score_contract("jax_batched")
    assert audit.decisions > 100 and audit.ticks > 10
    assert all(d.served_via == "top_k" for d in replayer.decisions())
    # one dispatch per epoch once the fleet exists
    assert audit.ticks - 1 <= svc.reprice_dispatches <= audit.ticks
    ev = replayer.evaluate()
    assert ev.summary()["backend"] == "jax_batched"
    assert 0.0 <= ev.mean_deviation < 0.25
    assert ev.mean_deviation < ev.static_mean_deviation


def test_bundled_fixture_sharded_daemon_audits_in_tolerance_mode():
    """ISSUE 8 acceptance: a *device-sharded* fleet daemon over the
    bundled ``gcp_spot_prices.csv`` fixture journals decisions the
    tolerance audit confirms against cold float64 re-ranks — the C axis
    split across every available device, one collective shard_map
    dispatch per price epoch, device-side per-shard top-k merged on the
    host — and the dynamic evaluation still beats the static-price
    oracle.  The journal stamps ``"backend": "jax_sharded"`` and the
    unmodified replayer resolves it to the tolerance contract."""
    pytest.importorskip("jax")
    from repro.core import costmodel, spark_sim
    from repro.market import synthetic_stream
    from repro.selector import GcpVmCatalog, score_contract
    trace = spark_sim.generate_trace(seed=0)
    store = ProfilingStore.from_trace(trace)
    catalog = GcpVmCatalog(trace.configs, costmodel.LinearPriceModel())
    svc = SelectionService(catalog, store, PriceTable.from_catalog(catalog),
                           backend="jax_sharded", serve_top_k=1)
    daemon = SelectionDaemon(svc, RecordedPriceFeed.load(PRICE_FIXTURE))
    daemon.run(synthetic_stream([j.name for j in trace.jobs], 400, seed=3,
                                tick_fraction=0.15))
    replayer = JournalReplayer(store, daemon.journal_dump())
    assert replayer.backend == "jax_sharded"
    audit = replayer.audit()
    assert audit.ok, audit.mismatches[:3]
    assert audit.contract == score_contract("jax_sharded")
    assert not audit.contract.bit_identical
    assert audit.decisions > 100 and audit.ticks > 10
    assert all(d.served_via == "top_k" for d in replayer.decisions())
    # one collective dispatch per epoch once the fleet exists
    assert audit.ticks - 1 <= svc.reprice_dispatches <= audit.ticks
    ev = replayer.evaluate()
    assert ev.summary()["backend"] == "jax_sharded"
    assert 0.0 <= ev.mean_deviation < 0.25
    assert ev.mean_deviation < ev.static_mean_deviation


if __name__ == "__main__":
    import sys
    if "--regen-golden" in sys.argv:
        for backend, top_k, path in (
                ("numpy", None, GOLDEN_JOURNAL),
                ("jax_batched", None, GOLDEN_JOURNAL_JAX_BATCHED),
                ("jax_batched", 2, GOLDEN_JOURNAL_TOPK)):
            daemon = golden_daemon(backend=backend, serve_top_k=top_k)
            daemon.run(GOLDEN_STREAM)
            with open(path, "w") as f:
                f.write(daemon.journal_dump())
            print(f"wrote {path}")
    else:
        print(__doc__)
