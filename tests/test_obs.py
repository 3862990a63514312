"""Tests for the unified telemetry layer (repro.obs, DESIGN.md §12).

Covers the ISSUE 7 acceptance surface: the lock-free sharded registry
(order/shard-count-independent merges, pinned by a hypothesis property),
deterministic span timing over an injectable clock, the Prometheus/JSON
exports, the periodic ``"metrics"`` journal record kind (golden-pinned,
replayable through the unmodified byte-exact audit, tick-latency
percentiles recovered from the journal alone), the front-end
memory-regression fix (per-submission logs -> counters), and the
``train.step`` / ``serve.prefill`` / ``serve.decode`` span promotion.

Regenerate the metrics-journal golden after a *deliberate* schema change
with

    PYTHONPATH=src python tests/test_obs.py --regen-golden

and add a migration note to DESIGN.md §8 in the same commit.
"""
import json
import os
import tracemalloc

import pytest

from hyputil import given, settings, st
from repro.market import (JournalReplayer, SelectionDaemon, ServeFrontend,
                          Submission, Tick)
from repro.obs import (Counter, FakeClock, Gauge, Histogram, MetricsRegistry,
                       NULL_SPAN, histogram_quantile, maybe_span)
from repro.selector import IdentityCatalog, PriceTable, SelectionService
from test_frontend import _frontend, _recorded, _universe

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
GOLDEN_METRICS = os.path.join(
    FIXTURES, "decision_journal_v2_metrics.golden.jsonl")


# --- registry primitives ---------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    c.inc()
    c.inc(4)
    assert c.value == 5
    c.set(2)                                 # legacy-attribute shim
    assert c.value == 2
    assert reg.counter("a.b") is c           # get-or-create

    g = reg.gauge("depth")
    g.set(3)
    assert g.value == 3.0

    h = reg.histogram("h", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(0.75)
    h.observe(2.0)
    assert h.count == 3
    assert h.sum == pytest.approx(3.0)
    assert h.merged()[0] == [1, 1, 1]

    with pytest.raises(TypeError):           # kind conflict
        reg.histogram("a.b")
    with pytest.raises(ValueError):          # bad metric name
        reg.counter("no spaces")
    with pytest.raises(ValueError):          # buckets must increase
        Histogram("bad", buckets=(1.0, 1.0))


def test_registry_render_prom_and_json():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    reg.gauge("g").set(1.5)
    h = reg.histogram("h", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(2.0)
    assert reg.render() == (
        "# TYPE a_b counter\n"
        "a_b 2\n"
        "# TYPE g gauge\n"
        "g 1.5\n"
        "# TYPE h histogram\n"
        'h_bucket{le="0.5"} 1\n'
        'h_bucket{le="1.0"} 1\n'
        'h_bucket{le="+Inf"} 2\n'
        "h_sum 2.25\n"
        "h_count 2\n")
    snap = json.loads(reg.render("json"))
    assert snap["counters"] == {"a.b": 2}
    assert snap["gauges"] == {"g": 1.5}
    assert snap["histograms"]["h"] == {"le": [0.5, 1.0], "counts": [1, 0, 1],
                                       "sum": 2.25, "count": 2}
    with pytest.raises(ValueError):
        reg.render("xml")


def test_histogram_quantile():
    bounds = (1.0, 2.0, 4.0)
    assert histogram_quantile(bounds, [0, 0, 0, 0], 0.5) is None
    # linear interpolation within the winning bucket (lo = 0 for the first)
    assert histogram_quantile(bounds, [4, 0, 0, 0], 0.5) \
        == pytest.approx(0.5)
    assert histogram_quantile(bounds, [2, 2, 0, 0], 0.75) \
        == pytest.approx(1.5)
    # samples in the +Inf bucket clamp to the last finite bound
    assert histogram_quantile(bounds, [0, 0, 0, 5], 0.99) == 4.0
    with pytest.raises(ValueError):
        histogram_quantile(bounds, [1, 0, 0, 0], 1.5)


def test_spans_fake_clock_deterministic():
    """A span across k intervening clock reads is exactly (k+1) steps —
    the advance-on-read contract golden tests pin span output with."""
    def run():
        reg = MetricsRegistry(clock=FakeClock(step=0.001))
        with reg.span("tick.total"):
            pass                             # enter + exit: one step
        with reg.span("tick.total"):
            reg.clock()                      # one intervening read: two
        return reg
    reg = run()
    h = reg.histogram("tick.total")
    counts, total_ns = h.merged()
    assert h.count == 2 and total_ns == 3_000_000
    assert reg.render() == run().render()    # same ops => same bytes


def test_spans_disabled_are_free_null_spans():
    reg = MetricsRegistry(spans_enabled=False)
    assert reg.span("x") is NULL_SPAN
    with reg.span("x"):
        pass
    assert reg.snapshot()["histograms"] == {}   # not even created
    assert maybe_span(None, "x") is NULL_SPAN
    # counters stay live in both modes: they are accounting, not spans
    reg.counter("c").inc()
    assert reg.counter("c").value == 1


def test_shard_merge_deterministic_example():
    """Always-on pin of the merge property (the hypothesis sweep below
    skips when the extra is absent): bucket-edge, overflow and zero
    samples through 1, 3 and 5 cells merge to identical renders."""
    samples = [0.0, 1e-6, 2.5e-6, 9.9e-6, 1e-3, 0.42, 11.0, 1e-6, 0.0]
    for n_shards in (3, 5):
        _assert_merge_invariant(samples, n_shards)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=15.0, allow_nan=False),
                max_size=120),
       st.integers(min_value=1, max_value=7))
def test_shard_merge_is_order_and_shard_count_independent(samples, n_shards):
    """The tentpole determinism property: the same samples through 1
    cell or N cells — in any observation order — merge to identical
    bucket counts, ns-exact sums, and rendered output."""
    _assert_merge_invariant(samples, n_shards)


def _assert_merge_invariant(samples, n_shards):
    one = Histogram("h")
    for v in samples:
        one.cell(0).observe(v)
    many = Histogram("h")
    for i, v in enumerate(samples):
        many.cell(i % n_shards).observe(v)
    rev = Histogram("h")
    for i, v in enumerate(reversed(samples)):
        rev.cell(n_shards - 1 - (i % n_shards)).observe(v)
    assert one.dump() == many.dump() == rev.dump()

    r1, rn = MetricsRegistry(), MetricsRegistry()
    for i, v in enumerate(samples):
        r1.histogram("h").cell(0).observe(v)
        r1.counter("c").cell(0).inc(i)
        rn.histogram("h").cell(i % n_shards).observe(v)
        rn.counter("c").cell(i % n_shards).inc(i)
    assert r1.render() == rn.render()
    assert r1.render("json") == rn.render("json")


# --- the metrics journal record kind (golden + replay) ---------------------------

def metrics_golden_frontend():
    """The pinned run: everything (service, ticker, front-end) on one
    FakeClock registry, every serve span timed (span_sample=1), a
    cumulative ``metrics`` record journaled every 2 ticks."""
    store, ids, base = _universe()
    feed = _recorded(base, n_ticks=6)
    reg = MetricsRegistry(clock=FakeClock(), spans_enabled=True)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend="numpy", metrics=reg)
    fe = ServeFrontend(svc, feed, workers=2, top_k=2,
                       metrics_every=2, span_sample=1)
    return fe, store


def run_metrics_golden(fe):
    fe.warm([Submission("j1"), Submission("j2")])
    fe.submit(Submission("j1"))
    fe.submit(Submission("j2"))
    fe.step_tick()                       # tick 1
    fe.serve_queued()                    # two snapshot decisions
    fe.step_tick()                       # tick 2 -> metrics record
    fe.submit(Submission("j3"))          # unwarmed: forwarded to control
    fe.serve_queued()
    fe.step_tick()                       # tick 3 (serves the forward)
    fe.submit(Submission("j1"))
    fe.serve_queued()
    fe.step_tick()                       # tick 4 -> metrics record
    fe.step_tick()                       # tick 5
    fe.step_tick()                       # tick 6 -> metrics record
    return fe.close()


def test_metrics_journal_golden_file():
    """Pins the metrics-record schema byte-for-byte: cumulative sorted
    counters + histogram dumps, worker/tick stamps, merge placement.
    If this fails you changed the record shape — follow the regen +
    DESIGN.md §8 discipline in the module docstring."""
    fe, _ = metrics_golden_frontend()
    stats = run_metrics_golden(fe)
    assert stats.accounted and stats.shed == 0
    with open(GOLDEN_METRICS) as f:
        assert fe.journal_dump() == f.read()


def test_metrics_journal_replays_through_unmodified_audit():
    """THE ISSUE 7 acceptance criterion: a journal carrying ``metrics``
    records passes the byte-exact numpy audit unchanged, and the audit
    recovers tick-latency percentiles from the journal alone."""
    fe, store = metrics_golden_frontend()
    run_metrics_golden(fe)
    fe2, _ = metrics_golden_frontend()
    run_metrics_golden(fe2)
    text = fe.journal_dump()
    assert text == fe2.journal_dump()    # deterministic end to end

    audit = JournalReplayer(store, text).audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.contract.bit_identical and audit.drift == ()
    assert audit.metrics_records == 3
    # tick latency recovered from the last cumulative record: all 6
    # ticks, FakeClock-deterministic percentiles
    assert audit.tick_latency is not None
    assert audit.tick_latency["count"] == 6
    assert 0.0 < audit.tick_latency["p50"] <= audit.tick_latency["p99"]

    header, records = SelectionDaemon.loads_journal(text)
    mets = [r for r in records if r["kind"] == "metrics"]
    assert [m["tick"] for m in mets] == [1, 3, 5]     # ticks 2, 4, 6
    assert all(m["worker"] == 0 for m in mets)
    # cumulative, not delta: counters never decrease across records
    for a, b in zip(mets, mets[1:]):
        assert all(b["counters"][k] >= v for k, v in a["counters"].items())
    last = mets[-1]["histograms"]["tick.total"]
    assert last["count"] == 6
    assert [r["seq"] for r in records] == list(range(1, len(records) + 1))


def test_daemon_metrics_every_and_audit_accounting():
    """The single-threaded daemon journals the same record kind; the
    audit counts them and checks their stamped price epoch."""
    store, ids, base = _universe()
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base))
    daemon = SelectionDaemon(svc, _recorded(base, n_ticks=5),
                             metrics_every=2)
    for _ in range(5):
        daemon.handle(Tick())
    daemon.handle(Submission("j1"))
    text = daemon.journal_dump()
    header, records = SelectionDaemon.loads_journal(text)
    assert [r["kind"] for r in records].count("metrics") == 2
    audit = JournalReplayer(store, text).audit()
    assert audit.ok, audit.mismatches[:5]
    assert audit.metrics_records == 2
    # last record taken after tick 4: cumulative count covers 4 ticks
    assert audit.tick_latency["count"] == 4

    with pytest.raises(ValueError):
        SelectionDaemon(svc, _recorded(base), metrics_every=0)
    with pytest.raises(ValueError):
        ServeFrontend(svc, _recorded(base), metrics_every=True)
    with pytest.raises(ValueError):
        ServeFrontend(svc, _recorded(base), span_sample=0)


def test_metrics_default_off_keeps_journals_metrics_free():
    """metrics_every=None (the default) journals no metrics records —
    the guarantee that kept the pre-obs golden journals byte-identical."""
    fe, _ = _frontend(n_ticks=4)
    fe.submit(Submission("j1"))
    fe.step_tick()
    fe.serve_queued()
    fe.step_tick()
    fe.close()
    _, records = SelectionDaemon.loads_journal(fe.journal_dump())
    assert all(r["kind"] != "metrics" for r in records)


# --- the front-end memory-regression fix -----------------------------------------

def test_frontend_shed_path_is_constant_memory():
    """The old per-submission ``_accepted_log``/``_shed_log`` deques grew
    forever on a long-running deployment; accounting is counters now.
    20k shed submissions must allocate ~nothing that survives."""
    fe, _ = _frontend(n_ticks=2)
    assert not hasattr(fe, "_accepted_log")
    assert not hasattr(fe, "_shed_log")
    fe.close()                           # closed => every submit sheds
    fe.submit(Submission("j0"))          # create the shed cell up front
    tracemalloc.start()
    before, _ = tracemalloc.get_traced_memory()
    for _ in range(20_000):
        assert fe.submit(Submission("j1")) is False
    after, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert fe.stats().shed == 20_001
    assert after - before < 64 * 1024    # vs ~MBs for the old logs
    # the merged stats stay exact counters
    assert fe.stats().accounted


# --- span promotion: train loop + serving engine ---------------------------------

def test_train_loop_records_step_spans_and_slow_steps():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.train.train_loop import (StragglerWatchdog, TrainConfig,
                                        train_loop)
    # scripted clock: two reads per step -> exact per-step durations,
    # with one 50x straggler the watchdog must flag
    durations = [0.001] * 6 + [0.05] + [0.001]
    reads, t = [], 0.0
    for d in durations:
        reads.append(t)
        t += d
        reads.append(t)
    reg = MetricsRegistry(clock=iter(reads).__next__)

    def fake_step(params, opt_state, batch):
        return params, opt_state, {"loss": jnp.float32(1.0),
                                   "grad_norm": jnp.float32(0.0)}

    wd = StragglerWatchdog(factor=3.0)
    _, _, history = train_loop(
        None, TrainConfig(), {"w": jnp.zeros((1,))}, {"t": jnp.zeros(())},
        iter([{}] * len(durations)), steps=len(durations), watchdog=wd,
        log_every=0, train_step=fake_step, obs=reg)
    assert history["step_time"] == pytest.approx(durations)
    assert reg.histogram("train.step").count == len(durations)
    assert len(wd.events) == 1
    assert reg.counter("train.slow_steps").value == 1


def test_engine_records_prefill_decode_spans():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import build_model
    from repro.serve.engine import Engine, Request
    cfg = C.reduced(C.get("qwen3-1.7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reg = MetricsRegistry(clock=FakeClock(step=0.001))
    eng = Engine(model, params, slots=2, max_len=32, metrics=reg)
    prompt = jnp.arange(8, dtype=jnp.int32) % cfg.vocab_size
    [comp] = eng.generate_batch([Request(uid=1, prompt=prompt,
                                         max_new_tokens=2)])
    assert len(comp.tokens) == 2
    assert reg.histogram("serve.prefill").count == 1
    assert reg.histogram("serve.decode").count == 1
    # the Completion ms fields ride the same injectable clock
    assert comp.prefill_ms == pytest.approx(1.0)
    assert comp.decode_ms == pytest.approx(1.0)



# --- registry spans on the profiler's timeline ----------------------------------

def test_span_args_reach_the_annotation_and_never_the_histogram(monkeypatch):
    import repro.obs.registry as registry
    seen = []

    def fake_annotation(name, **args):
        seen.append((name, args))
        return NULL_SPAN
    monkeypatch.setattr(registry, "_ANNOTATION", fake_annotation)
    reg = MetricsRegistry(clock=FakeClock(step=0.001))
    with reg.span("tick.reprice", epoch=4):
        pass
    with reg.annotate("tick.total", tick=2):
        pass
    assert seen == [("tick.reprice", {"epoch": 4}), ("tick.total", {"tick": 2})]
    # the annotation reads no clock: the span is one FakeClock step, and
    # annotate() alone makes no histogram
    assert list(reg.snapshot()["histograms"]) == ["tick.reprice"]
    h = reg.histogram("tick.reprice")
    assert h.count == 1 and h.merged()[1] == 1_000_000
    off = MetricsRegistry(spans_enabled=False)
    assert off.annotate("tick.total", tick=2) is NULL_SPAN
    assert off.span("tick.reprice", epoch=4) is NULL_SPAN
    assert len(seen) == 2


def test_importing_obs_imports_no_jax():
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    probe = ("import sys, repro.obs\n"
             "reg = repro.obs.MetricsRegistry()\n"
             "reg.counter('c').inc()\n"
             "assert 'jax' not in sys.modules, 'jax imported'\n")
    result = subprocess.run([sys.executable, "-c", probe],
                            capture_output=True, text=True,
                            env={**os.environ, "PYTHONPATH": src})
    assert result.returncode == 0, result.stderr


TICK_ANNOTATIONS = ("tick.total", "tick.reprice", "snapshot.build",
                    "topk.dispatch", "topk.readback")


def _traced_tick(fe, log_dir):
    """Run one ``step_tick`` under a ``jax.profiler`` trace; returns the
    tick's annotations, ``{host line: [(name, start, end, stats)]}``."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(log_dir))
    try:
        assert fe.step_tick() == "tick"
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                       recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in TICK_ANNOTATIONS:
                    lines.setdefault((plane.name, i), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return lines


def test_tick_spans_nest_on_one_host_line_of_the_profiler_trace(tmp_path):
    pytest.importorskip("jax")
    fe, _ = _frontend("jax_batched", n_ticks=4)
    fe.warm([Submission("j1"), Submission("j2")])
    assert fe.step_tick() == "tick"          # compiles outside the trace
    [events] = _traced_tick(fe, tmp_path).values()
    by = {}
    for ev in sorted(events, key=lambda e: e[1]):
        by.setdefault(ev[0], []).append(ev)
    inside = lambda a, b: b[1] <= a[1] and a[2] <= b[2]  # noqa: E731
    [tick] = by["tick.total"]
    [reprice] = by["tick.reprice"]
    [build] = by["snapshot.build"]
    assert tick[3]["tick"] == 1 and build[3]["tick"] == 1
    assert reprice[3]["epoch"] == fe.service.price_epoch == 2
    assert inside(reprice, tick) and inside(build, tick)
    assert reprice[2] <= build[1]
    # the fleet's reprice took both live heads in its own program, so
    # the publication launches and reads back no top-k of its own
    assert "topk.dispatch" not in by and "topk.readback" not in by
    fe.close()


def test_disabled_spans_leave_no_histograms_and_no_annotations(tmp_path):
    pytest.importorskip("jax")
    store, ids, base = _universe()
    reg = MetricsRegistry(spans_enabled=False)
    svc = SelectionService(IdentityCatalog(ids), store, PriceTable(base),
                           backend="jax_batched", metrics=reg)
    fe = ServeFrontend(svc, _recorded(base, n_ticks=4))
    fe.warm([Submission("j1"), Submission("j2")])
    assert fe.step_tick() == "tick"
    assert _traced_tick(fe, tmp_path) == {}
    hists = reg.snapshot()["histograms"]
    assert not set(TICK_ANNOTATIONS) & set(hists)
    assert all(h["count"] == 0 for h in hists.values())
    fe.close()


@pytest.mark.parametrize("backend,per_head,per_build", [
    ("numpy", 0, 0), ("jax_batched", 0, 0),
    ("jax_pallas", 0, 1), ("jax_sharded", 1, 0)])
def test_one_topk_dispatch_and_readback_per_live_selection(backend,
                                                           per_head,
                                                           per_build):
    """The sharded state launches and reads back one top-k per live
    head; the Pallas fleet one per publication; the XLA fleet none
    in a steady tick, because its reprice takes the heads asked at the
    previous publication."""
    if backend != "numpy":
        pytest.importorskip("jax")
    fe, _ = _frontend(backend, n_ticks=4)
    fe.warm([Submission("j1"), Submission("j2"),
             Submission("j1", exclude_groups=("g0",))])
    live = sum(e.head is not None for e in fe.snapshot.entries.values())
    assert live == 3
    reg = fe.metrics_registry

    def counts():
        hists = reg.snapshot()["histograms"]
        return {name: hists.get(name, {"count": 0})["count"]
                for name in ("snapshot.build", "topk.dispatch",
                             "topk.readback")}
    before = counts()
    assert fe.step_tick() == "tick"
    after = counts()
    launches = per_head * live + per_build
    assert {n: after[n] - before[n] for n in after} == {
        "snapshot.build": 1, "topk.dispatch": launches,
        "topk.readback": launches}
    if backend == "numpy":
        assert after["topk.dispatch"] == after["topk.readback"] == 0
    fe.close()


def test_each_steady_tick_of_the_xla_fleet_takes_its_heads_in_the_reprice():
    """On ``jax_batched`` every tick after the first publication
    reprices and reads the fleet's heads back in one program: the
    ``rank.reprice_heads`` counter moves with the ticks, and no
    publication launches a top-k of its own."""
    pytest.importorskip("jax")
    fe, _ = _frontend("jax_batched", n_ticks=6)
    fe.warm([Submission("j1"), Submission("j2"),
             Submission("j1", exclude_groups=("g0",))])
    reg = fe.metrics_registry

    def counts():
        snap = reg.snapshot()
        hists = snap["histograms"]
        return {"ticks": snap["counters"].get("rank.reprice_heads", 0),
                "launches": snap["counters"].get("rank.head_batches", 0),
                "builds": hists["snapshot.build"]["count"],
                "readbacks": hists.get("topk.readback",
                                       {"count": 0})["count"]}
    before = counts()
    for _ in range(5):
        assert fe.step_tick() == "tick"
    after = counts()
    assert {n: after[n] - before[n] for n in after} == {
        "ticks": 5, "launches": 0, "builds": 5, "readbacks": 0}
    fe.close()


if __name__ == "__main__":
    import sys
    if "--regen-golden" in sys.argv:
        fe, _ = metrics_golden_frontend()
        run_metrics_golden(fe)
        fe.save_journal(GOLDEN_METRICS)
        print(f"wrote {GOLDEN_METRICS}")
    else:
        print(__doc__)
