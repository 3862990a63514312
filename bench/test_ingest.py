"""The profile-ingest cell on the CPU at tiny sizes: the program is
``correct``; the bfloat16 control and each planted fault in the ingest
path are not; the records and the least work of an ingest match counts
made by hand; a program without the ingest API stops in set-up."""
from __future__ import annotations

import time
import types

import jax
import numpy as np
import pytest

import cells
import control
import harness
import trace_reduce as tr
from conftest import ROOT, shrink
from gen import deployment as gendep
from gen import records as genrec
from ingest_work import ingest_work
from repro.market import ServeFrontend
from repro.selector import BatchedRankState
from work import Work

NAME = "flora_gcp_profiling.ingest"
SECONDS = 2.0
SEED = 2 ** 31 + 77


@pytest.fixture
def tiny():
    """The cell cut as ``conftest.shrink`` cuts every cell; its catalog
    cut leaves 6 shapes, 4 of them in the profile's span, so each job
    starts on 2 of them, and the record pool is cut like the price
    pool."""
    def make():
        cell = shrink(cells.resolve(ROOT, NAME))
        cell.config["profiling"]["initial_shapes_per_job"] = 2
        cell.traffic["records"]["pool"] = 60
        return cell
    return make


def _run(cell, trace=False):
    return harness.run(cell, SEED, SECONDS, trace, jax.devices()[:1],
                       time.perf_counter())


def test_program_is_correct(tiny):
    res = _run(tiny())
    assert res["correct"], res["checks"]
    assert res["checks"]["unapplied_records"]["value"] == 0
    assert res["attempted"] > 10 and res["failed"] == 0
    assert res["metrics"]["ticks_per_s"]["value"] > 0


def test_control_is_not_correct(tiny):
    with control.installed():
        res = _run(tiny())
    assert not res["correct"]
    assert res["checks"]["fleet_score_err"]["value"] > 1e-4


def _skip_every_tenth(orig):
    calls = [0]

    def ingest(self, cells):
        calls[0] += 1
        if calls[0] % 10 == 0:
            return 0
        return orig(self, cells)
    return ingest


def _previous_runtime(orig):
    def ingest(self, cells):
        cells = list(cells)
        rows = [self._job_pos[j] for j, _, _ in cells]
        cols = [self._pos[c] for _, c, _ in cells]
        old = np.asarray(self.d_hours)[rows, cols]
        held = self._mask[rows, cols]
        return orig(self, [(j, c, float(o) if m else h)
                           for (j, c, h), o, m in zip(cells, old, held)])
    return ingest


@pytest.mark.parametrize("fault", [_skip_every_tenth, _previous_runtime])
def test_fault_is_not_correct(tiny, monkeypatch, fault):
    monkeypatch.setattr(BatchedRankState, "ingest",
                        fault(BatchedRankState.ingest))
    res = _run(tiny())
    assert not res["correct"], res["checks"]


def test_a_program_without_the_ingest_api_stops_in_setup(tiny,
                                                          monkeypatch):
    monkeypatch.delattr(ServeFrontend, "add_profiles")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="profile"):
        _run(tiny())
    assert time.perf_counter() - t0 < 10.0


def test_the_program_span_readers_read_the_window(tiny):
    cell = tiny()
    assert {m["name"] for m in cell.layer} == {
        "ingest_ms.ingest", "fleet_builds_per_tick.ingest",
        "ingest_roofline.ingest", "tick_reprice_ms.ingest",
        "snapshot_build_ms.ingest", "device_idle.ingest",
        "head_dispatch_ms.ingest", "head_readback_ms.ingest",
        "publish_host_ms.ingest", "readbacks_per_tick.ingest",
        "ingest_dispatch_ms.ingest"}
    assert {m["name"] for m in cell.e2e} == {"ticks_per_s", "setup_s"}
    window = harness.Window(False, harness.CompileCounter())
    out = cells.driver(cell.config["driver"]).run(
        cell, SEED, SECONDS, window, jax.devices()[:1], time.perf_counter())
    read = lambda n: cells.reader("layer_metrics", n).read(out)  # noqa
    assert read("fleet_builds_per_tick.ingest") == 0.0
    assert out.counters["service.ingest_fallbacks"] == 0
    assert abs(out.counters["rank.ingest_batches"] - out.attempted) <= 2
    for name in ("ingest_ms", "tick_reprice_ms", "snapshot_build_ms",
                 "head_dispatch_ms", "head_readback_ms", "publish_host_ms",
                 "ingest_dispatch_ms"):
        assert read(f"{name}.ingest") > 0
    # the enqueue lies inside the ingest; every tick's records move the
    # fleet, so every publication reads its heads back once
    assert read("ingest_dispatch_ms.ingest") < read("ingest_ms.ingest")
    assert read("readbacks_per_tick.ingest") == pytest.approx(1.0, abs=0.05)
    # the device readers need a --trace 1 run
    assert read("ingest_roofline.ingest") is None
    assert read("device_idle.ingest") is None


# --- the records ------------------------------------------------------------------

def test_records_same_seed_same_pool(tiny):
    cell = tiny()
    spec, mix = cell.config, cell.traffic["records"]
    a = genrec.pool(spec, mix, genrec.initial(spec, 18, SEED), SEED)
    b = genrec.pool(spec, mix, genrec.initial(spec, 18, SEED), SEED)
    c = genrec.pool(spec, mix, genrec.initial(spec, 18, SEED + 1),
                    SEED + 1)
    assert a == b and a != c and len(a) == 60


def test_full_size_profile_and_records():
    cell = cells.resolve(ROOT, NAME)
    spec, mix = cell.config, cell.traffic["records"]
    span = genrec.eligible(spec)
    # 12 N2 types of 4-32 vCPUs at every scale-out of 2-16 nodes
    assert span.size == 12 * 8
    start = genrec.initial(spec, 18, SEED)
    assert start.shape == (18, 10)
    assert all(np.unique(row).size == 10 for row in start)
    assert np.isin(start, span).all()
    recs = genrec.pool(spec, mix, start, SEED)
    assert len(recs) == 2000
    profiled = [set(row.tolist()) for row in start]
    fresh = 0
    for r in recs:
        assert r.shape in span and r.hours > 0
        fresh += r.shape not in profiled[r.job]
        profiled[r.job].add(r.shape)
    assert 0.45 < fresh / len(recs) < 0.55
    dep = gendep.build(spec, SEED)
    # a record's runtime is the deployment's model with its own noise
    r = recs[0]
    ratio = r.hours / dep.shape_hours[r.job, r.shape]
    assert 0.5 < ratio < 2.0


# --- the least work of an ingest ------------------------------------------------------

def test_ingest_work_row_minimum_moved():
    # row 0 wrote columns 1 and 2 and its cheapest cost moved, so its
    # four profiled columns 0-3 renormalise; row 2 wrote column 5 alone
    changes = [(0, np.array([1, 2]), np.array([0, 1, 2, 3])),
               (2, np.array([5]), np.array([5]))]
    members = [np.array([0, 1]), np.array([0, 2]), np.array([1])]
    w = ingest_work(changes, members)
    # cells: 4 of row 0, 1 of row 2; scores: member 0 four columns,
    # member 1 five columns, member 2 none
    assert w.bytes == 4 * 5 + 8 * (4 + 5)
    # two operations per cell; folds: member 0 4, member 1 4 + 1
    assert w.flops == 2 * 5 + (4 + 5)


def test_ingest_work_replayed_record_moves_no_score():
    changes = [(1, np.array([3, 4]), np.zeros(0, dtype=np.int64))]
    w = ingest_work(changes, [np.array([1])])
    assert w == Work(flops=2 * 2, bytes=4 * 2)


# --- the readers ---------------------------------------------------------------------

def test_ingest_readers_on_a_hand_made_window():
    spans = {"ingest.apply": (100, 0.05), "rank.build": (0, 0.0)}
    run = types.SimpleNamespace(
        trace=None, spans=spans, attempted=100,
        counters={"rank.cold_rebuilds": 0},
        span_mean=lambda n: spans[n][1] / spans[n][0] if n in spans
        else None)
    read = lambda n: cells.reader("layer_metrics", n).read(run)  # noqa
    assert read("ingest_ms.ingest") == pytest.approx(0.5)
    assert read("fleet_builds_per_tick.ingest") == 0.0
    run.counters = {"rank.cold_rebuilds": 25}
    assert read("fleet_builds_per_tick.ingest") == 0.25
    # a program without the counter reads nothing, whatever its spans
    run.counters = {}
    assert read("fleet_builds_per_tick.ingest") is None
    spans["ingest.dispatch"] = (100, 0.03)
    assert read("ingest_dispatch_ms.ingest") == pytest.approx(0.3)
    assert read("ingest_roofline.ingest") is None


def _plane(name, modules):
    events = [types.SimpleNamespace(name=n, start_ns=a * 10 ** 6,
                                    duration_ns=(b - a) * 10 ** 6)
              for n, a, b in modules]
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name="XLA Modules", events=events),
        types.SimpleNamespace(name="XLA Ops", events=[])])


def test_ingest_roofline_finds_the_ingest_program_by_module_name():
    roof = cells.reader("layer_metrics", "ingest_roofline.ingest")
    planes = [_plane("/host:CPU", [("jit_ingest", 0, 9)]),
              _plane("/device:TPU:0", [("jit_ingest(12)", 2, 3),
                                       ("jit_ingest_other(4)", 3, 4),
                                       ("jit_step(3)", 4, 5),
                                       ("jit_ingest", 6, 7),
                                       ("jit_ingest(12)", 6.5, 8)])]
    (got,) = roof.module_intervals(planes)
    ms = 10 ** 6
    np.testing.assert_array_equal(got, [[2 * ms, 3 * ms], [6 * ms, 8 * ms]])


def test_ingest_roofline_counts_the_device_time_inside_the_ingest(
        monkeypatch):
    # the ingest step runs after the host span that enqueued it: its
    # device time is found by its program, not inside the span
    ms = 10 ** 6
    span = lambda name, a, b, **st: tr.Span(name, a * ms, b * ms, st)  # noqa
    trace = tr.Trace(
        busy=[np.array([[1 * ms, 3 * ms], [5 * ms, 6 * ms],
                        [8 * ms, 9 * ms], [25 * ms, 26 * ms]])], ops=[[]],
        spans=[span("bench.window", 0, 20),
               span("bench.ingest", 0.5, 0.7, epoch=1),
               span("bench.reprice", 0.8, 1.0, epoch=1),
               span("bench.ingest", 4, 4.2, epoch=2)])
    planes = [_plane("/device:TPU:0", [("jit_ingest(7)", 2, 3),
                                       ("jit_step(3)", 3, 5),
                                       ("jit_ingest(7)", 5.5, 7),
                                       ("jit_step(3)", 8, 9),
                                       ("jit_ingest(7)", 25, 26)])]
    roof = cells.reader("layer_metrics", "ingest_roofline.ingest")
    monkeypatch.setattr(roof, "_planes", lambda log_dir: planes)
    run = types.SimpleNamespace(
        trace=trace, trace_dir="trace", peaks={"flops_per_s": 1e12,
                                               "bytes_per_s": 1e9},
        ingest_work=lambda epochs: {e: Work(flops=0.0, bytes=1e3)
                                    for e in epochs})
    # 2 us least time over the 1.5 ms the device ran the ingest program
    # in the window (1 ms and 0.5 ms; the step's and the later run's not)
    assert roof.read(run) == pytest.approx(2e-6 / 1.5e-3 * 100)
    run.trace_dir = None
    assert roof.read(run) is None
