"""The selector's served path: a threaded ``ServeFrontend`` over
``SelectionService``, built as ``examples/serve_frontend.py`` builds it.

Set-up generates the deployment from the seed, fills a ``ProfilingStore``,
registers the live routes, and warms every shape the window uses: the
delta bucket of the mix's ticks, the fleet's member capacity, the top-k
head, and (where routes churn) the retire and register path.  In the
window a tick thread reprices the fleet from a replayed feed and
publishes snapshots while the workers serve a producer thread's open-loop
submissions.  The benchmark stamps, from its own code:

* each tick's publication (a ``ServeFrontend`` subclass hook after the
  snapshot is stored), against the tick's due time in the open mix;
* each decision (``on_decision``), against its submission's due time;
* ``jax.profiler.TraceAnnotation`` spans around ``SelectionService.reprice``
  (``bench.reprice``, with the price epoch it makes), around snapshot
  publication (``bench.publish``) and around the feed's wait
  (``bench.feed_wait``).

After the window the served decisions, published heads and the fleet's
final scores are held to the plain reference (``reference.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.trace import JobClass
from repro.market import PriceDelta, ServeFrontend, Submission
from repro.selector import (IdentityCatalog, PriceTable, ProfilingStore,
                            SelectionService)

import reference as refmod
import work as workmod
from gen import deployment as gendep
from gen import submissions as gensubs
from gen.spot_walk import spot_batches

STREAM_CHECK = 16
TICK_THREAD = "flora-tick"


class ReplayFeed:
    """Replays the pool of batches generated in set-up, cyclically: tick
    ``t`` quotes batch ``t % len(batches)``.  In the open mix ``poll(t)``
    returns a window tick's batch at its due time; in the saturating mix,
    and for the warm-up ticks, at once."""

    def __init__(self, ids: Sequence[str], batches, warm: int,
                 rate: Optional[float]):
        self._ids = ids
        self._batches = batches
        self._warm = warm
        self._rate = rate
        self._t0 = 0.0

    def open(self, t0: float) -> None:
        self._t0 = t0

    def due(self, t: int) -> float:
        return self._t0 + (t - self._warm) / self._rate

    def poll(self, t: int):
        cols, prices = self._batches[t % len(self._batches)]
        ids = self._ids
        batch = tuple(PriceDelta(ids[c], p)
                      for c, p in zip(cols.tolist(), prices.tolist()))
        if self._rate is not None and t >= self._warm:
            with TraceAnnotation("bench.feed_wait"):
                wait = self.due(t) - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
        return batch


class BenchService(SelectionService):
    def reprice(self, deltas):
        with TraceAnnotation("bench.reprice", epoch=self.price_epoch + 1):
            return super().reprice(deltas)


class BenchFrontend(ServeFrontend):
    """Stamps every published snapshot on the tick thread."""

    def __init__(self, *args, **kwargs):
        self.publications: List[Tuple[float, object]] = []
        super().__init__(*args, **kwargs)

    def _publish(self) -> None:
        with TraceAnnotation("bench.publish"):
            super()._publish()
        self.publications.append((time.perf_counter(), self._snapshot))


class Recorder:
    """``on_decision``: stamps window submissions (integer job ids)."""

    def __init__(self, n: int):
        self.done = np.full(n, np.nan)
        self.decisions: List[object] = [None] * n
        self.forwarded = np.zeros(n, dtype=bool)

    def __call__(self, decision) -> None:
        t = time.perf_counter()
        i = decision.job_id
        if type(i) is int:
            self.done[i] = t
            self.decisions[i] = decision
            self.forwarded[i] = \
                threading.current_thread().name == TICK_THREAD


def build_store(dep: gendep.Deployment) -> ProfilingStore:
    ids = dep.config_ids
    store = ProfilingStore(config_ids=ids)
    hours = dep.hours_of()
    for j, job in enumerate(dep.job_ids):
        klass, group = JobClass(dep.job_class[j]), dep.job_group[j]
        add = store.add
        for c, h in zip(dep.profiled[j].tolist(), hours[j].tolist()):
            add(job, ids[c], h, job_class=klass, group=group)
            klass = group = None        # metadata once per job
    return store


def route_key(route) -> Tuple[Optional[JobClass], Tuple[str, ...]]:
    klass, excl = route
    return (None if klass is None else JobClass(klass)), tuple(excl)


def _submission(job_id, route) -> Submission:
    klass, excl = route_key(route)
    return Submission(job_id, annotation=klass, exclude_groups=excl)


@dataclasses.dataclass
class Run:
    """What one run of the cell leaves for the metric readers."""

    mix: str                              # "open" | "saturate"
    seconds: float
    setup_s: float
    t0: float
    t1: float
    tick_due: np.ndarray                  # open: due time per window tick
    tick_pub: np.ndarray                  # first publication per window tick
    sub_due: np.ndarray
    sub_done: np.ndarray                  # nan: never decided
    sub_shed: np.ndarray
    lateness: np.ndarray                  # producer's submit - due
    spans: Dict[str, Tuple[int, float]]   # window's (count, seconds)
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: object = None                  # trace_reduce.Trace, --trace 1
    summary: object = None                # trace_reduce.Summary
    peaks: Optional[dict] = None
    _check: object = None
    _work: object = None

    def span_mean(self, name: str) -> Optional[float]:
        n, total = self.spans.get(name, (0, 0.0))
        return total / n if n else None

    def checks(self) -> Dict[str, Dict[str, float]]:
        return self._check()

    def tick_work(self, epochs: Sequence[int]) -> Dict[int, workmod.Work]:
        return self._work(epochs)


def _span_totals(reg) -> Dict[str, Tuple[int, int]]:
    return {name: (h["count"], int(round(h["sum"] * 1e9)))
            for name, h in reg.snapshot()["histograms"].items()}


def _span_window(before, after) -> Dict[str, Tuple[int, float]]:
    out = {}
    for name, (n, ns) in after.items():
        n0, ns0 = before.get(name, (0, 0))
        out[name] = (n - n0, (ns - ns0) / 1e9)
    return out


def run(cell, seed: int, seconds: float, window, devices, t_start: float,
        tick_rate: Optional[float] = None,
        sub_rate: Optional[float] = None) -> Run:
    spec, mix = cell.config, cell.traffic
    dep = gendep.build(spec, seed)
    ticks = mix["ticks"]
    open_loop = ticks["schedule"] == "fixed_rate"
    warm = mix["warm_ticks"]
    rate = n_window = None
    if open_loop:
        rate = tick_rate or ticks["rate_of_knee"] * spec["knee_ticks_per_s"]
        n_window = int(math.ceil(rate * seconds))
    batches = spot_batches(dep.base_prices, dep.spot_cols, dep.region_of_col,
                           dep.n_regions, ticks["pool"], ticks,
                           gendep.rng_for(seed, gendep.STREAM_WALK))

    live = list(dep.live0)
    spare = [r for r in range(len(dep.routes)) if r not in set(live)]
    churn = mix.get("submissions") is not None and bool(spare)
    warm_retire = warm_add = None
    if churn:
        # set-up exercises retire + register once, so the window compiles
        # nothing on that path
        warm_retire, warm_add = live[-1], spare[0]
        live[-1] = warm_add
    stream = None
    if mix.get("submissions") is not None:
        sub_mix = dict(mix["submissions"])
        if sub_rate is not None:
            sub_mix["rate_per_s"] = sub_rate
        stream = gensubs.stream(sub_mix, len(dep.routes), live, seconds,
                                gendep.rng_for(seed, gendep.STREAM_SUBS))
    n_subs = 0 if stream is None else stream.due_s.shape[0]
    subs = [] if stream is None else \
        [_submission(i, dep.routes[r]) for i, r in enumerate(stream.route)]

    store = build_store(dep)
    ids = dep.config_ids
    service = BenchService(IdentityCatalog(ids), store,
                           PriceTable(dict(zip(ids, dep.base_prices))),
                           backend=spec["backend"],
                           serve_top_k=spec["serve_top_k"])
    feed = ReplayFeed(ids, batches, warm, rate)
    rec = Recorder(n_subs)
    # the open mix stops after its window's ticks; the saturating one
    # when the window closes
    fe = BenchFrontend(service, feed, workers=spec["workers"],
                       queue_capacity=spec["queue_capacity"],
                       on_decision=rec,
                       ticks=warm + n_window if open_loop else None)
    initial = list(dep.live0)
    fe.warm([_submission(f"warm-{r}", dep.routes[r]) for r in initial])
    used = 0
    if churn:
        fe.retire_selection(*route_key(dep.routes[warm_retire]))
        fe.submit(_submission("warm-fwd", dep.routes[warm_add]))
        fe.serve_queued()
        fe.step_tick()
        used = 1
    for _ in range(used, warm):
        fe.step_tick()
    if stream is not None:
        for r in live[:8]:
            fe.submit(_submission(f"warm-serve-{r}", dep.routes[r]))
        fe.serve_queued()

    shed = np.zeros(n_subs, dtype=bool)
    lateness = np.zeros(n_subs)

    def produce(t0: float) -> None:
        due = stream.due_s
        retire = stream.retire
        for i in range(n_subs):
            at = t0 + due[i]
            wait = at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if retire[i] >= 0:
                fe.retire_selection(*route_key(dep.routes[retire[i]]))
            if not fe.submit(subs[i]):
                shed[i] = True
            lateness[i] = time.perf_counter() - at

    reg = service.metrics
    window.begin()
    with TraceAnnotation("bench.window"):
        before = _span_totals(reg)
        t0 = time.perf_counter()
        feed.open(t0)
        fe.start()
        producer = None
        if stream is not None:
            producer = threading.Thread(target=produce, args=(t0,),
                                        name="bench-producer", daemon=True)
            producer.start()
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        t1 = time.perf_counter()
        if not open_loop:
            fe.ticks = fe.ticker.tick_count       # stop ticking
        after = _span_totals(reg)
    window.end()

    if producer is not None:
        producer.join(seconds + 60.0)
    try:
        fe.drain(timeout=60.0)
        if open_loop:
            fe.await_ticks(warm + n_window, timeout=60.0)
    except TimeoutError as exc:
        print(f"after the window: {exc}", flush=True)
    stats = fe.shutdown()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)

    pubs_t = np.asarray([t for t, _ in fe.publications])
    pubs_tick = np.asarray([s.tick for _, s in fe.publications])
    window_ticks = np.arange(warm, warm + n_window) if open_loop else \
        np.arange(warm, stats.ticks)
    first = np.searchsorted(pubs_tick, window_ticks, side="left")
    tick_pub = np.where(first < pubs_t.size,
                        pubs_t[np.minimum(first, pubs_t.size - 1)], np.nan)
    tick_due = np.asarray([feed.due(t) for t in window_ticks]) \
        if open_loop else np.zeros(0)
    accepted = ~shed
    lost = int(np.sum(accepted & np.isnan(rec.done)))
    if open_loop:
        attempted, failed = n_subs, int(shed.sum()) + lost
    else:
        in_window = (tick_pub >= t0) & (tick_pub <= t1)
        attempted, failed = int(in_window.sum()), 0

    # what the program produced, read before its state is freed
    pos = {c: i for i, c in enumerate(ids)}
    route_of = {route_key(r): i for i, r in enumerate(dep.routes)}
    final = fe.snapshot
    fleet = {}
    for key in final.entries:
        ranking = service.rank(*key)
        scores = np.full(len(ids), np.inf)
        for rc in ranking:
            scores[pos[rc.config_id]] = rc.score
        fleet[route_of[key]] = scores
    epochs_ok = all(s.price_epoch == s.tick + 1 for _, s in fe.publications
                    if s.tick >= 0)
    publications = [(s.price_epoch,
                     tuple(sorted(route_of[k] for k in s.entries)))
                    for _, s in fe.publications]
    items = _served_items(mix, seed, rec, stream, fe.publications, t0, t1,
                          pos, route_of)
    final_epoch = service.price_epoch
    del fe, service, store, feed
    gc.collect()

    due_abs = (t0 + stream.due_s) if stream is not None else np.zeros(0)
    unpublished = int(np.isnan(tick_pub).sum()) if open_loop else 0
    rel_tol = spec["guarantee"]["rel_tol"]
    k = spec["serve_top_k"]

    def check() -> Dict[str, Dict[str, float]]:
        ref = refmod.Reference(dep)
        history = refmod.PriceHistory(dep.base_prices, batches)
        numbers = served_numbers(ref, history, items, k)
        numbers["fleet_score_err"] = fleet_number(ref, history, fleet,
                                                  final_epoch)
        limits = {"head_score_err": rel_tol, "head_rank_err": rel_tol,
                  "fleet_score_err": rel_tol, "cost_mismatches": 0,
                  "lost_answers": 0, "unpublished_ticks": 0,
                  "epoch_mismatches": 0}
        numbers["lost_answers"] = lost
        numbers["unpublished_ticks"] = unpublished
        numbers["epoch_mismatches"] = 0 if epochs_ok else 1
        return {name: {"value": float(numbers[name]), "limit": lim}
                for name, lim in limits.items()}

    def tick_work(epochs: Sequence[int]) -> Dict[int, workmod.Work]:
        return _tick_work(dep, batches, publications, epochs)

    return Run(mix="open" if open_loop else "saturate", seconds=seconds,
               setup_s=t0 - t_start, t0=t0, t1=t1, tick_due=tick_due,
               tick_pub=tick_pub, sub_due=due_abs, sub_done=rec.done,
               sub_shed=shed, lateness=lateness,
               spans=_span_window(before, after), attempted=attempted,
               failed=failed, memory_peak_bytes=int(peak),
               _check=check, _work=tick_work)


# --- what the window served, and the reference's view of it -----------------

Item = Tuple[int, int, List[Tuple[int, float]], float]
#      (epoch, route, served head [(column, score)], $/h of the winner)


def _head(ranking, pos) -> List[Tuple[int, float]]:
    return [(pos[rc.config_id], rc.score) for rc in ranking]


def _served_items(mix, seed, rec: Recorder, stream, publications, t0, t1,
                  pos, route_of) -> List[Item]:
    """A sample drawn from the seed of what the window served: decisions
    (every forwarded one, the rest at random) in the open mix; entries
    of snapshots published in the window otherwise."""
    rng = gendep.rng_for(seed, STREAM_CHECK)
    want = mix["check"]
    items: List[Item] = []
    if stream is not None:
        done = np.flatnonzero(~np.isnan(rec.done))
        fwd = done[rec.forwarded[done]]
        rest = done[~rec.forwarded[done]]
        n_rest = max(0, min(rest.size, want["decisions"] - fwd.size))
        pick = np.concatenate(
            [fwd, rng.choice(rest, n_rest, replace=False) if n_rest
             else np.zeros(0, dtype=np.int64)]).astype(np.int64)
        for i in np.sort(pick):
            d = rec.decisions[i]
            items.append((d.price_epoch, int(stream.route[i]),
                          _head(d.ranking, pos), d.hourly_cost))
        return items
    in_window = [s for t, s in publications if t0 <= t <= t1]
    if not in_window:
        return items
    n = min(len(in_window), want["snapshots"])
    for j in np.sort(rng.choice(len(in_window), n, replace=False)):
        snap = in_window[j]
        keys = list(snap.entries)
        m = min(len(keys), want["routes_per_snapshot"])
        for r in np.sort(rng.choice(len(keys), m, replace=False)):
            entry = snap.entries[keys[r]]
            items.append((snap.price_epoch, route_of[keys[r]],
                          _head(entry.head, pos), entry.hourly_cost))
    return items


def served_numbers(ref: refmod.Reference, history: refmod.PriceHistory,
                   items: Sequence[Item], k: int) -> Dict[str, float]:
    score_err = rank_err = 0.0
    mismatches = 0
    by_epoch: Dict[int, List[Item]] = {}
    for it in items:
        by_epoch.setdefault(it[0], []).append(it)
    for epoch, prices in history.walk(list(by_epoch)):
        norm = ref.norm(prices)
        cache: Dict[int, np.ndarray] = {}
        for _, route, served, cost in by_epoch[epoch]:
            want = cache.get(route)
            if want is None:
                want = cache[route] = ref.scores(norm, route)
            s, r = refmod.head_errors(served, want, k)
            score_err, rank_err = max(score_err, s), max(rank_err, r)
            if cost != prices[served[0][0]]:
                mismatches += 1
    return {"head_score_err": score_err, "head_rank_err": rank_err,
            "cost_mismatches": mismatches}


def fleet_number(ref: refmod.Reference, history: refmod.PriceHistory,
                 fleet: Dict[int, np.ndarray], epoch: int) -> float:
    worst = 0.0
    for _, prices in history.walk([epoch]):
        norm = ref.norm(prices)
        for route, got in fleet.items():
            worst = max(worst, float(refmod.rel_err(
                got, ref.scores(norm, route)).max()))
    return worst


def _tick_work(dep, batches, publications, epochs) -> Dict[int, workmod.Work]:
    """Least work of the ticks that made ``epochs``, from the reference's
    row minima and the members live at each tick."""
    members_at: Dict[int, Tuple[int, ...]] = {}
    for e, routes in publications:
        members_at.setdefault(e, routes)    # as the tick's reprice saw it
    ref = refmod.Reference(dep)
    history = refmod.PriceHistory(dep.base_prices, batches)
    wanted = sorted({e for e in epochs if e >= 1} |
                    {e - 1 for e in epochs if e >= 1})
    mins = {}
    for e, prices in history.walk(wanted):
        mins[e] = (ref.hours * prices[ref.cols]).min(axis=1)
    out = {}
    for e in sorted(set(epochs)):
        if e < 1 or e not in members_at:
            continue
        changed = batches[(e - 1) % len(batches)][0]
        moved = np.flatnonzero(mins[e] != mins[e - 1])
        out[e] = workmod.tick_work(dep.profiled, dep.n_cfgs, changed, moved,
                                   [ref.rows(r) for r in members_at[e]])
    return out
