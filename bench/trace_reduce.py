"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

* Device planes are the planes named ``/device:TPU:<n>``.  On each, the
  busy time is the union of the intervals in which an operation ran: the
  events of its ``XLA Ops`` line.  A device plane without that line is an
  error: its other lines (modules, steps) span whole programs, idle
  stretches included.
* Host spans are the events whose name starts with ``bench.``: the
  benchmark's own ``jax.profiler.TraceAnnotation``s around the calls into
  each layer, with their arguments as stats.
* Inside a host span, the device time is the busy union clipped to the
  span.  The spans this is used for end in a host sync, so the device
  work they issued lies inside them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Span:
    name: str
    start: int          # ns
    end: int            # ns
    stats: Dict[str, object]


@dataclasses.dataclass
class Trace:
    #: per device plane: (n, 2) sorted disjoint busy intervals in ns
    busy: List[np.ndarray]
    #: per device plane: [(op name, start ns, end ns)]
    ops: List[List[Tuple[str, int, int]]]
    spans: List[Span]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (n, 2) [start, end) intervals."""
    if intervals.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.int64)


def covered(busy: np.ndarray, lo: int, hi: int) -> int:
    """Nanoseconds of ``busy`` (sorted, disjoint) inside [lo, hi)."""
    if busy.size == 0 or hi <= lo:
        return 0
    i0 = int(np.searchsorted(busy[:, 1], lo, side="right"))
    i1 = int(np.searchsorted(busy[:, 0], hi, side="left"))
    if i1 <= i0:
        return 0
    part = busy[i0:i1]
    return int((np.minimum(part[:, 1], hi) - np.maximum(part[:, 0], lo))
               .sum())


def _op_name(event) -> str:
    stats = dict(event.stats)
    module = stats.get("hlo_module")
    return f"{module}/{event.name}" if module else event.name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    busy, ops, spans = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chosen = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not chosen:
                raise ValueError(f"device plane {plane.name!r} has no "
                                 f"{OPS_LINE!r} line")
            events = [(_op_name(ev), int(ev.start_ns),
                       int(ev.start_ns + ev.duration_ns))
                      for ln in chosen for ev in ln.events]
            ops.append(events)
            busy.append(union(np.asarray([(s, e) for _, s, e in events],
                                         dtype=np.int64).reshape(-1, 2)))
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, int(ev.start_ns),
                                          int(ev.start_ns + ev.duration_ns),
                                          dict(ev.stats)))
    spans.sort(key=lambda s: s.start)
    return Trace(busy=busy, ops=ops, spans=spans)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over device planes
    idle_share: float
    #: per span name: (spans, device seconds inside them, summed)
    in_spans: Dict[str, Tuple[int, float]]
    device_ops: List[List[object]]      # [[op, seconds]] top 10
    idle_gaps: List[List[object]]       # [[host activity, seconds]] top 10


def window_of(trace: Trace, name: str) -> Tuple[int, int]:
    """[start, end) of the first host span called ``name``."""
    for s in trace.spans:
        if s.name == name:
            return s.start, s.end
    raise ValueError(f"no {name!r} span in the trace")


def summarize(trace: Trace, lo: int, hi: int, top: int = 10) -> Summary:
    if not trace.busy:
        raise ValueError("the trace holds no device plane")
    window = hi - lo
    busy_ns = float(np.mean([covered(b, lo, hi) for b in trace.busy]))
    inside: Dict[str, Tuple[int, float]] = {}
    for s in trace.spans:
        if s.start >= lo and s.end <= hi:
            n, t = inside.get(s.name, (0, 0.0))
            dev = float(np.mean([covered(b, s.start, s.end)
                                 for b in trace.busy]))
            inside[s.name] = (n + 1, t + dev / 1e9)
    per_op: Dict[str, float] = {}
    for name, s, e in trace.ops[0]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=window / 1e9, busy_s=busy_ns / 1e9,
                   idle_share=1.0 - busy_ns / window, in_spans=inside,
                   device_ops=[[k, v] for k, v in ops],
                   idle_gaps=_idle_by_host(trace, lo, hi, top))


def _idle_by_host(trace: Trace, lo: int, hi: int,
                  top: int) -> List[List[object]]:
    """Idle time of device 0 inside [lo, hi), by the innermost benchmark
    span the host was in at the middle of each gap."""
    busy = trace.busy[0]
    edges = busy[(busy[:, 1] > lo) & (busy[:, 0] < hi)]
    starts = np.concatenate([[lo], np.minimum(edges[:, 1], hi)])
    ends = np.concatenate([np.maximum(edges[:, 0], lo), [hi]])
    spans = [s for s in trace.spans if s.end > lo and s.start < hi
             and s.name != "bench.window"]
    span_starts = np.asarray([s.start for s in spans], dtype=np.int64)
    by: Dict[str, float] = {}
    for s, e in zip(starts, ends):
        if e <= s:
            continue
        mid = (s + e) // 2
        label = "outside every benchmark span"
        # the latest-starting span that still covers the middle, among
        # the few that began last: the innermost one where spans nest
        last = int(np.searchsorted(span_starts, mid, side="right")) - 1
        for i in range(last, max(last - 4, -1), -1):
            if spans[i].end > mid:
                label = spans[i].name
                break
        by[label] = by.get(label, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(by.items(),
                                      key=lambda kv: -kv[1])[:top]]


def device_in(trace: Trace, span: Span) -> float:
    """Device seconds inside one host span (mean over device planes)."""
    return float(np.mean([covered(b, span.start, span.end)
                          for b in trace.busy])) / 1e9


def reduce_dir(log_dir: str, window_span: str = "bench.window"
               ) -> Tuple[Trace, Summary]:
    trace = load(find_xplane(log_dir))
    lo, hi = window_of(trace, window_span)
    return trace, summarize(trace, lo, hi)


def spans_named(trace: Trace, name: str, lo: Optional[int] = None,
                hi: Optional[int] = None) -> List[Span]:
    return [s for s in trace.spans if s.name == name
            and (lo is None or s.start >= lo) and (hi is None or s.end <= hi)]
