"""The profile ingest's share of its roofline: the least time the chip
could take for the window's ingests (``ingest_work.py``, one per
``bench.ingest`` span, against the peaks of ``peaks.json``) over the
device time of the ingest step's own program.

The program enqueues the ingest without a sync, so its device work runs
after the host span that issued it, beside the reprice's.  The device
time is therefore found by program, not by span: the busy time of each
device plane inside the executions of the jitted ingest step (events
named ``jit_ingest`` on the plane's ``XLA Modules`` line), clipped to
the traced window, averaged over the planes."""
import re
from typing import List

import numpy as np

import trace_reduce

MODULES_LINE = "XLA Modules"
#: the jitted ``ingest`` of ``BatchedRankState`` and of its Pallas form,
#: with the program id the profiler may append
INGEST_MODULE = re.compile(r"jit_ingest(\(\d+\))?$")


def module_intervals(planes, pattern=INGEST_MODULE) -> List[np.ndarray]:
    """Per device plane, in ``trace_reduce.load``'s order: the sorted,
    disjoint [start, end) ns of the executions of the programs whose
    module name matches ``pattern``."""
    out = []
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        iv = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
              for ln in plane.lines if ln.name == MODULES_LINE
              for ev in ln.events if pattern.match(ev.name)]
        out.append(trace_reduce.union(
            np.asarray(iv, dtype=np.int64).reshape(-1, 2)))
    return out


def device_in_modules(trace, modules, lo: int, hi: int) -> float:
    """Device seconds of ``trace`` inside ``modules`` and [lo, hi), the
    mean over device planes."""
    per_plane = [sum(trace_reduce.covered(busy, max(int(s), lo),
                                          min(int(e), hi))
                     for s, e in mods)
                 for busy, mods in zip(trace.busy, modules)]
    return float(np.mean(per_plane)) / 1e9 if per_plane else 0.0


def _planes(log_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(trace_reduce.find_xplane(log_dir)).planes


def read(run):
    if run.trace is None or getattr(run, "trace_dir", None) is None \
            or not hasattr(run, "ingest_work"):
        return None
    lo, hi = trace_reduce.window_of(run.trace, "bench.window")
    spans = trace_reduce.spans_named(run.trace, "bench.ingest", lo, hi)
    if not spans:
        return None
    device = device_in_modules(run.trace, module_intervals(
        _planes(run.trace_dir)), lo, hi)
    work = run.ingest_work([int(s.stats["epoch"]) for s in spans])
    least = sum(w.seconds(run.peaks) for w in work.values())
    return None if device <= 0 else least / device * 100.0
