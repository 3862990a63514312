"""The fleet reprice's share of its roofline: the least time the chip
could take for the ticks' least work (``work.py``, against the peaks of
``peaks.json``) over the device time inside the ``bench.reprice`` spans
of the traced window."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = trace_reduce.window_of(run.trace, "bench.window")
    spans = trace_reduce.spans_named(run.trace, "bench.reprice", lo, hi)
    if not spans:
        return None
    work = run.tick_work([int(s.stats["epoch"]) for s in spans])
    least = device = 0.0
    for s in spans:
        w = work.get(int(s.stats["epoch"]))
        if w is not None:
            least += w.seconds(run.peaks)
            device += trace_reduce.device_in(run.trace, s)
    return None if device <= 0 else least / device * 100.0
