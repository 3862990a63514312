"""Ticks applied and published inside the window, over its length, with
the feed never blocking."""
import numpy as np


def read(run):
    if run.mix != "saturate":
        return None
    inside = (run.tick_pub >= run.t0) & (run.tick_pub <= run.t1)
    return float(np.sum(inside)) / (run.t1 - run.t0)
