"""95th percentile, over every tick due in the window, of the time from
the tick's due time to the publication of the first snapshot that
reflects it.  A tick never published counts as infinitely stale."""
import numpy as np


def read(run):
    if run.tick_due.size == 0:
        return None
    stale = np.where(np.isnan(run.tick_pub), np.inf,
                     run.tick_pub - run.tick_due)
    return float(np.percentile(stale, 95)) * 1e3
