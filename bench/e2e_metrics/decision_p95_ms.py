"""95th percentile, over every submission due in the window, of the time
from its due time to its decision (open loop: a late producer or a full
queue counts against the system).  A shed or lost submission counts as
infinitely late, and in ``failed``."""
import numpy as np


def read(run):
    if run.sub_due.size == 0:
        return None
    late = np.where(np.isnan(run.sub_done) | run.sub_shed, np.inf,
                    run.sub_done - run.sub_due)
    return float(np.percentile(late, 95)) * 1e3
