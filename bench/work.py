"""The least work any implementation must do for one fleet price tick.

A tick re-quotes some configurations.  Whatever the implementation, it
has to

* read the runtime of every profiled cell of a re-quoted configuration,
  and of every profiled cell of a job whose cheapest configuration moved
  (its whole row renormalises), four bytes (float32) per cell;
* read and write every score that changes, four bytes each way: in each
  live member, the re-quoted configurations that member profiled, and
  every configuration a member's moved job profiled;
* compute each of those cells' cost and normalised cost (two operations)
  and fold each cell into every member that holds its job (one addition
  per member and cell).

What today's step does beyond that (whole-matrix passes, dense matmuls
over every member and job) is not counted, so a faster or more
incremental step still reads under 100% of the roofline.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

CELL_BYTES = 4           # float32 runtime per profiled cell
SCORE_BYTES = 4 + 4      # read and write one float32 score


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def seconds(self, peaks: dict) -> float:
        """The least time on the chip: the larger of the two bounds."""
        return max(self.flops / peaks["flops_per_s"],
                   self.bytes / peaks["bytes_per_s"])


def tick_work(profiled: np.ndarray, n_cfgs: int, changed: np.ndarray,
              moved: np.ndarray, members: Sequence[np.ndarray]) -> Work:
    """``profiled`` (J, P) columns each job profiled; ``changed`` the
    re-quoted columns; ``moved`` the jobs whose cheapest configuration
    changed; ``members`` the job rows of each live member."""
    n_jobs, per_job = profiled.shape
    changed = np.unique(changed)
    moved = np.unique(moved)
    in_changed = np.isin(profiled, changed)           # (J, P)
    changed_cells = int(in_changed.sum())
    overlap = int(in_changed[moved].sum())
    cells = changed_cells + moved.size * per_job - overlap

    # per-member view: member x job membership, job x column incidence
    member_of = np.zeros((len(members), n_jobs), dtype=np.float32)
    for s, rows in enumerate(members):
        member_of[s, rows] = 1.0
    jobs_c, slots_c = np.nonzero(in_changed)
    has_changed = np.zeros((n_jobs, changed.size), dtype=np.float32)
    has_changed[jobs_c, np.searchsorted(changed,
                                        profiled[jobs_c, slots_c])] = 1.0
    counts_changed = member_of @ has_changed              # (S, k)
    incidences = float(counts_changed.sum())
    if moved.size:
        moved_cols = np.zeros((moved.size, n_cfgs), dtype=np.float32)
        moved_cols[np.repeat(np.arange(moved.size), per_job),
                   profiled[moved].ravel()] = 1.0
        touched = member_of[:, moved] @ moved_cols > 0     # (S, C)
        touched[:, changed] |= counts_changed > 0
        n_score_cells = float(touched.sum())
        rest = per_job - in_changed[moved].sum(axis=1)     # (m,)
        incidences += float((member_of[:, moved] * rest).sum())
    else:
        n_score_cells = float((counts_changed > 0).sum())
    return Work(flops=2.0 * cells + incidences,
                bytes=CELL_BYTES * cells + SCORE_BYTES * n_score_cells)
