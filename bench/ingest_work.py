"""The least work any implementation must do for one profile ingest.

An ingest writes new runtimes into some job rows.  Whatever the
implementation, it has to

* write every written cell's runtime and read every cell whose
  normalised cost changes (a written cell whose runtime changed; every
  profiled cell of a row whose cheapest cost moved), four bytes
  (float32) each, and compute each such cell's cost and normalised cost
  (two operations);
* read and write every score that changes, four bytes each way: in each
  live member, every column renormalised in a row the member holds;
* fold each renormalised cell into every member that holds its row (one
  addition per member and cell).

A written cell whose runtime did not change (a record replayed with the
same runtime) moves no score.  What today's step does beyond that (the
touched rows recomputed whole, the fold as a matmul over every slot) is
not counted, so a more incremental step still reads under 100% of the
roofline.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from work import CELL_BYTES, SCORE_BYTES, Work


def ingest_work(changes: Sequence[Tuple[int, np.ndarray, np.ndarray]],
                members: Sequence[np.ndarray]) -> Work:
    """``changes`` per touched job row: ``(row, written columns,
    renormalised columns)``; ``members`` the job rows of each live
    member."""
    cells = sum(np.union1d(written, renormed).size
                for _, written, renormed in changes)
    folds = 0
    score_cells = 0
    for rows in members:
        held = [renormed for row, _, renormed in changes if row in set(
            np.asarray(rows).tolist())]
        folds += sum(r.size for r in held)
        if held:
            score_cells += np.unique(np.concatenate(held)).size
    return Work(flops=2.0 * cells + folds,
                bytes=CELL_BYTES * cells + SCORE_BYTES * score_cells)
