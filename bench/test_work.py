"""The least work of a tick against counts made by hand."""
from __future__ import annotations

import numpy as np

from work import Work, tick_work


def test_changed_column_and_moved_row():
    # job 0 profiles configs 0 and 1, job 1 configs 1 and 2, job 2 3 and 4;
    # config 1 is re-quoted and job 2's cheapest config moved
    w = tick_work(np.array([[0, 1], [1, 2], [3, 4]]), 6, np.array([1]),
                  np.array([2]), [np.array([0, 1]), np.array([1, 2])])
    # runtime cells read: (0,1) (1,1) of config 1, (2,3) (2,4) of job 2
    # scores changed: member 0 config 1; member 1 configs 1, 3, 4
    assert w.bytes == 4 * 4 + 8 * 4
    # 2 operations per cell read; member 0 folds 2 cells of config 1,
    # member 1 folds 1 cell of config 1 and job 2's 2 cells
    assert w.flops == 2 * 4 + (2 + 1 + 2)


def test_moved_row_inside_changed_column():
    w = tick_work(np.array([[0, 1], [1, 2]]), 3, np.array([1]),
                  np.array([1]), [np.array([0, 1])])
    # cells: config 1 in jobs 0 and 1, plus job 1's config 2
    # scores changed: configs 1 and 2 of the one member
    assert w.bytes == 4 * 3 + 8 * 2
    assert w.flops == 2 * 3 + (2 + 1)


def test_nothing_moved():
    w = tick_work(np.array([[0, 1], [1, 2]]), 3, np.array([0, 0]),
                  np.zeros(0, dtype=np.int64), [np.array([1])])
    # config 0 is profiled only by job 0, which the member does not hold
    assert w.bytes == 4 * 1 and w.flops == 2 * 1


def test_seconds_is_the_larger_bound():
    peaks = {"flops_per_s": 10.0, "bytes_per_s": 2.0}
    assert Work(flops=100.0, bytes=4.0).seconds(peaks) == 10.0
    assert Work(flops=1.0, bytes=40.0).seconds(peaks) == 20.0
