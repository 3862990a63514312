"""``correct`` on the CPU at tiny sizes: the program passes; the control
(the reference in bfloat16 in the program's place) and each fault the
cells can have, planted in the timed path, fail.  The harness's look for
a chip is skipped; everything after it runs as in a real run."""
from __future__ import annotations

import time

import jax
import pytest

import control
import harness
from repro.selector import BatchedRankState

SECONDS = 2.0
SEED = 2 ** 31 + 99


def _run(cell):
    return harness.run(cell, SEED, SECONDS, False, jax.devices()[:1],
                       time.perf_counter())


@pytest.mark.parametrize("name", ["store.open", "flora_gcp.open",
                                  "flora_gcp.saturate"])
def test_program_is_correct(tiny, name):
    res = _run(tiny(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def test_control_is_not_correct(tiny):
    with control.installed():
        res = _run(tiny("store.open"))
    assert not res["correct"]
    assert res["checks"]["fleet_score_err"]["value"] > 1e-4


def _unchanged(orig):
    def reprice(self, deltas):
        return 0
    return reprice


def _half_batch(orig):
    def reprice(self, deltas):
        items = list(dict(deltas).items())
        return orig(self, dict(items[: len(items) // 2]))
    return reprice


def _altered(orig):
    def top_k(self, key, k):
        head = orig(self, key, k)
        return [head[-1]] + head[1:-1] + [head[0]]
    return top_k


@pytest.mark.parametrize("name,method,fault", [
    ("store.open", "reprice", _unchanged),
    ("store.open", "reprice", _half_batch),
    ("store.open", "top_k", _altered),
    ("flora_gcp.saturate", "reprice", _unchanged),
    ("flora_gcp.saturate", "top_k", _altered),
])
def test_fault_is_not_correct(tiny, monkeypatch, name, method, fault):
    orig = getattr(BatchedRankState, method)
    monkeypatch.setattr(BatchedRankState, method, fault(orig))
    res = _run(tiny(name))
    assert not res["correct"], res["checks"]
