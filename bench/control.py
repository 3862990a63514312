"""The control of ``correct``: the reference put in the program's place,
computed one precision below the configuration's float32.

While installed, every score the served path reads from the fleet state
(``BatchedRankState.top_k`` for the snapshot heads and the forwarded
decisions, ``BatchedRankState.scores`` for full rankings) comes from the
reference's formula evaluated in bfloat16 on the device: cost, the
job's cheapest cost and the normalised cost in bfloat16, and each
member's sum as a matmul of bfloat16 operands accumulated in float32
(the default precision of a TPU matmul).  The program's own step still
runs; its scores are not served.  A run with the control installed must
come out not correct.
"""
from __future__ import annotations

import contextlib
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.selector import BatchedRankState, RankedConfig


@jax.jit
def _bf16_scores(hours, mask, prices, row_mask):
    bf = jnp.bfloat16
    cost = jnp.where(mask, hours.astype(bf) * prices.astype(bf)[None, :],
                     jnp.inf)
    best = cost.min(axis=1, keepdims=True)
    norm = jnp.where(mask, cost / best, 0).astype(bf)
    return jnp.matmul(row_mask.astype(bf), norm,
                      preferred_element_type=jnp.float32)


def _scores(state: BatchedRankState, slot: int) -> np.ndarray:
    row = state.d_row_masks[slot]
    out = _bf16_scores(state.d_hours, state.d_mask, state.d_prices, row)
    return np.asarray(out, dtype=np.float64)


def _top_k(self: BatchedRankState, key, k: int) -> List[RankedConfig]:
    slot = self._slot_of(key)
    counts = self._counts[slot]
    scores = np.where(counts > 0, _scores(self, slot), np.inf)
    order = np.lexsort((np.arange(scores.size), scores))[:k]
    return [RankedConfig(self.config_ids[i], float(scores[i]),
                         float(scores[i]) / counts[i] if counts[i]
                         else float("inf")) for i in order]


def _scores_method(self: BatchedRankState, key) -> np.ndarray:
    return _scores(self, self._slot_of(key))


@contextlib.contextmanager
def installed():
    saved = BatchedRankState.top_k, BatchedRankState.scores
    BatchedRankState.top_k = _top_k
    BatchedRankState.scores = _scores_method
    try:
        yield
    finally:
        BatchedRankState.top_k, BatchedRankState.scores = saved
