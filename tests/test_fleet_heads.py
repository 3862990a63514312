"""Fleet-wide head serving on the single-device fleet states.

:class:`~repro.selector.BatchedRankState` and its Pallas subclass serve
``top_k`` from one ``lax.top_k`` over every slot, read back once and
memoized until the scores or the membership change (DESIGN.md §10).
Every head served from that memo must equal the head of the member's
materialized ranking element-wise, near-ties included, across reprices,
adds, retires, capacity growth and mixed depths; the registry counters
say how often the fleet launch ran and how often the memo answered.
"""
import numpy as np
import pytest

from repro.obs import MetricsRegistry
from repro.selector import (BatchedRankState, NothingRankableError,
                            PallasBatchedRankState, backend_available)

pytestmark = pytest.mark.skipif(not backend_available("jax_batched"),
                                reason="jax not installed")

FLEET_STATES = {"jax_batched": BatchedRankState,
                "jax_pallas": PallasBatchedRankState}

N_JOBS, N_CFGS = 6, 14
MEMBERS = {"all": list(range(N_JOBS)), "head": [0, 1], "tail": [3, 4, 5]}


def _universe(seed=3):
    """Three exact clone columns (bit-equal scores) and two near-tie
    columns (runtimes a few float32 ulps apart), plus one unprofiled
    column, so both tie-breaks are exercised."""
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.05, 10.0, (N_JOBS, N_CFGS))
    prices = rng.uniform(0.5, 20.0, N_CFGS)
    for c in (N_CFGS - 2, N_CFGS - 1):                 # exact clones
        hours[:, c], prices[c] = hours[:, N_CFGS - 3], prices[N_CFGS - 3]
    hours[:, 2] = hours[:, 1] * (1 + 3e-7)             # near-ties
    prices[2] = prices[1]
    mask = np.ones((N_JOBS, N_CFGS), dtype=bool)
    mask[:, 0] = False                                 # never profiled
    ids = [f"c{i}" for i in range(N_CFGS)]
    return hours, mask, prices, ids


def _state(backend, capacity=None):
    hours, mask, prices, ids = _universe()
    reg = MetricsRegistry()
    state = FLEET_STATES[backend](hours, mask, prices, ids,
                                  capacity=capacity, metrics=reg)
    for key, rows in MEMBERS.items():
        state.add_state(key, rows=rows)
    return state, reg, ids


def _launches(reg):
    counters = reg.snapshot()["counters"]
    return counters["rank.head_batches"], counters["rank.head_memo_hits"]


def _assert_heads(state, keys, k):
    for key in keys:
        assert state.top_k(key, k) == state.ranking(key)[:k]


@pytest.mark.parametrize("backend", sorted(FLEET_STATES))
def test_memoised_heads_are_the_heads_of_every_ranking(backend):
    state, _, ids = _state(backend)
    for k in (1, 3):
        _assert_heads(state, MEMBERS, k)
        assert state.winner("all") == state.ranking("all")[0]
    state.reprice({ids[3]: 0.01, ids[7]: 40.0})
    _assert_heads(state, MEMBERS, 3)
    # near-ties and clones come back in catalog order from the memo
    full = [r.config_id for r in state.top_k("all", N_CFGS)]
    assert full == [r.config_id for r in state.ranking("all")]
    near = {r.config_id: r.score for r in state.ranking("all")}
    assert near[ids[1]] != near[ids[2]]
    assert abs(near[ids[1]] - near[ids[2]]) < 1e-5 * near[ids[1]]
    i = full.index(ids[N_CFGS - 3])
    assert full[i:i + 3] == ids[N_CFGS - 3:]


@pytest.mark.parametrize("backend", sorted(FLEET_STATES))
def test_add_and_retire_between_two_heads_of_one_tick(backend):
    state, _, ids = _state(backend)
    state.reprice({ids[5]: 0.2})
    _assert_heads(state, ["all"], 3)                   # memo filled
    state.add_state("mid", rows=[1, 2, 3])
    _assert_heads(state, ["all", "head", "tail", "mid"], 3)
    state.retire_state("head")
    _assert_heads(state, ["all", "tail", "mid"], 3)
    with pytest.raises(NothingRankableError, match="retired"):
        state.top_k("head", 3)


@pytest.mark.parametrize("backend", sorted(FLEET_STATES))
def test_memoised_heads_across_a_capacity_doubling(backend):
    state, _, ids = _state(backend, capacity=4)
    _assert_heads(state, MEMBERS, 2)
    state.add_state("d", rows=[2])
    state.add_state("e", rows=[0, 5])                  # 5 members > 4
    assert state.realloc_count == 1
    keys = list(MEMBERS) + ["d", "e"]
    _assert_heads(state, keys, 2)
    state.reprice({ids[9]: 0.05})
    _assert_heads(state, keys, 2)


@pytest.mark.parametrize("backend", sorted(FLEET_STATES))
def test_two_depths_in_one_tick_are_each_launched_once(backend):
    state, reg, ids = _state(backend)
    state.reprice({ids[4]: 3.0})
    for _ in range(2):
        for k in (1, 3):
            _assert_heads(state, MEMBERS, k)
    batches, _ = _launches(reg)
    assert batches == 2                                # one per depth


@pytest.mark.parametrize("backend", sorted(FLEET_STATES))
def test_retired_key_raises_when_the_memo_is_warm(backend):
    state, _, _ = _state(backend)
    state.retire_state("tail")
    state.top_k("all", 3)                              # memo filled
    with pytest.raises(NothingRankableError, match="retired"):
        state.top_k("tail", 3)
    with pytest.raises(ValueError, match="unknown member"):
        state.top_k("never", 3)


@pytest.mark.parametrize("backend", sorted(FLEET_STATES))
def test_head_counters_count_launches_per_state_change(backend):
    state, reg, ids = _state(backend)
    for key in MEMBERS:
        state.top_k(key, 3)
    assert _launches(reg) == (1, 2)
    for key in MEMBERS:
        state.top_k(key, 3)
    assert _launches(reg) == (1, 5)
    state.reprice({ids[6]: 1.5})                       # a state change
    for key in MEMBERS:
        state.top_k(key, 3)
    assert _launches(reg) == (2, 7)
    state.retire_state("head")                         # another
    state.top_k("all", 3)
    assert _launches(reg) == (3, 7)
    hists = reg.snapshot()["histograms"]
    assert hists["topk.dispatch"]["count"] == 3
    assert hists["topk.readback"]["count"] == 3
