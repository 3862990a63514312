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
    # the XLA fleet's reprice takes the depth asked before it in its own
    # program: the memo answers all three heads after it
    fused = backend == "jax_batched"
    assert _launches(reg) == ((1, 8) if fused else (2, 7))
    state.retire_state("head")                         # another
    state.top_k("all", 3)
    assert _launches(reg) == ((2, 8) if fused else (3, 7))
    hists = reg.snapshot()["histograms"]
    assert hists["topk.dispatch"]["count"] == (2 if fused else 3)
    assert hists["topk.readback"]["count"] == (2 if fused else 3)


# --- the heads taken by the XLA fleet's reprice ------------------------------------

def _fused(reg):
    return reg.snapshot()["counters"]["rank.reprice_heads"]


def _separate_heads(state, k):
    """Every slot's ``k``-head from a separate top-k launch."""
    from repro.selector.rank import _jax_topk_fn
    idx, vals = _jax_topk_fn()(state.d_scores, state._d_finite, k)
    return np.asarray(idx), np.asarray(vals, dtype=np.float64)


def _spy_depths(state, operands=None):
    """Record the depth of each fused reprice+top-k program ``state``
    runs, and, into ``operands``, the delta operand each reprice hands
    its program, fused or plain."""
    depths, real, plain = [], state._step_heads, state._step

    def spy(*args):
        depths.append(args[-1])
        if operands is not None:
            operands.append(args[8])
        return real(*args)

    def spy_plain(*args):
        operands.append(args[8])
        return plain(*args)
    state._step_heads = spy
    if operands is not None:
        state._step = spy_plain
    return depths


def test_every_shallower_head_is_a_prefix_of_the_deeper():
    """``lax.top_k`` keeps the lower index first on exact ties, unprofiled
    configurations included, so a depth-k head is the first k entries
    of any deeper one: one launch at the deepest depth serves them all."""
    state, _, ids = _state("jax_batched")
    state.reprice({ids[3]: 0.5})
    idx, vals = _separate_heads(state, N_CFGS)
    for k in range(1, N_CFGS):
        ik, vk = _separate_heads(state, k)
        np.testing.assert_array_equal(ik, idx[:, :k])
        np.testing.assert_array_equal(vk, vals[:, :k])


def test_reprice_takes_the_heads_asked_before_it():
    state, reg, ids = _state("jax_batched")
    _assert_heads(state, MEMBERS, 3)
    launched = _launches(reg)
    # a clone column moves onto its twins' price: exact ties stay exact
    state.reprice({ids[N_CFGS - 1]: state.prices[N_CFGS - 3],
                   ids[1]: 0.9, ids[2]: 0.9, ids[6]: 0.02})
    assert _fused(reg) == 1
    idx, vals = _separate_heads(state, 3)
    memo_idx, memo_vals = state._fleet_heads(3)
    np.testing.assert_array_equal(memo_idx, idx)
    np.testing.assert_array_equal(memo_vals, vals)
    _assert_heads(state, MEMBERS, 3)
    full = [r.config_id for r in state.ranking("all")]
    i = full.index(ids[N_CFGS - 3])
    assert full[i:i + 3] == ids[N_CFGS - 3:]
    assert _launches(reg)[0] == launched[0]            # no launch of its own


def test_two_depths_asked_before_a_reprice_take_one_program():
    state, reg, ids = _state("jax_batched")
    depths = _spy_depths(state)
    for k in (1, 3):
        _assert_heads(state, MEMBERS, k)
    state.reprice({ids[4]: 3.0, ids[8]: 0.3})
    assert depths == [3] and _fused(reg) == 1
    launched = _launches(reg)[0]
    for k in (1, 3):
        memo = state._fleet_heads(k)
        for got, want in zip(memo, _separate_heads(state, k)):
            np.testing.assert_array_equal(got, want)
        _assert_heads(state, MEMBERS, k)
    assert _launches(reg)[0] == launched
    state.reprice({ids[4]: 2.0})           # both depths asked again
    assert depths == [3, 3]


def test_a_reprice_with_no_depth_asked_is_the_plain_step():
    state, reg, ids = _state("jax_batched")
    depths = _spy_depths(state)
    state.reprice({ids[5]: 0.2})
    assert depths == [] and _fused(reg) == 0
    assert _launches(reg) == (0, 0)
    _assert_heads(state, MEMBERS, 3)                   # launched as before
    assert _launches(reg) == (1, 2)
    state.reprice({ids[5]: 0.3})                       # now the depth is asked
    state.reprice({ids[5]: 0.4})                       # and used up
    assert depths == [3] and _fused(reg) == 1


@pytest.mark.parametrize("asked", [True, False], ids=["depth", "none"])
def test_the_reprice_crosses_once_each_way(asked):
    """The delta batch reaches the jitted program as one host int32
    array (columns, then the float32 prices' bits) of twice the bucket,
    and the registry counts two crossings a reprice: that operand in,
    the packed result (or the bare handoff count) out."""
    state, reg, ids = _state("jax_batched")
    operands = []
    depths = _spy_depths(state, operands)

    def transfers():
        return reg.snapshot()["counters"]["rank.reprice_transfers"]
    for n, bucket in ((3, 8), (9, 32)):
        if asked:
            state.top_k("all", 2)
        before = transfers()
        batch = {ids[c]: 0.1 * c for c in range(1, n + 1)}
        state.reprice(batch)
        assert transfers() - before == 2
        delta = operands[-1]
        assert type(delta) is np.ndarray and delta.dtype == np.int32
        assert delta.shape == (2 * bucket,)
        np.testing.assert_array_equal(delta[:n], np.arange(1, n + 1))
        np.testing.assert_array_equal(
            delta[bucket:bucket + n].view(np.float32),
            np.float32([0.1 * c for c in range(1, n + 1)]))
    assert len(operands) == 2 and len(depths) == (2 if asked else 0)
    assert _fused(reg) == (2 if asked else 0)


def test_an_ingest_before_the_reprice_lands_in_the_heads_it_takes():
    hours, mask, prices, ids = _universe()
    jobs = [f"j{i}" for i in range(N_JOBS)]
    mask[:, 0] = False
    reg = MetricsRegistry()
    state = BatchedRankState(hours, mask, prices, ids, job_ids=jobs,
                             metrics=reg)
    for key, rows in MEMBERS.items():
        state.add_state(key, rows=rows)
    _assert_heads(state, MEMBERS, 3)
    state.ingest([("j1", ids[0], 1e-3), ("j4", ids[5], 1e-3)])
    hours[1, 0] = hours[4, 5] = 1e-3
    mask[1, 0] = mask[4, 5] = True
    state.reprice({ids[7]: 0.05})
    prices[7] = 0.05
    launched = _launches(reg)[0]
    cold = BatchedRankState(hours, mask, prices, ids, job_ids=jobs)
    for key, rows in MEMBERS.items():
        cold.add_state(key, rows=rows)
        got = state.top_k(key, 3)
        assert [r.config_id for r in got] == \
            [r.config_id for r in cold.top_k(key, 3)]
        assert [r.score for r in got] == pytest.approx(
            [r.score for r in cold.top_k(key, 3)], rel=1e-5)
    assert _fused(reg) == 1 and _launches(reg)[0] == launched


@pytest.mark.parametrize("change", ["add", "retire"])
def test_a_membership_change_after_the_reprice_launches_its_own_top_k(
        change):
    state, reg, ids = _state("jax_batched")
    _assert_heads(state, MEMBERS, 3)
    state.reprice({ids[9]: 0.07})
    assert _fused(reg) == 1
    launched = _launches(reg)[0]
    if change == "add":
        state.add_state("mid", rows=[1, 2, 3])
        keys = list(MEMBERS) + ["mid"]
    else:
        state.retire_state("head")
        keys = ["all", "tail"]
    _assert_heads(state, keys, 3)
    assert _launches(reg)[0] == launched + 1


def test_reprice_returns_the_same_handoffs_with_and_without_a_depth():
    plain, _, ids = _state("jax_batched")
    fused, reg, _ = _state("jax_batched")
    rng = np.random.default_rng(11)
    moved = []
    for tick in range(8):
        fused.top_k("all", 2)
        cols = rng.choice(np.arange(1, N_CFGS), 3, replace=False)
        # every other tick one column drops far below its rows' minima
        new = rng.uniform(0.5, 20.0, 3) if tick % 2 else \
            np.array([1e-3, 5.0, 6.0])
        deltas = {ids[c]: float(p) for c, p in zip(cols, new)}
        a, b = plain.reprice(deltas), fused.reprice(deltas)
        assert a == b
        moved.append(a)
    assert any(moved) and _fused(reg) == 8
    for key in MEMBERS:
        np.testing.assert_allclose(fused.scores(key), plain.scores(key),
                                   rtol=1e-6)
