"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Sweeps shapes/dtypes per the kernel contract and asserts allclose against
ref.py; includes the model-side chunked jnp attention as a third
implementation for mutual agreement.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas
from repro.models.layers import sdpa
from repro.models.recurrent import wkv6_scan_ref, wkv6_scan_chunked


def _qkv(key, B, Tq, Tk, H, G, D, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Tq, H, D), dtype)
    k = jax.random.normal(kk, (B, Tk, G, D), dtype)
    v = jax.random.normal(kv, (B, Tk, G, D), dtype)
    return q, k, v


ATTN_CASES = [
    # B, T, H, G, D, causal, window, bq, bkv
    (2, 128, 4, 4, 32, True, None, 32, 32),
    (1, 256, 4, 2, 64, True, None, 64, 64),     # GQA
    (2, 128, 8, 1, 32, True, None, 64, 32),     # MQA
    (1, 128, 2, 2, 32, False, None, 32, 64),    # bidirectional
    (1, 256, 4, 4, 32, True, 64, 64, 32),       # local window
    (1, 64, 2, 2, 128, True, None, 64, 64),     # full head dim
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, T, H, G, D, causal, window, bq, bkv = case
    q, k, v = _qkv(jax.random.PRNGKey(hash(case) % 2**31), B, T, T, H, G, D,
                   dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_kv=bkv, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=atol, rtol=1e-2)


@pytest.mark.parametrize("case", ATTN_CASES[:4])
def test_model_sdpa_matches_ref(case):
    """The model's chunked online-softmax jnp path equals the oracle."""
    B, T, H, G, D, causal, window, bq, bkv = case
    q, k, v = _qkv(jax.random.PRNGKey(7), B, T, T, H, G, D, jnp.float32)
    out = sdpa(q, k, v, causal=causal, window=window, q_chunk=32,
               kv_chunk=32)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5, rtol=1e-3)


WKV_CASES = [
    # B, T, H, N, chunk
    (2, 64, 2, 16, 16),
    (1, 128, 4, 32, 64),
    (2, 32, 1, 64, 32),
    (1, 96, 3, 16, 32),
]


def _wkv_inputs(key, B, T, H, N, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, T, H, N), dtype)
    k = jax.random.normal(ks[1], (B, T, H, N), dtype)
    v = jax.random.normal(ks[2], (B, T, H, N), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N))) * 0.5 + 0.45
    u = (jax.random.normal(ks[4], (H, N)) * 0.5).astype(dtype)
    s0 = jnp.zeros((B, H, N, N), jnp.float32)
    return r, k, v, w.astype(dtype), u, s0


@pytest.mark.parametrize("case", WKV_CASES)
def test_wkv6_pallas_matches_ref(case):
    B, T, H, N, chunk = case
    inputs = _wkv_inputs(jax.random.PRNGKey(sum(case)), B, T, H, N)
    y, sT = wkv6_pallas(*inputs, chunk=chunk, interpret=True)
    y_ref, sT_ref = wkv6_scan_ref(*inputs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("case", WKV_CASES[:2])
def test_wkv6_chunked_matches_ref(case):
    """The model-side chunk-remat scan equals the exact recurrence."""
    B, T, H, N, chunk = case
    inputs = _wkv_inputs(jax.random.PRNGKey(3), B, T, H, N)
    y_c, sT_c = wkv6_scan_chunked(*inputs, chunk=chunk)
    y_ref, sT_ref = wkv6_scan_ref(*inputs)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sT_c), np.asarray(sT_ref),
                               atol=1e-4, rtol=1e-3)


def test_wkv6_state_carry():
    """Splitting a sequence across two kernel calls carries state exactly."""
    B, T, H, N = 1, 64, 2, 16
    r, k, v, w, u, s0 = _wkv_inputs(jax.random.PRNGKey(11), B, T, H, N)
    y_full, sT_full = wkv6_scan_ref(r, k, v, w, u, s0)
    half = T // 2
    y1, s_mid = wkv6_pallas(r[:, :half], k[:, :half], v[:, :half],
                            w[:, :half], u, s0, chunk=16, interpret=True)
    y2, sT = wkv6_pallas(r[:, half:], k[:, half:], v[:, half:],
                         w[:, half:], u, s_mid, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_full),
                               atol=1e-4, rtol=1e-3)


def test_flash_attention_gqa_grouping_property():
    """Repeating kv heads R times and running MHA equals GQA directly."""
    B, T, H, G, D = 1, 64, 4, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(5), B, T, T, H, G, D, jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=32,
                                 block_kv=32, interpret=True)
    k_rep = jnp.repeat(k, H // G, axis=2)
    v_rep = jnp.repeat(v, H // G, axis=2)
    out_mha = flash_attention_pallas(q, k_rep, v_rep, causal=True,
                                     block_q=32, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_mha),
                               atol=1e-5, rtol=1e-4)


# --- fused delta-rank kernel (rank_delta.py) vs numpy oracle ---------------

from repro.kernels import ops
from repro.kernels import rank_delta


def _rank_universe(seed, J=16, C=24, S=5, n_changed=3):
    """A random masked universe mid-stream: settled scores + a delta."""
    rng = np.random.default_rng(seed)
    hours = rng.uniform(0.5, 4.0, (J, C)).astype(np.float32)
    mask = rng.random((J, C)) > 0.2
    hours = np.where(mask, hours, 1.0).astype(np.float32)
    oldp = rng.uniform(0.1, 2.0, (1, C)).astype(np.float32)
    newp = oldp.copy()
    cols = rng.choice(C, size=n_changed, replace=False)
    newp[0, cols] = (newp[0, cols] * rng.uniform(0.4, 1.6, n_changed)
                     ).astype(np.float32)
    changed = np.zeros((1, C), np.float32)
    changed[0, cols] = 1.0
    cost_old = np.where(mask, hours * oldp, np.inf)
    rb_old = cost_old.min(axis=1, keepdims=True).astype(np.float32)
    norm_old = np.where(mask, cost_old / rb_old, 0.0).astype(np.float32)
    rm = (rng.random((S, J)) > 0.4).astype(np.float32)
    scores = (rm @ norm_old).astype(np.float32)
    return hours, mask, oldp, newp, changed, rb_old, rm, scores


def _rank_oracle(hours, mask, oldp, newp, changed, rb_old, rm, scores):
    """The tick's float64-free numpy reference (same float32 exprs)."""
    cost_old = np.where(mask, hours * oldp, np.inf)
    cost_new = np.where(mask, hours * newp, np.inf)
    rb_new = cost_new.min(axis=1, keepdims=True).astype(np.float32)
    norm_old = np.where(mask, cost_old / rb_old, 0.0).astype(np.float32)
    norm_new = np.where(mask, cost_new / rb_new, 0.0).astype(np.float32)
    want = np.where(changed > 0, rm @ norm_new,
                    scores + rm @ (norm_new - norm_old))
    moved = int((rb_new != rb_old).sum())
    return want, rb_new, moved


@pytest.mark.parametrize("blocks", [(16, 24), (8, 24), (4, 12), (8, 8)])
def test_rank_delta_fused_matches_oracle(blocks):
    """The fused kernel == the unfused reference on every tiling,
    including multi-tile C (phase-0 min scan spans tiles)."""
    bj, bc = blocks
    u = _rank_universe(0)
    want, rb_want, moved_want = _rank_oracle(*u)
    s, rb, mv = rank_delta.fused_reprice(*u, block_j=bj, block_c=bc)
    np.testing.assert_allclose(np.asarray(s), want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rb), rb_want)
    assert int(np.asarray(mv)[0, 0]) == moved_want


def test_rank_delta_identity_tick_is_bitwise_noop():
    """An unchanged-price tick reproduces the standing accumulators
    bit-for-bit: the in-stream recompute is deterministic IEEE, so
    norm_new - norm_old is an exact zero everywhere (DESIGN.md §14)."""
    hours, mask, oldp, _, _, rb_old, rm, scores = _rank_universe(1)
    zeros = np.zeros_like(oldp)
    s, rb, mv = rank_delta.fused_reprice(hours, mask, oldp, oldp, zeros,
                                         rb_old, rm, scores,
                                         block_j=8, block_c=24)
    assert np.array_equal(np.asarray(s), scores)
    assert np.array_equal(np.asarray(rb), rb_old)
    assert int(np.asarray(mv)[0, 0]) == 0


def test_rank_delta_fused_heads_matches_sorted_scores():
    """The in-kernel top-k tail == a stable argsort of the finalized
    masked scores (argmin first-occurrence == catalog-order ties)."""
    u = _rank_universe(2)
    hours, mask, oldp, newp, changed, rb_old, rm, scores = u
    want, _, _ = _rank_oracle(*u)
    fin = (rm @ mask.astype(np.float32)) > 0
    k = 4
    s, rb, mv, ti, tv = rank_delta.fused_reprice_heads(
        hours, mask, oldp, newp, changed, rb_old, rm, scores, fin,
        block_j=8, block_c=24, k=k)
    np.testing.assert_allclose(np.asarray(s), want, rtol=1e-4, atol=1e-6)
    masked = np.where(fin, want, np.inf)
    ti_want = np.argsort(masked, axis=1, kind="stable")[:, :k]
    assert np.array_equal(np.asarray(ti), ti_want)
    np.testing.assert_allclose(np.asarray(tv),
                               np.take_along_axis(masked, ti_want, 1),
                               rtol=1e-4, atol=1e-6)


def test_rank_delta_heads_needs_single_c_tile():
    u = _rank_universe(3)
    fin = np.ones((u[6].shape[0], u[0].shape[1]), bool)
    with pytest.raises(ValueError, match="block_c"):
        rank_delta.fused_reprice_heads(*u, fin, block_j=8, block_c=12,
                                       k=2)


def test_rank_delta_rejects_nondividing_blocks():
    u = _rank_universe(4)
    with pytest.raises(ValueError, match="block_j"):
        rank_delta.fused_reprice(*u, block_j=5, block_c=24)
    with pytest.raises(ValueError, match="block_c"):
        rank_delta.fused_reprice(*u, block_j=8, block_c=7)


# --- regression: interpret is resolved at call time, outside the trace -----

def test_interpret_flag_not_baked_into_jit_cache(monkeypatch):
    """``_interpret()`` flipping between calls must re-trace, not
    replay: pre-fix the flag was read INSIDE the traced function, so
    the second call replayed the first call's flag from the jit cache
    (keyed only on shapes/other statics) and the spy fired once."""
    traced = []

    def spy(q, k, v, **kw):
        traced.append(kw["interpret"])
        return q

    monkeypatch.setattr(ops, "flash_attention_pallas", spy)
    # odd head dim -> a fresh jit cache entry for this test alone
    q = jnp.zeros((1, 8, 2, 17), jnp.float32)
    monkeypatch.setattr(ops, "_interpret", lambda: True)
    ops.flash_attention(q, q, q)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    ops.flash_attention(q, q, q)
    assert traced == [True, False]


def test_interpret_flag_wkv6_and_rank_delta_accept_explicit(monkeypatch):
    """The explicit ``interpret=`` override is a static arg on every
    kernel wrapper: distinct values produce distinct traces."""
    traced = []

    def spy(r, k, v, w, u, s0, **kw):
        traced.append(kw["interpret"])
        return v, s0

    monkeypatch.setattr(ops, "wkv6_pallas", spy)
    r = jnp.zeros((1, 4, 1, 19), jnp.float32)
    u = jnp.zeros((1, 19), jnp.float32)
    s0 = jnp.zeros((1, 1, 19, 19), jnp.float32)
    ops.wkv6(r, r, r, r, u, s0, interpret=True)
    ops.wkv6(r, r, r, r, u, s0, interpret=False)
    assert traced == [True, False]
    # the rank_delta dispatch resolves the default the same way: its
    # jitted fns declare interpret static (a flip re-traces, never
    # replays)
    import inspect
    sig = inspect.signature(rank_delta._reprice)
    assert "interpret" in sig.parameters


# --- regression: use_pallas is a thread-safe context manager ---------------

def test_use_pallas_context_manager_restores_prior():
    """Pre-fix ``use_pallas`` returned None, so the context-manager
    form raised AttributeError and tests had to flip the raw global."""
    assert ops._FORCE_PALLAS is False
    with ops.use_pallas():
        assert ops.pallas_enabled()
        with ops.use_pallas(False):
            assert ops._FORCE_PALLAS is False
        assert ops._FORCE_PALLAS is True
    assert ops._FORCE_PALLAS is False
    # restores on the exception path too
    with pytest.raises(RuntimeError):
        with ops.use_pallas():
            raise RuntimeError("boom")
    assert ops._FORCE_PALLAS is False


def test_use_pallas_concurrent_toggles_settle_clean():
    """N threads bouncing the toggle through the context manager leave
    the flag exactly where it started (the lock serializes the
    read-modify-write the bare global raced on)."""
    import threading

    n = 16
    barrier = threading.Barrier(n)

    def worker():
        barrier.wait()
        for _ in range(50):
            with ops.use_pallas():
                pass

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ops._FORCE_PALLAS is False


# --- regression: lazy jitted singletons build exactly once -----------------

def _stress_first_call(monkeypatch, reset, getter, expected_jits):
    """Race ``n`` threads into a cold ``getter`` with a slowed
    ``jax.jit``: pre-fix (no lock) several threads pass the None check
    together and the build runs more than once."""
    import threading
    import time

    reset(monkeypatch)
    real_jit = jax.jit
    jits = []

    def slow_jit(*a, **kw):
        jits.append(1)
        time.sleep(0.02)
        return real_jit(*a, **kw)

    monkeypatch.setattr(jax, "jit", slow_jit)
    n = 8
    barrier = threading.Barrier(n)
    results = []

    def worker():
        barrier.wait()
        results.append(getter())

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    monkeypatch.setattr(jax, "jit", real_jit)
    assert len(jits) == expected_jits
    assert all(r is results[0] for r in results)


def test_jax_state_fns_first_call_races_build_once(monkeypatch):
    """The fleet's cold program, the first thing every fleet state
    builds."""
    from repro.selector import rank

    _stress_first_call(
        monkeypatch,
        lambda mp: mp.setattr(rank, "_JAX_COLD_FN", None),
        rank._jax_cold_fn, expected_jits=1)


def test_jax_topk_fn_first_call_races_build_once(monkeypatch):
    from repro.selector import rank

    _stress_first_call(
        monkeypatch,
        lambda mp: mp.setattr(rank, "_JAX_TOPK_FN", None),
        rank._jax_topk_fn, expected_jits=1)


def test_jax_batched_fns_first_call_races_build_once(monkeypatch):
    from repro.selector import rank

    _stress_first_call(
        monkeypatch,
        lambda mp: mp.setattr(rank, "_JAX_BATCHED_FNS", None),
        rank._jax_batched_fns, expected_jits=3)


def test_rank_delta_fns_first_call_races_build_once(monkeypatch):
    _stress_first_call(
        monkeypatch,
        lambda mp: mp.setattr(rank_delta, "_RANK_DELTA_FNS", None),
        rank_delta.rank_delta_fns, expected_jits=2)
