"""Pallas TPU kernel for the RWKV-6 (Finch) WKV recurrence.

TPU adaptation: the recurrence is O(1)-state sequential in T, so the grid
parallelises over (batch, head) and streams each time series through
VMEM in ``chunk``-row blocks (the innermost, sequential grid axis) while
the (N, N) state matrix stays resident in VMEM scratch — the same
structure Mamba/linear-attention TPU kernels use.  Operands are laid out
(B, H, T, N), as the flash kernel lays out its heads, so every block's
last two dimensions are a (chunk, N) slab.  The T-loop body is pure VPU
elementwise work + rank-1 updates.

    y_t = r_t^T (s_{t-1} + (u * k_t) outer v_t)
    s_t = diag(w_t) s_{t-1} + k_t outer v_t

Oracle: repro.models.recurrent.wkv6_scan_ref (re-exported in ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ROWS = 8   # time steps per aligned f32 tile (the sublane height)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
            s_scr, x_scr, y_scr, *, chunk: int):
    """One (b, h, time-chunk) program.  r/k/v/w/y refs: (1, 1, chunk, N);
    u: (1, 1, N); s0/sT: (1, 1, N, N); s_scr: the (N, N) running state,
    rows = k index, cols = v index; x_scr/y_scr: the chunk's inputs and
    outputs in float32."""
    c = pl.program_id(2)
    N = r_ref.shape[3]

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    eye = (lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == lax.broadcasted_iota(jnp.int32, (N, N), 1))
    tile_row = lax.broadcasted_iota(jnp.int32, (_ROWS, N), 0)

    def col(row):
        # (1, N) row -> (N, 1) column through a diagonal mask and a lane
        # reduction (exact: every other term is 0.0)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    u = col(u_ref[0].astype(jnp.float32))
    for i, ref in enumerate((r_ref, k_ref, v_ref, w_ref)):
        x_scr[i] = ref[0, 0].astype(jnp.float32)

    def tile(g, s):
        # one aligned (8, N) tile of time steps per iteration; the steps
        # inside it are unrolled over static row slices
        rows = pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)
        r, k, v, w = (x_scr[i, rows, :] for i in range(4))
        ys = jnp.zeros((_ROWS, N), jnp.float32)
        for t in range(_ROWS):
            kv = col(k[t:t + 1]) * v[t:t + 1]            # (N, N)
            y = jnp.sum((s + u * kv) * col(r[t:t + 1]), axis=0,
                        keepdims=True)
            ys = jnp.where(tile_row == t, y, ys)
            s = col(w[t:t + 1]) * s + kv
        y_scr[rows, :] = ys
        return s

    s = lax.fori_loop(0, chunk // _ROWS, tile, s_scr[...])
    s_scr[...] = s
    y_ref[0, 0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        sT_ref[0, 0] = s.astype(sT_ref.dtype)


def wkv6_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, s0: jax.Array, *, chunk: int = 64,
                interpret: bool = False):
    """r,k,v,w: (B,T,H,N); u: (H,N); s0: (B,H,N,N) -> (y, s_T)."""
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0 and chunk % _ROWS == 0, (T, chunk)
    # layout: put head next to batch so each block is a (chunk, N) slab
    r, k, v, w = (x.transpose(0, 2, 1, 3) for x in (r, k, v, w))
    io_spec = pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0))
    state_spec = pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0))
    y, sT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B, H, T // chunk),
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0)),
                  state_spec],
        out_specs=[io_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
                   jax.ShapeDtypeStruct((B, H, N, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32),
                        pltpu.VMEM((4, chunk, N), jnp.float32),
                        pltpu.VMEM((chunk, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, N), s0)
    return y.transpose(0, 2, 1, 3), sT
