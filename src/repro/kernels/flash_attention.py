"""Pallas TPU flash-attention kernel (causal / windowed, GQA).

TPU-native adaptation of the flash-attention blocking: one grid program
owns a (batch, head, q-block) tile; K/V stream through VMEM in
``block_kv``-sized slices with an online-softmax accumulator held in VMEM
scratch.  Block shapes are MXU-aligned (q/kv blocks multiples of 128 at
production sizes; the ``interpret=True`` CPU tests also sweep ragged
sizes).  GQA is expressed in the index maps — q heads map onto their
kv-head group, so KV tiles are fetched once per group, not per q head.

The pure-jnp oracle is ``repro.kernels.ref.attention_ref``; the jitted
dispatch wrapper is ``repro.kernels.ops.flash_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
            window: Optional[int], block_kv: int, seq_k: int):
    """One (b, h, iq) tile.  q_ref: (1,1,bq,D); k_ref/v_ref: (1,1,Sk,D)."""
    bq, D = q_ref.shape[2], q_ref.shape[3]
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale

    nkv = seq_k // block_kv
    q0 = iq * bq
    # block range this q tile can see (dynamic fori bounds are fine)
    if causal:
        hi = jnp.minimum((q0 + bq + block_kv - 1) // block_kv, nkv)
    else:
        hi = nkv
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (q0 - window) // block_kv)

    def body(j, carry):
        acc, m, l = carry
        kv_rows = pl.ds(j * block_kv, block_kv)
        k = k_ref[0, 0, kv_rows, :].astype(jnp.float32)
        v = v_ref[0, 0, kv_rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q0 + lax.broadcasted_iota(jnp.int32, (bq, block_kv), 0)
        kpos = j * block_kv + lax.broadcasted_iota(jnp.int32,
                                                   (bq, block_kv), 1)
        mask = jnp.ones((bq, block_kv), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_b = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_b)
        p = jnp.exp(s - m_new[:, None])
        c = jnp.exp(m - m_new)
        l_new = l * c + jnp.sum(p, axis=1)
        acc_new = acc * c[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    out = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    o_ref[...] = out[None, None]


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Tq, H, D); k, v: (B, Tk, G, D); H = G * R.  Returns (B,Tq,H,D).

    Grid: (B, H, Tq/block_q).  KV index maps route q head h to kv head
    h // R (GQA sharing).
    """
    B, Tq, H, D = q.shape
    Tk, G = k.shape[1], k.shape[2]
    R = H // G
    block_q = min(block_q, Tq)
    block_kv = min(block_kv, Tk)
    assert Tq % block_q == 0 and Tk % block_kv == 0, (Tq, Tk)
    scale = 1.0 / math.sqrt(D)

    # layout: put head next to batch so each tile is a contiguous 2D slab
    qt = q.transpose(0, 2, 1, 3)          # (B, H, Tq, D)
    kt = k.transpose(0, 2, 1, 3)          # (B, G, Tk, D)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, Tq // block_q)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, block_kv=block_kv, seq_k=Tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tk, D),
                         lambda b, h, i, R=R: (b, h // R, 0, 0)),
            pl.BlockSpec((1, 1, Tk, D),
                         lambda b, h, i, R=R: (b, h // R, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
