"""Fused Pallas delta-rank reprice kernel (the ``jax_pallas`` backend).

The XLA delta step (:func:`repro.selector.rank._delta_universe_update`
plus the batched score fold) is ~5 streamed passes over the (J x C)
universe per tick: gather/scatter the changed cost columns, a full
``cost.min(axis=1)``, a full renormalization, and two matmuls over
J x C operands — each materializing an intermediate in HBM between XLA
fusions.  This module fuses the whole tick into **one**
``pl.pallas_call`` over the (S x J x C)-tiled universe:

* **changed-column score re-reduction** — every member's score on a
  changed column is re-reduced from scratch (``P = row_masks @
  norm_new`` restricted to changed columns), the ``.set`` semantics the
  ScoreContract's drift story depends on;
* **masked row-min handoff detection** — the fresh masked row minimum
  falls out of the same streamed tiles (see below), and the handoff
  count (#rows whose minimum moved) is counted from the old and new
  row minima in the same jitted call;
* **accumulator score updates** — unchanged columns fold
  ``D = row_masks @ (norm_new - norm_old)`` into the standing
  accumulators; rows whose minimum did not move contribute *exact*
  zeros (see the recompute identity below), so a no-handoff tick is
  drift-free, exactly like the XLA path.

**Why the handoff-row min needs no second pass over universe state**
(DESIGN.md §14): the kernel keeps *no* resident cost or norm matrix.
Both are recomputed in-stream from the read-only ``hours``/``mask``
residents and the price vectors — float32 elementwise multiply and
divide are deterministic IEEE ops, so an unchanged column's
recomputed cost is bit-identical to what a stored matrix would hold,
and ``norm_new - norm_old`` is an exact ``0.0`` wherever nothing
moved.  The fresh row minimum is therefore a byproduct of the same
tile stream (phase 0 of the grid), not a second pass over a
delta-patched cost matrix; resident per-tick state shrinks to the
price vector, the row minima and the score accumulators.

**Tiling.**  The grid is ``(2, C//block_c, J//block_j)``: phase 0
sweeps the tiles accumulating the masked row minima of the *new* cost
into the resident ``(J, 1)`` row-minima output block; phase 1
recomputes both norms per tile and accumulates the two member matmuls
(``S x block_j @ block_j x block_c``, at float32 precision).  The j
axis is innermost so each ``(S, block_c)`` output block sees its
accumulation visits consecutively (the Pallas revisiting rule).  The
member axis S rides whole in every block.  On the chip every block's
last two dimensions must be multiples of (8, 128) or the whole array's
(DESIGN.md §14), which is why the row masks travel as
``(J/block_j, S, block_j)``.

Like the other kernels in this package the Pallas body runs natively on
TPU and under ``interpret=True`` on CPU; ``interpret`` is a *static*
argument resolved at call time (never baked into a jit trace — the
regression the ops.py wrappers fixed).  The lazy jitted dispatch is
built under a lock: the serving front-end first-calls from N worker
threads plus the tick thread concurrently.
"""
from __future__ import annotations

import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _interpret

__all__ = ["VMEM_LIMIT_BYTES", "fused_reprice", "fused_reprice_heads",
           "fused_vmem_bytes", "rank_delta_fns"]

#: the scoped VMEM a Mosaic kernel may hold on a TPU v5e by default
VMEM_LIMIT_BYTES = 16 * 2**20


def fused_vmem_bytes(members: int, jobs: int, block_c: int,
                     heads: bool) -> int:
    """Estimated VMEM of one fused call at 8-row job tiles, in bytes.

    Per 128-lane column group of a ``block_c`` tile the call holds about
    4 float32 rows per member (the member scores in, out and in scratch)
    and 28 rows of job-tile operands and temporaries; with the top-k
    tail, 9 rows per member and 12.  The ``(J, 1)`` row minima (in and
    out) and the ``(S, 8)`` row-mask block pad every row to 128 lanes.
    The coefficients are fitted to 74 verdicts of the v5e compiler on
    chip-less compiles (S = 8..128, C = 1,000..80,000, J = 64 and
    1,024); they reproduce every one, each at least 1.8 MiB from the
    limit."""
    lanes = -(-block_c // 128) * 128
    sublanes = -(-members // 8) * 8
    rows = 9 * sublanes + 12 if heads else 4 * sublanes + 28
    return 4 * (rows * lanes + 128 * (2 * jobs + 2 * sublanes))


def _make_kernel(block_j: int, n_j_tiles: int, heads: Optional[int]):
    """The fused kernel body; ``heads=k`` adds the in-kernel top-k tail
    (requires a single C tile — the final scores must be resident)."""

    def kernel(hours_ref, mask_ref, oldp_ref, newp_ref, chg_ref,
               rb_in_ref, rm_ref, scores_in_ref, *refs):
        if heads is None:
            scores_out_ref, rb_out_ref, p_acc = refs
        else:
            fin_ref, scores_out_ref, rb_out_ref, ti_ref, tv_ref, p_acc = refs
        p = pl.program_id(0)
        c = pl.program_id(1)
        j = pl.program_id(2)
        jsl = pl.ds(pl.multiple_of(j * block_j, block_j), block_j)
        hours = hours_ref[...]                        # (Jt, Ct)
        mask = mask_ref[...]
        # the new cost tile, recomputed in-stream: unchanged columns
        # reproduce the old cost bit-for-bit (deterministic IEEE mul),
        # so no resident cost matrix — and no second pass over one —
        # is needed to find the fresh masked row minima
        cost_new = jnp.where(mask, hours * newp_ref[...], jnp.inf)

        @pl.when(p == 0)
        def _min_scan():
            # phase 0: running masked row minima across the C tiles,
            # kept in the resident row_best output block
            tile_min = jnp.min(cost_new, axis=1, keepdims=True)

            @pl.when(c == 0)
            def _():
                rb_out_ref[jsl, :] = tile_min

            @pl.when(c > 0)
            def _():
                rb_out_ref[jsl, :] = jnp.minimum(rb_out_ref[jsl, :],
                                                 tile_min)

        @pl.when(p == 1)
        def _fold():
            # phase 1: both norms recomputed per tile, two member
            # matmuls accumulated — the row minima are final (phase 0
            # swept every tile before phase 1 starts)
            cost_old = jnp.where(mask, hours * oldp_ref[...], jnp.inf)
            norm_old = jnp.where(mask, cost_old / rb_in_ref[jsl, :], 0.0)
            norm_new = jnp.where(mask, cost_new / rb_out_ref[jsl, :], 0.0)
            rm = rm_ref[0]                            # (S, Jt)
            dims = (((1,), (0,)), ((), ()))
            re_reduce = jax.lax.dot_general(
                rm, norm_new, dims, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            delta = jax.lax.dot_general(
                rm, norm_new - norm_old, dims,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

            @pl.when(j == 0)
            def _():
                scores_out_ref[...] = delta
                p_acc[...] = re_reduce

            @pl.when(j > 0)
            def _():
                scores_out_ref[...] += delta
                p_acc[...] += re_reduce

            @pl.when(j == n_j_tiles - 1)
            def _combine():
                # changed columns: re-set from the scratch re-reduction;
                # unchanged: fold the (exact-zero-for-unmoved-rows)
                # delta into the standing accumulators
                chg = chg_ref[...] > 0                # (1, Ct)
                scores = jnp.where(chg, p_acc[...],
                                   scores_in_ref[...] + scores_out_ref[...])
                scores_out_ref[...] = scores
                if heads is not None:
                    ti_ref[...], tv_ref[...] = _heads(
                        scores, fin_ref[...], heads)

    return kernel


def _heads(scores, finite, k: int):
    """The fused top-k tail: k rounds of a masked row minimum over the
    just-finalized resident scores.  Each round takes the lowest column
    holding the minimum among the columns not yet taken — the catalog-
    order tie-break of ``_materialize`` and of ``lax.top_k``, unprofiled
    (+inf) columns included.  Column positions ride as float32 (exact
    below 2**24) so every reduction is a float lane minimum."""
    S, C = scores.shape
    masked = jnp.where(finite, scores, jnp.inf)
    cols = jax.lax.broadcasted_iota(jnp.int32, (S, C), 1).astype(
        jnp.float32)
    slot = jax.lax.broadcasted_iota(jnp.int32, (S, k), 1)
    taken = jnp.zeros((S, C), jnp.bool_)
    ti = jnp.zeros((S, k), jnp.int32)
    tv = jnp.zeros((S, k), jnp.float32)
    for t in range(k):
        live = jnp.where(taken, jnp.inf, masked)
        best = jnp.min(live, axis=1, keepdims=True)           # (S, 1)
        first = jnp.min(jnp.where((live == best) & ~taken, cols, C),
                        axis=1, keepdims=True)                 # (S, 1)
        ti = jnp.where(slot == t, first.astype(jnp.int32), ti)
        tv = jnp.where(slot == t, best, tv)
        taken = taken | (cols == first)
    return ti, tv


def _check_tiling(shape_j: int, shape_c: int, block_j: int,
                  block_c: int) -> Tuple[int, int]:
    if block_j < 1 or shape_j % block_j:
        raise ValueError(f"block_j={block_j} must divide the (padded) "
                         f"job axis {shape_j}")
    if block_c < 1 or shape_c % block_c:
        raise ValueError(f"block_c={block_c} must divide the config "
                         f"axis {shape_c}")
    return shape_j // block_j, shape_c // block_c


def _fused_call(hours, mask, old_prices, new_prices, changed, row_best,
                row_masks, scores, finite, *, block_j, block_c, heads,
                interpret):
    """Build and invoke the single fused ``pallas_call`` for one tick."""
    J, C = hours.shape
    S = row_masks.shape[0]
    nj, nc = _check_tiling(J, C, block_j, block_c)
    if heads is not None and nc != 1:
        raise ValueError("the fused reprice+top-k variant needs the "
                         "final scores resident: use block_c == C")
    kernel = _make_kernel(block_j, nj, heads)
    vec = lambda p, c, j: (0, c)                     # (1, Ct) vectors
    tile = lambda p, c, j: (j, c)                    # (Jt, Ct) tiles
    whole = lambda p, c, j: (0, 0)                   # resident blocks
    # the row masks as (J/Jt, S, Jt): each job tile's (S, Jt) slab is a
    # whole trailing pair of dimensions, as the chip's (8, 128) block
    # rule requires of a Jt narrower than 128 lanes
    rm_tiles = row_masks.reshape(S, nj, block_j).transpose(1, 0, 2)
    in_specs = [
        pl.BlockSpec((block_j, block_c), tile),      # hours
        pl.BlockSpec((block_j, block_c), tile),      # mask
        pl.BlockSpec((1, block_c), vec),             # old prices
        pl.BlockSpec((1, block_c), vec),             # new prices
        pl.BlockSpec((1, block_c), vec),             # changed columns
        pl.BlockSpec((J, 1), whole),                 # row_best in
        pl.BlockSpec((1, S, block_j),
                     lambda p, c, j: (j, 0, 0)),     # row masks
        pl.BlockSpec((S, block_c), vec),             # scores in
    ]
    args = [hours, mask, old_prices, new_prices, changed, row_best,
            rm_tiles, scores]
    out_specs = [
        pl.BlockSpec((S, block_c), vec),             # scores out
        pl.BlockSpec((J, 1), whole),                 # row_best out
    ]
    out_shape = [
        jax.ShapeDtypeStruct((S, C), jnp.float32),
        jax.ShapeDtypeStruct((J, 1), jnp.float32),
    ]
    if heads is not None:
        in_specs.append(pl.BlockSpec((S, block_c), vec))  # finite
        args.append(finite)
        out_specs += [pl.BlockSpec((S, heads), whole),
                      pl.BlockSpec((S, heads), whole)]
        out_shape += [jax.ShapeDtypeStruct((S, heads), jnp.int32),
                      jax.ShapeDtypeStruct((S, heads), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(2, nc, nj),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((S, block_c), jnp.float32)],
        interpret=interpret,
    )(*args)
    # the handoff count: rows whose masked minimum moved (an all-+inf
    # padded row compares equal to itself and never counts)
    moved = jnp.sum(out[1] != row_best, dtype=jnp.int32).reshape(1, 1)
    return (out[0], out[1], moved, *out[2:])


def _reprice(hours, mask, old_prices, new_prices, changed, row_best,
             row_masks, scores, *, block_j, block_c, interpret):
    return _fused_call(hours, mask, old_prices, new_prices, changed,
                       row_best, row_masks, scores, None,
                       block_j=block_j, block_c=block_c, heads=None,
                       interpret=interpret)


def _reprice_heads(hours, mask, old_prices, new_prices, changed,
                   row_best, row_masks, scores, finite, *, block_j,
                   block_c, k, interpret):
    return _fused_call(hours, mask, old_prices, new_prices, changed,
                       row_best, row_masks, scores, finite,
                       block_j=block_j, block_c=block_c, heads=k,
                       interpret=interpret)


# the lazy jitted dispatch, built once under a lock (double-checked):
# the serving front-end first-calls from N snapshot workers plus the
# tick thread concurrently, the same hazard the rank.py singletons fix
_RANK_DELTA_FNS: Optional[Tuple[Any, Any]] = None
_RANK_DELTA_LOCK = threading.Lock()


def rank_delta_fns() -> Tuple[Any, Any]:
    """``(reprice, reprice_heads)`` jitted fused kernels, built once on
    first use (importing the package never initializes a backend).
    ``interpret`` is a static jit argument — callers resolve it at call
    time, so a backend change re-traces instead of replaying a stale
    flag from the jit cache."""
    global _RANK_DELTA_FNS
    if _RANK_DELTA_FNS is None:
        with _RANK_DELTA_LOCK:
            if _RANK_DELTA_FNS is None:
                _RANK_DELTA_FNS = (
                    jax.jit(_reprice,
                            static_argnames=("block_j", "block_c",
                                             "interpret")),
                    jax.jit(_reprice_heads,
                            static_argnames=("block_j", "block_c", "k",
                                             "interpret")),
                )
    return _RANK_DELTA_FNS


def fused_reprice(hours, mask, old_prices, new_prices, changed,
                  row_best, row_masks, scores, *, block_j: int,
                  block_c: int, interpret: Optional[bool] = None):
    """One fused tick: ``(scores, row_best, moved)`` from the streamed
    universe.  ``interpret=None`` resolves from the current default
    backend at call time (interpreted everywhere but TPU)."""
    if interpret is None:
        interpret = _interpret()
    return rank_delta_fns()[0](
        hours, mask, old_prices, new_prices, changed, row_best,
        row_masks, scores, block_j=block_j, block_c=block_c,
        interpret=interpret)


def fused_reprice_heads(hours, mask, old_prices, new_prices, changed,
                        row_best, row_masks, scores, finite, *,
                        block_j: int, block_c: int, k: int,
                        interpret: Optional[bool] = None):
    """The fused reprice+top-k variant: additionally returns every
    member's k best ``(indices, values)`` computed in-kernel from the
    just-finalized scores (single C tile only)."""
    if interpret is None:
        interpret = _interpret()
    return rank_delta_fns()[1](
        hours, mask, old_prices, new_prices, changed, row_best,
        row_masks, scores, finite, block_j=block_j, block_c=block_c,
        k=k, interpret=interpret)
