"""Int8 error-feedback gradient compression for the DP all-reduce.

Distributed-optimization trick for bandwidth-bound scale-out: gradients are
quantised to int8 with a per-tensor scale before the data-parallel
reduction, and the quantisation residual is fed back into the next step
(error feedback preserves convergence; Karimireddy et al., 2019).

Under pjit the all-reduce is implicit (XLA inserts it where gradients
combine), so the compression point is expressed with shard_map: gradients
are quantised per shard, all-reduced in int32 across the "data"/"pod"
axes, and rescaled.  ``compressed_psum_grads`` is the shard_map version
used when a mesh is active; ``ErrorFeedback`` carries the residual state
and works in single-process tests too.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantise_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantisation.  Returns (q, scale)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantise(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


@dataclasses.dataclass
class ErrorFeedback:
    """Residual state + compress step (pure; state is a grad-shaped tree)."""

    def init(self, grads_template: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads_template)

    def compress(self, grads: Any, residual: Any) -> Tuple[Any, Any]:
        """Quantise (grads + residual); return (dequantised, new residual)."""
        def leaf(g, r):
            x = g.astype(jnp.float32) + r
            q, s = quantise_int8(x)
            deq = dequantise(q, s)
            return deq.astype(g.dtype), x - deq
        out = jax.tree_util.tree_map(leaf, grads, residual)
        deq = jax.tree_util.tree_map(lambda t: t[0], out,
                                     is_leaf=lambda x: isinstance(x, tuple))
        res = jax.tree_util.tree_map(lambda t: t[1], out,
                                     is_leaf=lambda x: isinstance(x, tuple))
        return deq, res


def compressed_psum(x: jax.Array, axis_names) -> jax.Array:
    """Quantise-then-psum: int8 payload on the wire, f32 result.

    Per-shard scales are reduced with a max so the dequantisation is
    consistent; payload = int8 tensor + one f32 scalar.
    """
    q, scale = quantise_int8(x)
    scale = jax.lax.pmax(scale, axis_names)
    q32 = jax.lax.psum(q.astype(jnp.int32), axis_names)
    return q32.astype(jnp.float32) * scale


def make_compressed_allreduce(mesh: Mesh, axis_names=("data",)):
    """shard_map'd gradient all-reduce with int8 payload.

    Gradients arrive sharded over the model axis (TP) and replicated over
    data after jax's grad; in the compressed variant the train step keeps
    per-data-shard partial gradients (microbatch split) and reduces them
    here explicitly.
    """
    def allreduce(grads_tree):
        def per_shard(*leaves_in):
            return tuple(compressed_psum(l, axis_names) for l in leaves_in)

        leaves, treedef = jax.tree_util.tree_flatten(grads_tree)
        specs = tuple(P() for _ in leaves)   # replicated view per leaf
        fn = jax.shard_map(per_shard, mesh=mesh, in_specs=specs,
                           out_specs=specs, check_vma=False)
        out = fn(*leaves)
        return jax.tree_util.tree_unflatten(treedef, list(out))
    return allreduce
