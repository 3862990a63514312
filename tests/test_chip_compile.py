"""The main-path Pallas kernels compile for a TPU v5e chip.

The chip's compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology and refuses what the chip
would refuse (block shapes off the (8, 128) tiling, VMEM overruns,
unsupported vector ops), none of which interpret mode can show.  Each
test asserts that the program holds a Mosaic kernel
(``tpu_custom_call``), i.e. that nothing fell back to the interpreter.

The topology is described inside module-scoped fixtures, never while the
module is imported: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels import rank_delta
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a chip-less compile is written to the persistent cache but cannot
    # be read back without a chip: keep the cache out of these compiles
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(one_chip):
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


#: the smoke's fleet: 64 jobs x 10,000 configs, 8 member slots, 5-heads
J, C, S, K = 64, 10_000, 8, 5


def _lower_fused(shape, variant, members):
    """The fused tick at the state's default tiling (8-row job tiles, the
    whole catalog per tile) over ``members`` slots."""
    f32 = jnp.float32
    args = (shape((J, C), f32), shape((J, C), jnp.bool_),
            shape((1, C), f32), shape((1, C), f32), shape((1, C), f32),
            shape((J, 1), f32), shape((members, J), f32),
            shape((members, C), f32))
    tiles = dict(block_j=8, block_c=C, interpret=False)
    reprice, heads = rank_delta.rank_delta_fns()
    if variant == "reprice":
        return reprice.lower(*args, **tiles)
    return heads.lower(*args, shape((members, C), jnp.bool_), k=K, **tiles)


@pytest.mark.parametrize("variant", ["reprice", "reprice_heads"])
def test_fused_reprice_compiles_for_v5e(shape, variant):
    _assert_kernel(_lower_fused(shape, variant, S).compile())


@pytest.mark.parametrize("members", [16, 32, 64])
@pytest.mark.parametrize("variant", ["reprice", "reprice_heads"])
def test_fused_reprice_vmem_verdict_at_grown_capacity(shape, variant,
                                                      members):
    """A fleet doubles its member slots from 8 as it grows.  At each
    capacity it reaches, the state's VMEM estimate and the chip's
    compiler agree: what the estimate admits compiles to a Mosaic
    kernel, and what it refuses the compiler refuses too."""
    need = rank_delta.fused_vmem_bytes(members, J, C,
                                       heads=variant == "reprice_heads")
    lowered = _lower_fused(shape, variant, members)
    if need <= rank_delta.VMEM_LIMIT_BYTES:
        _assert_kernel(lowered.compile())
    else:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()


def test_flash_attention_compiles_for_v5e_at_qwen3_widths(shape):
    cfg = get("qwen3-1.7b")
    T, D = 4096, cfg.head_dim
    q = shape((1, T, cfg.num_heads, D), jnp.bfloat16)
    kv = shape((1, T, cfg.num_kv_heads, D), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention_pallas(q, k, v,
                                                        causal=True))
    _assert_kernel(fn.lower(q, kv, kv).compile())


def test_wkv6_compiles_for_v5e_at_rwkv6_3b_widths(shape):
    cfg = get("rwkv6-3b")
    H, N, T = cfg.num_heads, cfg.rwkv_head_dim, 4096
    x = shape((1, T, H, N), jnp.bfloat16)
    fn = jax.jit(lambda r, k, v, w, u, s0: wkv6_pallas(r, k, v, w, u, s0))
    lowered = fn.lower(x, x, x, x, shape((H, N), jnp.bfloat16),
                       shape((1, H, N, N), jnp.float32))
    _assert_kernel(lowered.compile())


@pytest.mark.parametrize("backend", ["jax_batched", "jax_pallas"])
def test_profile_ingest_step_compiles_for_v5e(shape, backend):
    """The fleet's ingest step (XLA, no Pallas kernel) at the benchmark's
    profiling deployment: 18 jobs x 15,360 configurations, 16 member
    slots, one record's 80 cells in a 128-cell bucket, one touched row."""
    from repro.selector import rank
    from repro.selector.pallas_rank import _helper_fns
    f32, i32 = jnp.float32, jnp.int32
    j, c, s = 18, 15_360, 16
    cells = (shape((2 * 128 + 1,), i32), shape((128 + 1,), f32))
    if backend == "jax_batched":
        args = (shape((j, c), f32), shape((j, c), jnp.bool_),
                shape((j, c), f32), shape((j,), f32), shape((j, c), f32),
                shape((s, c), f32), shape((s, c), jnp.bool_),
                shape((c,), f32), shape((s, j), f32)) + cells
        step = rank._jax_ingest_fn()
    else:
        jp = 24                                 # padded to 8-row tiles
        args = (shape((jp, c), f32), shape((jp, c), jnp.bool_),
                shape((jp, 1), f32), shape((s, c), f32),
                shape((s, c), jnp.bool_), shape((1, c), f32),
                shape((s, jp), f32)) + cells
        step = _helper_fns()[2]
    compiled = step.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_fleet_reprice_with_heads_compiles_for_v5e(shape):
    """The XLA fleet's reprice+top-k program (no Pallas kernel) at the
    benchmark's saturate deployment: 18 jobs x 15,360 configurations,
    16 member slots, the 512-column bucket of a 1% re-quote packed into
    one int32 operand, 3-deep heads packed with the handoff count into
    one int32 result."""
    from repro.selector import rank
    f32 = jnp.float32
    j, c, s, b, k = 18, 15_360, 16, 512, 3
    args = (shape((c,), f32), shape((j, c), f32), shape((j,), f32),
            shape((j, c), f32), shape((s, c), f32), shape((j, c), f32),
            shape((j, c), jnp.bool_), shape((s, j), f32),
            shape((2 * b,), jnp.int32), shape((s, c), jnp.bool_))
    lowered = rank._jax_batched_fns()[1].lower(*args, k)
    out = lowered.out_info[-1]
    assert out.shape == (1 + 2 * s * k,) and out.dtype == jnp.int32
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
