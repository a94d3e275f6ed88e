"""Compile the serve path's Pallas kernels for a TPU v5e that is described,
not attached, at phi3-mini widths (32 q/kv heads of width 96, vocab 32064,
8 slots, 2048 positions).

Interpret mode on the CPU cannot see what the chip's compiler refuses:
blocks that break the (8, 128) tiling rule, more VMEM or SMEM than a
kernel may use, casts Mosaic does not lower.  Each test lowers the
kernel with ``interpret=False`` and checks that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs, so these
say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cache_sim, decode_attention, paged_attention
from repro.kernels import sampling

SLOTS, HEADS, KV_HEADS, HEAD_DIM = 8, 32, 32, 96
MAX_LEN, VOCAB, PAGE = 2048, 32064, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip; keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _dense(fused):
    bf = jnp.bfloat16
    cache = (SLOTS, MAX_LEN, KV_HEADS, HEAD_DIM)
    rows = [((SLOTS, KV_HEADS, HEAD_DIM), bf)] * 2 if fused else []
    return [((SLOTS, HEADS, HEAD_DIM), bf), (cache, bf), (cache, bf),
            *rows, ((SLOTS,), jnp.int32), ((), jnp.int32)]


def _paged(fused):
    bf = jnp.bfloat16
    nb = MAX_LEN // PAGE
    pool = (SLOTS * nb + 1, PAGE, KV_HEADS, HEAD_DIM)
    rows = [((SLOTS, KV_HEADS, HEAD_DIM), bf)] * 2 if fused else []
    return [((SLOTS, HEADS, HEAD_DIM), bf), (pool, bf), (pool, bf), *rows,
            ((SLOTS, nb), jnp.int32), ((SLOTS,), jnp.int32),
            ((), jnp.int32)]


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_decode_attention_compiles(one_chip, fused):
    fn = (decode_attention.decode_attention_fused if fused
          else decode_attention.decode_attention)
    _compile(fn, one_chip, *_dense(fused))


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_paged_decode_attention_compiles(one_chip, fused):
    fn = (paged_attention.paged_decode_attention_fused if fused
          else paged_attention.paged_decode_attention)
    _compile(fn, one_chip, *_paged(fused))


def test_fused_sample_compiles_at_unaligned_vocab(one_chip):
    _compile(sampling.fused_sample, one_chip, ((SLOTS, VOCAB), jnp.float32),
             ((SLOTS,), jnp.float32), ((2,), jnp.uint32))


def test_cache_sim_ladder_compiles_for_long_traces(one_chip):
    """A million-access trace is four times what SMEM could hold whole."""
    _compile(lambda t: cache_sim.cache_sim_ladder(
        t, (64, 181, 512, 1448, 4096), ways=16), one_chip,
        ((4, 1 << 20), jnp.int32))
