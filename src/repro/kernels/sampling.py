"""Pallas TPU fused token-sampling kernel for the serve decode epilogue.

The engine's two-step sampler (``jnp.argmax`` + ``jax.random.categorical``
+ ``jnp.where`` over temperature) round-trips the full ``(B, V)`` logit
tensor through three separate XLA ops per tick.  This kernel folds the
whole per-row sample into one launch blocked over the vocab:

  grid (row blocks, nv), j innermost (sequential, carries scratch);
  per block: running per-row (max, first-argmax) in VMEM scratch.

Greedy rows (``temps[b] <= 0``) reduce the raw logits and are
*bitwise-equal* to ``jnp.argmax`` (strictly-greater cross-block updates
plus min-index tie-breaks inside a block reproduce first-occurrence
semantics exactly).  Temperature rows add in-kernel Gumbel noise to
``logits / temp`` — a Gumbel-max sample from the same softmax
distribution as ``jax.random.categorical``, but NOT the same draw: the
kernel derives its bits from a counter-based murmur3-finalizer hash of
(key words, flat element index), chosen over ``pltpu.prng_*`` because
it produces identical bits in interpret (CPU) and compiled (TPU) mode
— so only greedy rows are parity-pinned against the XLA path
(DESIGN.md §15).  Sampled rows are deterministic given (key, shapes).

Layouts: logits (B, V); temps (B,) f32; key (2,) uint32 -> (B,) int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import NEG_INF

_ROW_BLOCK = 8     # f32 sublanes per vreg
_LANES = 128


def _shr(h, n):
    return jax.lax.shift_right_logical(h, jnp.uint32(n))


def _fmix(h):
    """murmur3 32-bit finalizer (uint32, wrapping multiplies)."""
    h ^= _shr(h, 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h ^= _shr(h, 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h ^= _shr(h, 16)
    return h


def _sample_kernel(seed_ref, temps_ref, logits_ref, o_ref, m_scr, i_scr, *,
                   bb: int, bv: int, vocab: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        i_scr[...] = jnp.zeros_like(i_scr)

    x = logits_ref[...].astype(jnp.float32)               # (bb, bv)
    t = temps_ref[...]                                    # (bb, 1)

    # Gumbel-max: argmax(logits/t + g) ~ Categorical(softmax(logits/t)).
    # Counter = the element's flat (row, vocab) index; each key word is
    # folded in through a murmur3 finalizer round.
    row = i * bb + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    ctr = (row.astype(jnp.uint32) * jnp.uint32(vocab)
           + col.astype(jnp.uint32))
    # key words are bitcast as vectors (Mosaic bitcasts no scalars)
    k0, k1 = (jax.lax.bitcast_convert_type(
        jnp.full(x.shape, seed_ref[w], jnp.int32), jnp.uint32) for w in (0, 1))
    bits = _fmix(_fmix(ctr ^ k0) ^ k1)
    # 23 random bits fit int32 exactly (Mosaic has no u32 -> f32 cast)
    frac = jax.lax.bitcast_convert_type(_shr(bits, 9), jnp.int32).astype(
        jnp.float32)
    u = frac * (2.0 ** -23) + (2.0 ** -24)                # u in (0, 1)
    g = -jnp.log(-jnp.log(u))
    x = jnp.where(t > 0.0, x / jnp.maximum(t, 1e-6) + g, x)
    # the ragged last vocab block reads padding: it can never win
    x = jnp.where(col < vocab, x, -jnp.inf)

    vmax = jnp.max(x, axis=1, keepdims=True)              # (bb, 1)
    # first index attaining the block max (jnp.argmax tie-break)
    loc = jnp.min(jnp.where(x == vmax, col, jnp.int32(2 ** 31 - 1)),
                  axis=1, keepdims=True)
    better = vmax > m_scr[...]    # strict: earlier blocks win ties
    i_scr[...] = jnp.where(better, loc, i_scr[...])
    m_scr[...] = jnp.where(better, vmax, m_scr[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[...] = i_scr[...]


def fused_sample(logits, temps, key, *, bv: int = 2048,
                 interpret: bool = False):
    """One-launch greedy/temperature sample of the next token per row.

    logits (B, V); temps (B,) — <= 0 greedy, > 0 Gumbel-max at that
    temperature; key (2,) uint32 PRNG key data -> tokens (B,) int32.

    Rows tile in blocks of 8 and the vocabulary in lane-aligned blocks of
    ``bv`` (rounded to a multiple of 128): vocabularies such as 32064 =
    2^6 * 501 have no 128-aligned divisor, so both axes are padded up to
    whole blocks and the kernel masks the padding out.
    """
    B, V = logits.shape
    bb = _ROW_BLOCK
    bv = min(-(-int(bv) // _LANES), -(-V // _LANES)) * _LANES
    nb, nv = -(-B // bb), -(-V // bv)
    logits = jnp.pad(logits, ((0, nb * bb - B), (0, nv * bv - V)))
    temps2 = jnp.pad(jnp.asarray(temps, jnp.float32),
                     (0, nb * bb - B)).reshape(nb * bb, 1)
    seed = jax.lax.bitcast_convert_type(
        jnp.asarray(key, jnp.uint32), jnp.int32)

    def row_map(i, j, seed_ref):
        return (i, 0)

    def blk_map(i, j, seed_ref):
        return (i, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((bb, 1), row_map),
            pl.BlockSpec((bb, bv), blk_map),
        ],
        out_specs=pl.BlockSpec((bb, 1), row_map),
        scratch_shapes=[
            pltpu.VMEM((bb, 1), jnp.float32),  # running max per row
            pltpu.VMEM((bb, 1), jnp.int32),    # its first index
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sample_kernel, bb=bb, bv=bv, vocab=V),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * bb, 1), jnp.int32),
        interpret=interpret,
    )(seed, temps2, logits)
    return out[:B, 0]
