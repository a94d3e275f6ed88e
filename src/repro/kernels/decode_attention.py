"""Pallas TPU decode-attention kernel for the serve hot path.

One query row per slot attends that slot's KV-cache prefix (``k_idx <=
pos[b]``) — the continuous-batching decode tick of ``serve/engine.py``.
The XLA path (``models.attention.decode_attention``) broadcasts every
slot's query against the FULL ``(B, L)`` cache buffer and materializes a
``(B, K, G, L)`` f32 logit tensor per layer per tick; this kernel streams
the cache in ``bk``-row blocks with flash-style running (m, l, acc)
online-softmax state in VMEM scratch, and — the decode-specific part —
uses the per-row positions as *scalar-prefetch* operands so the KV
block-fetch index map clamps to each row's live window:

  grid (B, nk), j innermost (sequential, carries scratch);
  kv index map   (b, clip(j, lo_b, tb_b), 0, 0)

where ``tb_b = pos[b] // bk`` is the row's last live block and ``lo_b``
the first block inside its local window.  Pallas elides block copies
whose index map repeats the previous index, so a slot at depth 5 in a
4096-deep cache DMAs one block, not 32 — per-slot read traffic scales
with the slot's own depth, the access pattern the paper's LLC analysis
prices (DESIGN.md §13).  All H query heads ride in one grid step (q
block (1, H, hd) reshaped to (K, G, hd) in-kernel), so each KV block is
fetched ONCE per slot — GQA grouping happens in the batched dot, never
as extra grid steps or per-q-head refetches.

The FUSED variant additionally scatters the new token's K/V row into the
cache block that contains ``pos[b]`` inside the same launch (the block is
already in VMEM for the self-attention term), writing only visited
blocks back via an aliased input/output cache buffer — this replaces the
engine's separate per-layer ``cache.at[rows, pos].set`` pass and never
writes a block past a live slot's position (rows beyond ``pos[b]`` in
the boundary block are written back bit-identically).

Layouts (cache-native; no transposes on the hot path):
  q (B, H, hd); k/v cache (B, L, K, hd); new k/v rows (B, K, hd);
  pos (B,) int32; window () int32 (0 or negative = global; may be a
  traced per-layer scalar) -> o (B, H, hd) [, updated k/v caches].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


# Scoped VMEM the kernel declares to Mosaic (v5e has 128 MiB per core;
# the compiler's default scope is 16 MiB), and the share of it the KV
# blocks may plan for: the rest covers what the row estimate below
# leaves out (q, scratch, score tensors, the compiler's own relayouts).
VMEM_LIMIT_BYTES = 32 << 20
_KV_BLOCK_BUDGET = VMEM_LIMIT_BYTES // 2


def decode_block_size(max_len: int, bk: int) -> int:
    """Largest KV block <= ``bk`` that divides ``max_len`` (the kernel
    tiles the cache exactly; same contract as cachesim's divisor tile)."""
    for tile in range(min(int(bk), int(max_len)), 0, -1):
        if max_len % tile == 0:
            return tile
    return 1


def kv_block_rows(max_len: int, kv_heads: int, head_dim: int, dtype, *,
                  fused: bool, bk: int = 128) -> int:
    """KV block rows for the decode kernel, sized from the VMEM budget.

    One cache row costs, in VMEM: its k and v blocks (plus the aliased
    k/v output blocks when ``fused``), each double-buffered in the cache
    dtype with sublanes padded to the dtype's packing and lanes to 128;
    and four f32 copies of (K, hd) — the upcast k/v blocks and the
    relayouts the batched dots make of them.  The block is the largest
    divisor of ``max_len`` that is at most ``bk`` and fits the budget.
    """
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // itemsize
    lanes = -(-head_dim // 128) * 128
    k_pad = -(-kv_heads // sublanes) * sublanes
    streams = 4 if fused else 2
    row = (k_pad * lanes * itemsize * 2 * streams
           + kv_heads * lanes * 4 * 4)
    return decode_block_size(max_len, max(1, min(bk, _KV_BLOCK_BUDGET // row)))


def _block_bounds(pos_b, win, bk):
    """(lo, tb): first and last live KV-block index for a row at pos_b.

    ``win <= 0`` means global attention (the traced per-layer escape
    hatch shared with the jnp paths).
    """
    tb = pos_b // bk
    lo = jnp.where(win > 0,
                   jnp.maximum(pos_b - win + 1, 0) // bk,
                   0)
    return lo, tb


def _decode_kernel(pos_ref, win_ref, q_ref, k_ref, v_ref, *rest,
                   bk: int, group: int, logit_cap: float, scale: float,
                   fused: bool):
    if fused:
        nk_ref, nv_ref, o_ref, ck_ref, cv_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    pos_b = pos_ref[b]
    win = win_ref[0]
    lo, tb = _block_bounds(pos_b, win, bk)
    jc = jnp.clip(j, lo, tb)          # block actually mapped by the specs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kb = k_ref[0].astype(jnp.float32)                     # (bk, K, hd)
    vb = v_ref[0].astype(jnp.float32)
    if fused:
        # The boundary block holds the write position: inject the new
        # token's K/V row so the self-attention term sees it, and write
        # the visited block back (rows > pos_b stay bit-identical).
        row = jax.lax.broadcasted_iota(jnp.int32, (kb.shape[0], 1, 1), 0)
        hit = (jc == tb) & (row == pos_b % bk)
        kb = jnp.where(hit, nk_ref[0].astype(jnp.float32)[None], kb)
        vb = jnp.where(hit, nv_ref[0].astype(jnp.float32)[None], vb)
        ck_ref[0] = kb.astype(ck_ref.dtype)
        cv_ref[0] = vb.astype(cv_ref.dtype)

    @pl.when((j >= lo) & (j <= tb))
    def _accumulate():
        K = kb.shape[1]
        # (K, G, hd): q head k*G + g attends kv head k — same grouping
        # as the h // G index-map trick, done in one batched dot.
        q = (q_ref[0].astype(jnp.float32) * scale).reshape(K, group, -1)
        s = jnp.einsum("kgd,tkd->kgt", q, kb)             # (K, G, bk)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        ok = k_pos <= pos_b
        ok &= (win <= 0) | (k_pos > pos_b - win)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2)
        acc_scr[...] = (acc_scr[...] * corr[..., None]
                        + jnp.einsum("kgt,tkd->kgd", p, vb))
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        acc = acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)[..., None]
        o_ref[0] = acc.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _call(q, k, v, pos, window, new_k, new_v, *, logit_cap, bk, fused,
          interpret):
    B, H, hd = q.shape
    _, L, K, _ = k.shape
    if H % K:
        raise ValueError(f"q heads {H} not divisible by kv heads {K}")
    G = H // K
    bk = kv_block_rows(L, K, hd, k.dtype, fused=fused, bk=bk)
    nk = L // bk

    pos = jnp.asarray(pos, jnp.int32)
    win = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, bk=bk, group=G, logit_cap=float(logit_cap),
        scale=hd ** -0.5, fused=fused)

    def q_map(b, j, pos_ref, win_ref):
        return (b, 0, 0)

    def kv_map(b, j, pos_ref, win_ref):
        lo, tb = _block_bounds(pos_ref[b], win_ref[0], bk)
        return (b, jnp.clip(j, lo, tb), 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, hd), q_map),
        pl.BlockSpec((1, bk, K, hd), kv_map),
        pl.BlockSpec((1, bk, K, hd), kv_map),
    ]
    out_specs = [pl.BlockSpec((1, H, hd), q_map)]
    out_shape = [jax.ShapeDtypeStruct((B, H, hd), q.dtype)]
    operands = [q, k, v]
    scratch = [
        pltpu.VMEM((K, G), jnp.float32),      # m (running max, per head)
        pltpu.VMEM((K, G), jnp.float32),      # l (running sum, per head)
        pltpu.VMEM((K, G, hd), jnp.float32),  # acc
    ]
    aliases = {}
    if fused:
        in_specs += [pl.BlockSpec((1, K, hd), q_map),
                     pl.BlockSpec((1, K, hd), q_map)]
        operands += [new_k, new_v]
        out_specs += [pl.BlockSpec((1, bk, K, hd), kv_map),
                      pl.BlockSpec((1, bk, K, hd), kv_map)]
        out_shape += [jax.ShapeDtypeStruct(k.shape, k.dtype),
                      jax.ShapeDtypeStruct(v.shape, v.dtype)]
        # cache in-place: operand indices count the 2 scalar-prefetch
        # args (pos, win), so k/v sit at 3/4; blocks the grid never
        # maps (beyond a row's live window) keep their input bits.
        aliases = {3: 1, 4: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(pos, win, *operands)
    return tuple(out) if fused else out[0]


def decode_attention(q, k, v, pos, window=0, *, logit_cap: float = 0.0,
                     bk: int = 128, interpret: bool = False):
    """Blocked decode attention; the cache already holds the new KV row.

    q (B,H,hd); k/v (B,L,K,hd); pos (B,) int32 -> o (B,H,hd).  ``bk``
    caps the KV block; ``kv_block_rows`` may shrink it to fit VMEM."""
    return _call(q, k, v, pos, window, None, None, logit_cap=logit_cap,
                 bk=bk, fused=False, interpret=interpret)


def decode_attention_fused(q, k, v, new_k, new_v, pos, window=0, *,
                           logit_cap: float = 0.0, bk: int = 128,
                           interpret: bool = False):
    """Fused scatter + blocked decode attention.

    Writes ``new_k/new_v`` (B,K,hd) into the caches at each row's own
    ``pos[b]`` inside the launch and attends ``k_idx <= pos[b]``.
    Returns (o, k_cache, v_cache); the caches are aliased in/out, so no
    separate per-layer ``dynamic_update_slice`` pass and no full-cache
    copy.  Invariant: no cache row past a live slot's ``pos`` changes.
    """
    return _call(q, k, v, pos, window, new_k, new_v, logit_cap=logit_cap,
                 bk=bk, fused=True, interpret=interpret)
