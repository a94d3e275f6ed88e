"""Pallas TPU *paged* decode-attention kernel (DESIGN.md §15).

Extends ``kernels/decode_attention.py`` (§13) to a KV cache that lives
as a shared physical page pool instead of one dense ``(B, L)`` buffer
per slot: k/v are layer-stacked ``(L, P, ps, K, hd)`` pools (one page =
``ps`` token positions) and each slot owns a logical->physical page
table row ``pt (B, nb)``.  Requests with a common prompt prefix map the
*same* physical pages, so the pool holds one copy of every shared
prefix.

The page table rides in as a scalar-prefetch operand beside ``pos``,
``window`` and the layer index, and the KV block-fetch index map
dereferences it:

  grid (B, nb), j innermost (sequential, carries scratch);
  kv index map   (layer, pt[b, clip(j, lo_b, tb_b)], 0, 0, 0)

with ``tb_b = pos[b] // ps`` the row's last live logical page and
``lo_b`` the first page inside its local window — DMA is still clamped
to each slot's own depth exactly as in the dense kernel, and Pallas
elides refetches when consecutive grid steps map the same physical
page.  Logical key positions are reconstructed in-kernel as
``j * ps + iota`` (valid because accumulation is gated on
``lo <= j <= tb`` where the clamp is the identity).

The FUSED variant scatters the new token's K/V row through the page
table into the *boundary page* (the page holding ``pos[b]``) of layer
``layer`` inside the same launch.  It takes the whole layer stack and
aliases it whole to its outputs, so the model's decode scan hands it the
stacks it carries and the traced layer index and takes the returned
stacks as its next carry.  Handed one layer's slice instead, XLA would
materialise the slice and its write-back — two copies of a whole layer
pool per layer and tick, for the one row the kernel writes.
Preconditions the engine maintains (DESIGN.md §15): each live row's
boundary page is private to that row (copy-on-write at admission
guarantees it), so the in-place row injection never races; pages shared
read-only are written back bit-identically, and blocks the grid never
maps (other layers, unmapped pages of this layer) keep their input bits.

Layouts: q (B, H, hd); k/v pools (L, P, ps, K, hd) (one layer's
``(P, ps, K, hd)`` for the unfused variant); page_table (B, nb) int32;
pos (B,) int32; window () int32 (0 or negative = global; may be a traced
per-layer scalar); layer () int32 (may be traced) -> o (B, H, hd)
[, updated k/v stacks].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import NEG_INF, _block_bounds


def _paged_kernel(pt_ref, pos_ref, win_ref, layer_ref, q_ref, k_ref, v_ref,
                  *rest, ps: int, group: int, logit_cap: float, scale: float,
                  fused: bool):
    if fused:
        nk_ref, nv_ref, o_ref, ck_ref, cv_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    pos_b = pos_ref[b]
    win = win_ref[0]
    lo, tb = _block_bounds(pos_b, win, ps)
    jc = jnp.clip(j, lo, tb)          # logical page actually mapped

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kb = k_ref[0, 0].astype(jnp.float32)                  # (ps, K, hd)
    vb = v_ref[0, 0].astype(jnp.float32)
    if fused:
        # The boundary page holds the write position: inject the new
        # token's K/V row and write the visited page back through the
        # page table (rows > pos_b % ps stay bit-identical; the page
        # is private to this slot by the CoW admission rule).
        row = jax.lax.broadcasted_iota(jnp.int32, (kb.shape[0], 1, 1), 0)
        hit = (jc == tb) & (row == pos_b % ps)
        kb = jnp.where(hit, nk_ref[0].astype(jnp.float32)[None], kb)
        vb = jnp.where(hit, nv_ref[0].astype(jnp.float32)[None], vb)
        ck_ref[0, 0] = kb.astype(ck_ref.dtype)
        cv_ref[0, 0] = vb.astype(cv_ref.dtype)

    @pl.when((j >= lo) & (j <= tb))
    def _accumulate():
        q = (q_ref[0].astype(jnp.float32) * scale).reshape(
            kb.shape[1], group, -1)
        s = jnp.einsum("kgd,tkd->kgt", q, kb)             # (K, G, ps)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        # logical position of each key row (jc == j inside the gate)
        k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
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


def _call(q, k, v, page_table, pos, window, layer, new_k, new_v, *,
          logit_cap, fused, interpret):
    B, H, hd = q.shape
    _, _, ps, K, _ = k.shape
    nb = page_table.shape[1]
    if H % K:
        raise ValueError(f"q heads {H} not divisible by kv heads {K}")
    G = H // K

    pt = jnp.asarray(page_table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    kernel = functools.partial(
        _paged_kernel, ps=ps, group=G, logit_cap=float(logit_cap),
        scale=hd ** -0.5, fused=fused)

    def q_map(b, j, pt_ref, pos_ref, win_ref, layer_ref):
        return (b, 0, 0)

    def kv_map(b, j, pt_ref, pos_ref, win_ref, layer_ref):
        lo, tb = _block_bounds(pos_ref[b], win_ref[0], ps)
        return (layer_ref[0], pt_ref[b, jnp.clip(j, lo, tb)], 0, 0, 0)

    kv_block = (1, 1, ps, K, hd)
    in_specs = [
        pl.BlockSpec((1, H, hd), q_map),
        pl.BlockSpec(kv_block, kv_map),
        pl.BlockSpec(kv_block, kv_map),
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
        out_specs += [pl.BlockSpec(kv_block, kv_map),
                      pl.BlockSpec(kv_block, kv_map)]
        out_shape += [jax.ShapeDtypeStruct(k.shape, k.dtype),
                      jax.ShapeDtypeStruct(v.shape, v.dtype)]
        # the whole layer stack in place: operand indices count the 4
        # scalar-prefetch args (pt, pos, win, layer), so k/v sit at 5/6;
        # blocks the grid never maps (other layers, unmapped pages of
        # this one) keep their input bits.
        aliases = {5: 1, 6: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(pt, pos, win, lyr, *operands)
    return tuple(out) if fused else out[0]


def paged_decode_attention(q, k, v, page_table, pos, window=0, *,
                           logit_cap: float = 0.0, interpret: bool = False):
    """Paged decode attention; the pool already holds the new KV row.

    q (B,H,hd); k/v one layer's pools (P,ps,K,hd); page_table (B,nb)
    i32; pos (B,) i32 -> o (B,H,hd)."""
    return _call(q, k[None], v[None], page_table, pos, window, 0, None,
                 None, logit_cap=logit_cap, fused=False, interpret=interpret)


def paged_decode_attention_fused(q, k, v, new_k, new_v, page_table, pos,
                                 window=0, layer=0, *,
                                 logit_cap: float = 0.0,
                                 interpret: bool = False):
    """Fused through-the-page-table KV scatter + paged decode attention.

    k/v are the layer-stacked pools (L,P,ps,K,hd) and ``layer`` the
    layer to attend (an int32 scalar, may be traced).  Writes
    ``new_k/new_v`` (B,K,hd) into each row's boundary page of that layer
    at ``pos[b] % ps`` inside the launch (the stacks are aliased whole)
    and attends ``k_idx <= pos[b]``.  Returns (o, k_stack, v_stack).
    Precondition: every live row's boundary page is private to that row
    (the engine's CoW-at-admission rule).
    """
    return _call(q, k, v, page_table, pos, window, layer, new_k, new_v,
                 logit_cap=logit_cap, fused=True, interpret=interpret)
