"""Pallas TPU *paged* decode-attention kernel (DESIGN.md §15).

Extends ``kernels/decode_attention.py`` (§13) to a KV cache that lives
as a shared physical page pool instead of one dense ``(B, L)`` buffer
per slot: k/v are layer-stacked ``(L, P, ps, K, hd)`` pools (one page =
``ps`` token positions) and each slot owns a logical->physical page
table row ``pt (B, nb)``.  Requests with a common prompt prefix map the
*same* physical pages, so the pool holds one copy of every shared
prefix.

The pools stay in HBM (``pl.ANY``) and the kernel moves pages itself.
The page table (flattened), ``pos``, ``window`` and the layer index are
scalar-prefetch operands; the grid is ``(B,)``, one step per row, and
each row walks only its live pages ``[lo, tb]`` (``_block_bounds``:
``tb = pos // ps``, ``lo`` the first page inside a local window):

  blocks of ``ppb`` consecutive logical pages from ``lo``, each page one
  async copy ``pool[layer, pt[b, j]] -> VMEM`` through the page table,
  double-buffered so block ``c + 1`` is in flight while block ``c`` runs
  the online softmax (m, l, acc in f32) over its ``ppb * ps`` keys.

A block's scores are one matmul of the H query heads against all its
(key, kv head) rows, masked to each head's own kv head: K times the MXU
work of a per-head product, but the block is used in its DMA layout
with no transpose.

``ppb`` follows from a page's bytes (a K+V block of at most
``_BLOCK_BYTES``), never from a per-model knob.  Pages past ``tb`` in
the last block are not copied; the key mask hides their stale VMEM and
their V rows are zeroed.  A row whose boundary page is the TRASH page
(the pool's last page, ``init_paged_cache``; an idle slot's table row is
all TRASH) walks nothing and reads no page.

The FUSED variant writes the new token's K/V row ``(K, hd)`` straight
from VMEM into ``(layer, pt[b, tb], pos[b] % ps)`` of the layer stacks,
which it aliases whole to its outputs, and injects the same row into
the VMEM copy of the boundary page, so this call attends it whatever
the order of the write and the read.  That one row per row is all the
kernel writes: every other bit of both stacks (other layers, the rest of
the boundary page, every page read) keeps its input bits, so the decode
scan hands it the stacks it carries and takes the returned stacks as its
next carry, with no copy of a layer's pool.  Preconditions the engine
maintains (DESIGN.md §15): each live row's boundary page is private to
that row (copy-on-write at admission), so the row write never races
another row's read; an idle row's write lands in the TRASH page.

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

from repro.kernels.decode_attention import (NEG_INF, VMEM_LIMIT_BYTES,
                                            _block_bounds)

# Logical bytes of one K+V page block: enough keys a DMA block to
# amortise the per-block loop and DMA waits, small enough that the two
# buffers and their f32 copies sit well inside the scoped VMEM.
_BLOCK_BYTES = 512 << 10


def pages_per_block(nb: int, ps: int, kv_heads: int, head_dim: int,
                    dtype) -> int:
    """Pages per DMA block: the most whose K+V bytes fit ``_BLOCK_BYTES``,
    at least 1 and at most the table's ``nb``."""
    page = ps * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(nb, _BLOCK_BYTES // (2 * page)))


def _paged_kernel(pt_ref, pos_ref, win_ref, layer_ref, q_ref, k_hbm, v_hbm,
                  *rest, ps: int, nb: int, ppb: int, trash: int, group: int,
                  hd: int, logit_cap: float, scale: float, fused: bool):
    if fused:
        (nk_ref, nv_ref, o_ref, ck_hbm, cv_hbm,
         kbuf, vbuf, sems, wsems) = rest
    else:
        o_ref, kbuf, vbuf, sems = rest
    b = pl.program_id(0)
    pos_b = pos_ref[b]
    win = win_ref[0]
    layer = layer_ref[0]
    lo, tb = _block_bounds(pos_b, win, ps)
    pt_row = b * nb                    # row b's offset in the flat table
    boundary = pt_ref[pt_row + tb]
    n_blocks = jnp.where(boundary == trash, 0, (tb - lo) // ppb + 1)
    K, lanes = kbuf.shape[-2:]
    T = ppb * ps
    tile = (slice(None), slice(None), pl.ds(0, lanes))  # (ps, K, lanes)

    if fused:
        r = pos_b % ps
        writes = [pltpu.make_async_copy(
            new.at[0], out.at[(layer, boundary, r) + tile[1:]], wsems.at[i])
            for i, (new, out) in enumerate(((nk_ref, ck_hbm),
                                            (nv_ref, cv_hbm)))]
        for w in writes:
            w.start()

    def page_copies(c, slot):
        """(live, k copy, v copy) for each page of block ``c``."""
        first = lo + c * ppb
        out = []
        for i in range(ppb):
            page = pt_ref[pt_row + jnp.minimum(first + i, tb)]
            out.append((first + i <= tb,
                        pltpu.make_async_copy(k_hbm.at[(layer, page) + tile],
                                              kbuf.at[slot, i],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[(layer, page) + tile],
                                              vbuf.at[slot, i],
                                              sems.at[1, slot])))
        return out

    def start(c, slot):
        for live, ck, cv in page_copies(c, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(c, slot):
        for live, ck, cv in page_copies(c, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(n_blocks > 0)
    def _prefetch():
        start(0, 0)

    H = K * group
    q = q_ref[0].astype(kbuf.dtype)                       # (H, hd)
    # scores (H, T*K): row h = k*G + g, column c = t*K + k'; only
    # k' == k is attention, the rest is masked
    col = jax.lax.broadcasted_iota(jnp.int32, (H, T * K), 1)
    same_head = (col % K) == jax.lax.broadcasted_iota(
        jnp.int32, (H, T * K), 0) // group
    row_t = jax.lax.broadcasted_iota(jnp.int32, (T * K, 1), 0) // K

    def block(c, carry):
        m_prev, l_prev, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_blocks)
        def _next():
            start(c + 1, 1 - slot)

        wait(c, slot)
        first = lo + c * ppb
        if fused:
            # the boundary page is in this block: attend the new row
            @pl.when(c == n_blocks - 1)
            def _inject():
                kbuf[slot, tb - first, pos_b % ps] = nk_ref[0]
                vbuf[slot, tb - first, pos_b % ps] = nv_ref[0]

        kb = kbuf[slot, :, :, :, :hd].reshape(T * K, hd)
        vb = vbuf[slot, :, :, :, :hd].reshape(T * K, hd).astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        k_pos = first * ps + col // K
        ok = same_head & (k_pos <= pos_b)
        ok &= (win <= 0) | (k_pos > pos_b - win)
        s = jnp.where(ok, s, NEG_INF)
        # pages past tb were not copied: zero their stale V rows
        vb = jnp.where(first * ps + row_t <= pos_b, vb, 0.0)

        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc = acc * corr + jnp.dot(p, vb, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, hd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)
    if fused:
        for w in writes:
            w.wait()


def _call(q, k, v, page_table, pos, window, layer, new_k, new_v, *,
          logit_cap, fused, interpret):
    B, H, hd = q.shape
    _, P, ps, K, _ = k.shape
    nb = page_table.shape[1]
    if H % K:
        raise ValueError(f"q heads {H} not divisible by kv heads {K}")
    G = H // K
    ppb = pages_per_block(nb, ps, K, hd, k.dtype)
    # On the chip the pools' tiled layout pads each (K, hd) slab to whole
    # 128-lane tiles, and Mosaic slices an HBM ref only in whole tiles:
    # the kernel moves the padded width and computes on the first hd
    # lanes.  The interpreter holds the logical width.
    lanes = hd if interpret else -(-hd // 128) * 128

    pt = jnp.asarray(page_table, jnp.int32).reshape(-1)
    pos = jnp.asarray(pos, jnp.int32)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    kernel = functools.partial(
        _paged_kernel, ps=ps, nb=nb, ppb=ppb, trash=P - 1, group=G, hd=hd,
        logit_cap=float(logit_cap), scale=hd ** -0.5, fused=fused)

    def row_map(b, *_):
        return (b, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, H, hd), row_map), hbm, hbm]
    out_specs = [pl.BlockSpec((1, H, hd), row_map)]
    out_shape = [jax.ShapeDtypeStruct((B, H, hd), q.dtype)]
    operands = [q, k, v]
    scratch = [
        pltpu.VMEM((2, ppb, ps, K, lanes), k.dtype),   # K blocks, 2 slots
        pltpu.VMEM((2, ppb, ps, K, lanes), v.dtype),   # V blocks
        pltpu.SemaphoreType.DMA((2, 2)),            # (k|v, slot)
    ]
    aliases = {}
    if fused:
        in_specs += [pl.BlockSpec((1, K, lanes), row_map),
                     pl.BlockSpec((1, K, lanes), row_map)]
        pad = ((0, 0), (0, 0), (0, lanes - hd))
        operands += [jnp.pad(new_k, pad), jnp.pad(new_v, pad)]
        out_specs += [hbm, hbm]
        out_shape += [jax.ShapeDtypeStruct(k.shape, k.dtype),
                      jax.ShapeDtypeStruct(v.shape, v.dtype)]
        scratch += [pltpu.SemaphoreType.DMA((2,))]  # the row writes
        # the whole layer stacks in place: operand indices count the 4
        # scalar-prefetch args (pt, pos, win, layer), so k/v sit at 5/6
        aliases = {5: 1, 6: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
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
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(pt, pos, win, lyr, *operands)
    return tuple(out) if fused else out[0]


def paged_decode_attention(q, k, v, page_table, pos, window=0, *,
                           logit_cap: float = 0.0, interpret: bool = False):
    """Paged decode attention; the pool already holds the new KV row.

    q (B,H,hd); k/v one layer's pools (P,ps,K,hd); page_table (B,nb)
    i32; pos (B,) i32 -> o (B,H,hd).  A row whose page at ``pos // ps``
    is the pool's last page (TRASH) is idle: it reads no page and its
    output is zero."""
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
