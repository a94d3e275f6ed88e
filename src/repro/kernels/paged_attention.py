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

The kernel sees each pool as page rows: ``(L, P, ps * K, hd)``, row
``t * K + k`` of a page holding token ``t``'s kv head ``k``.  The view
is a bitcast, not a copy: a bf16 ``(ps, K, 128)`` page in the
``(4, 128)(2, 1)`` tiles XLA gives 4 kv heads has the same bytes as
``(ps * K, 128)`` rows in ``(8, 128)(2, 1)`` tiles.  A page lands in
rows ``[i * ps * K, (i + 1) * ps * K)`` of the block buffer
``(ppb * ps * K, lanes)``, which is the key operand of the score
product and the value operand of the PV product as it stands: no
relayout between the DMA and the MXU, also where K = 4 leaves a page's
``(K, 128)`` slabs smaller than a sublane tile.

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

The FUSED variant writes the new token's K/V rows ``[r * K, (r + 1) *
K)`` (``r = pos[b] % ps``) into page ``pt[b, tb]`` of layer ``layer``
of the stacks, which it aliases whole to its outputs.  An HBM write
moves whole 8-row tiles, so it writes the tile-aligned group of ``tg``
tokens that holds the row (2 at K = 4, 1 at K = 32): the group's other
rows are read beside block 0, the new row is selected into them, and
the group goes back while the row walks its blocks, waited at the
row's end.  The same group replaces its rows in the VMEM
copy of the boundary page, so this call attends the new row whatever
the order of the write and the read.  Those rows are all the kernel
writes: every other bit of both stacks (other layers, the rest of the
boundary page, every page read) keeps its input bits, so the decode
scan hands it the stacks it carries and takes the returned stacks as
its next carry, with no copy of a layer's pool.  Preconditions the
engine maintains (DESIGN.md §15): each live row's boundary page is
private to that row (copy-on-write at admission), so the group write
never races another row's read; an idle row writes nothing.

Layouts: q (B, H, hd); k/v pools (L, P, ps, K, hd) (one layer's
``(P, ps, K, hd)`` for the unfused variant), handed to the kernel as the
page-row view ``(L, P, ps * K, hd)`` and returned in their own shape;
VMEM blocks ``(2, ppb * ps * K, lanes)``; page_table (B, nb) int32; pos
(B,) int32; window () int32 (0 or negative = global; may be a traced
per-layer scalar); layer () int32 (may be traced) -> o (B, H, hd)
[, updated k/v stacks].
"""
from __future__ import annotations

import functools
import math

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
# rows of one tile of the page-row view: the granule of a row write
_TILE_ROWS = 8


def pages_per_block(nb: int, ps: int, kv_heads: int, head_dim: int,
                    dtype) -> int:
    """Pages per DMA block: the most whose K+V bytes fit ``_BLOCK_BYTES``,
    at least 1 and at most the table's ``nb``."""
    page = ps * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(nb, _BLOCK_BYTES // (2 * page)))


def _paged_kernel(pt_ref, pos_ref, win_ref, layer_ref, q_ref, k_hbm, v_hbm,
                  *rest, ps: int, nb: int, ppb: int, trash: int, group: int,
                  kv_heads: int, hd: int, tg: int, logit_cap: float,
                  scale: float, fused: bool):
    if fused:
        nk_ref, nv_ref, o_ref, ck_hbm, cv_hbm, kbuf, vbuf, *grps, sems, \
            wsems = rest
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
    K = kv_heads
    R = ps * K                         # rows of a page: row t*K + k
    lanes = kbuf.shape[-1]
    T = ppb * ps
    cols = pl.ds(0, lanes)

    if fused:
        # The write group: the tile-aligned W rows of the boundary page
        # that hold the new token's K rows [r*K, (r+1)*K), tg tokens.
        # With tg > 1 its other rows are read beside block 0 and the new
        # row is selected in; with tg == 1 the group is the new row.
        W = tg * K
        grp_rows = pl.ds(pl.multiple_of(pos_b % ps // tg * W, W), W)
        # the same rows in the last block's buffer
        inject_rows = pl.ds(pl.multiple_of(
            (tb - lo) % ppb * R + pos_b % ps // tg * W, W), W)
        new = (nk_ref, nv_ref)
        grps = grps or new
        reads = [pltpu.make_async_copy(
            src.at[layer, boundary, grp_rows, cols], grp.at[0],
            wsems.at[0, i])
            for i, (src, grp) in enumerate(zip((k_hbm, v_hbm), grps))]
        writes = [pltpu.make_async_copy(
            grp.at[0], out.at[layer, boundary, grp_rows, cols],
            wsems.at[1, i])
            for i, (out, grp) in enumerate(zip((ck_hbm, cv_hbm), grps))]

    def page_copies(c, slot):
        """(live, k copy, v copy) for each page of block ``c``."""
        first = lo + c * ppb
        out = []
        for i in range(ppb):
            page = pt_ref[pt_row + jnp.minimum(first + i, tb)]
            rows = pl.ds(i * R, R)
            out.append((first + i <= tb,
                        pltpu.make_async_copy(k_hbm.at[layer, page, :, cols],
                                              kbuf.at[slot, rows],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[layer, page, :, cols],
                                              vbuf.at[slot, rows],
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
        if fused and tg > 1:
            for rd in reads:
                rd.start()
        start(0, 0)
        if fused:
            if tg > 1:
                # the group's other rows came in beside block 0: select
                # the new row into them
                own = (jax.lax.broadcasted_iota(jnp.int32, (W, lanes), 0)
                       // K == pos_b % tg)
                for rd, grp, row in zip(reads, grps, new):
                    rd.wait()
                    grp[...] = jnp.where(own, row[...], grp[...])
            # written back while the row walks its blocks
            for w in writes:
                w.start()

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
                for buf, grp in zip((kbuf, vbuf), grps):
                    buf[slot, inject_rows] = grp[0]

        kb = kbuf[slot, :, :hd]                               # (T*K, hd)
        vb = vbuf[slot, :, :hd].astype(jnp.float32)
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
        @pl.when(n_blocks > 0)
        def _written():
            for w in writes:
                w.wait()


def _call(q, k, v, page_table, pos, window, layer, new_k, new_v, *,
          logit_cap, fused, interpret):
    B, H, hd = q.shape
    L, P, ps, K, _ = k.shape
    nb = page_table.shape[1]
    if H % K:
        raise ValueError(f"q heads {H} not divisible by kv heads {K}")
    G = H // K
    ppb = pages_per_block(nb, ps, K, hd, k.dtype)
    # tokens the fused row write moves: the fewest whole tokens whose K
    # rows fill whole tiles, a divisor of the page (2 at K = 4, 1 at 32)
    tg = math.gcd(ps, math.lcm(K, _TILE_ROWS) // K)
    # On the chip the pools' tiled layout pads each (K, hd) slab to whole
    # 128-lane tiles, and Mosaic slices an HBM ref only in whole tiles:
    # the kernel moves the padded width and computes on the first hd
    # lanes.  The interpreter holds the logical width.
    lanes = hd if interpret else -(-hd // 128) * 128
    # the page-row view: a page's (ps, K) rows as ps*K matmul rows, the
    # same bytes in the pool's tiled layout, so XLA makes it a bitcast
    view = (L, P, ps * K, hd)

    pt = jnp.asarray(page_table, jnp.int32).reshape(-1)
    pos = jnp.asarray(pos, jnp.int32)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    kernel = functools.partial(
        _paged_kernel, ps=ps, nb=nb, ppb=ppb, trash=P - 1, group=G,
        kv_heads=K, hd=hd, tg=tg, logit_cap=float(logit_cap),
        scale=hd ** -0.5, fused=fused)

    def row_map(b, *_):
        return (b, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, H, hd), row_map), hbm, hbm]
    out_specs = [pl.BlockSpec((1, H, hd), row_map)]
    out_shape = [jax.ShapeDtypeStruct((B, H, hd), q.dtype)]
    operands = [q, k.reshape(view), v.reshape(view)]
    scratch = [
        pltpu.VMEM((2, ppb * ps * K, lanes), k.dtype),  # K blocks, 2 slots
        pltpu.VMEM((2, ppb * ps * K, lanes), v.dtype),  # V blocks
    ]
    sems = [pltpu.SemaphoreType.DMA((2, 2))]            # (k|v, slot)
    aliases = {}
    if fused:
        W = tg * K
        in_specs += [pl.BlockSpec((1, W, lanes), row_map),
                     pl.BlockSpec((1, W, lanes), row_map)]
        # the new row repeated over the write group's tg tokens
        pad = ((0, 0), (0, 0), (0, lanes - hd))
        operands += [jnp.tile(jnp.pad(x, pad), (1, tg, 1))
                     for x in (new_k, new_v)]
        out_specs += [hbm, hbm]
        out_shape += [jax.ShapeDtypeStruct(view, k.dtype),
                      jax.ShapeDtypeStruct(view, v.dtype)]
        if tg > 1:
            scratch += [pltpu.VMEM((1, W, lanes), k.dtype),  # K write group
                        pltpu.VMEM((1, W, lanes), v.dtype)]  # V write group
        sems += [pltpu.SemaphoreType.DMA((2, 2))]       # (read|write, k|v)
        # the whole layer stacks in place: operand indices count the 4
        # scalar-prefetch args (pt, pos, win, layer), so k/v sit at 5/6
        aliases = {5: 1, 6: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch + sems,
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
    if not fused:
        return out[0]
    o, ck, cv = out
    return o, ck.reshape(k.shape), cv.reshape(v.shape)


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
