"""Pallas TPU kernels: trace-driven set-associative LRU cache simulator.

This is the paper's GPGPU-Sim replacement hot loop (DESIGN.md §3): iso-area
DRAM-access counts need cache-miss simulation at capacities that don't
exist in hardware. Two kernels share the LRU semantics:

``cache_sim`` (per-point, the seed path retained as the parity baseline):
SETS are embarrassingly parallel (grid over set tiles, tag/LRU-age state
lives in VMEM scratch); the TRACE is sequential (fori_loop). Each set tile
scans the full trace and handles only accesses that map to one of its sets
via masked vectorized updates — O(sets_tile x ways) vector work per access
on the VPU, no serialized per-way branching.

``cache_sim_ladder`` (batched engine): one launch whose grid spans
(workload traces x capacity-ladder set tiles). Each grid cell owns one set
tile of one ladder rung, derives set ids / tags from the raw line trace
and its rung's set count in-kernel, and touches only the one (1, ways)
LRU row an access maps to (dynamic-slice read/modify/write) — O(ways)
work per access instead of O(sets_tile x ways).

Inputs: per-point takes set_ids/tags (T,) int32 precomputed from line
addresses; the ladder engine takes raw line traces (W, T) int32 plus the
static per-rung set counts. Outputs: [hits, misses] counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EMPTY = -1  # empty-way tag sentinel
TRACE_CHUNK = 16384  # ladder trace ids per SMEM block (SMEM holds 1 MiB)


def _cachesim_kernel(setid_ref, tag_ref, out_ref, tags_scr, age_scr,
                     cnt_scr, *, sets_tile: int, ways: int, trace_len: int):
    s0 = pl.program_id(0) * sets_tile

    tags_scr[...] = jnp.full(tags_scr.shape, EMPTY, tags_scr.dtype)
    age_scr[...] = jnp.zeros_like(age_scr)
    cnt_scr[...] = jnp.zeros_like(cnt_scr)

    set_ids = setid_ref[...]
    tags_in = tag_ref[...]

    def step(t, _):
        sid = set_ids[t] - s0                       # local set row
        tag = tags_in[t]
        in_tile = (sid >= 0) & (sid < sets_tile)
        row = jnp.where(in_tile, sid, 0)
        row_mask = (jax.lax.broadcasted_iota(jnp.int32, (sets_tile, ways), 0)
                    == row) & in_tile               # (sets, ways)
        tags = tags_scr[...]
        ages = age_scr[...]
        hit_mask = row_mask & (tags == tag)
        hit = jnp.any(hit_mask)
        # LRU victim within the row: max age
        row_ages = jnp.where(row_mask, ages, -1)
        victim_flat = jnp.argmax(row_ages.reshape(-1))
        victim_mask = (jax.lax.broadcasted_iota(
            jnp.int32, (sets_tile * ways,), 0) == victim_flat
        ).reshape(sets_tile, ways) & row_mask
        write_mask = jnp.where(hit, hit_mask, victim_mask)
        tags_scr[...] = jnp.where(write_mask, tag, tags)
        # age: touched line -> 0; other lines in the row -> +1
        age_scr[...] = jnp.where(write_mask, 0,
                                 jnp.where(row_mask, ages + 1, ages))
        cnt_scr[0] = cnt_scr[0] + jnp.where(in_tile & hit, 1, 0)
        cnt_scr[1] = cnt_scr[1] + jnp.where(in_tile & ~hit, 1, 0)
        return 0

    jax.lax.fori_loop(0, trace_len, step, 0)
    out_ref[0] = cnt_scr[...]


def cache_sim(set_ids, tags, *, num_sets: int, ways: int,
              sets_tile: int = 128, interpret: bool = False):
    """Simulate an LRU set-associative cache over an access trace.

    Returns (hits, misses) totals.
    """
    T = set_ids.shape[0]
    assert num_sets % sets_tile == 0, (num_sets, sets_tile)
    n_tiles = num_sets // sets_tile
    kernel = functools.partial(_cachesim_kernel, sets_tile=sets_tile,
                               ways=ways, trace_len=T)
    counts = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((T,), lambda i: (0,)),
            pl.BlockSpec((T,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 2), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((sets_tile, ways), jnp.int32),
            pltpu.VMEM((sets_tile, ways), jnp.int32),
            pltpu.VMEM((2,), jnp.int32),
        ],
        interpret=interpret,
    )(set_ids.astype(jnp.int32), tags.astype(jnp.int32))
    total = counts.sum(axis=0)
    return total[0], total[1]


def _ladder_kernel(ns_ref, base_ref, trace_ref, out_ref, tags_scr, age_scr,
                   cnt_scr, *, sets_tile: int, ways: int, chunk: int,
                   trace_len: int):
    g = pl.program_id(1)
    c = pl.program_id(2)
    ns = ns_ref[g]                               # this tile's rung set count
    s0 = base_ref[g]                             # first set owned by the tile

    @pl.when(c == 0)
    def _init():
        tags_scr[...] = jnp.full(tags_scr.shape, EMPTY, tags_scr.dtype)
        age_scr[...] = jnp.zeros_like(age_scr)
        cnt_scr[0] = 0
        cnt_scr[1] = 0

    way_iota = jax.lax.broadcasted_iota(jnp.int32, (1, ways), 1)

    def step(t, carry):
        hits, misses = carry
        line = trace_ref[0, t]
        sid = line % ns - s0                     # local set row
        tag = line // ns
        in_tile = (sid >= 0) & (sid < sets_tile)
        row = jnp.where(in_tile, sid, 0)
        row_tags = tags_scr[pl.ds(row, 1), :]    # (1, ways)
        row_ages = age_scr[pl.ds(row, 1), :]
        hit_way = jnp.min(jnp.where(row_tags == tag, way_iota, ways))
        hit = hit_way < ways
        # LRU way: the first way of max age (Mosaic argmaxes f32 only)
        victim = jnp.min(jnp.where(row_ages == jnp.max(row_ages), way_iota,
                                   ways))
        way = jnp.where(hit, hit_way, victim)
        touched = way_iota == way
        # touched way -> age 0; rest of the row ages by one
        new_tags = jnp.where(touched, tag, row_tags)
        new_ages = jnp.where(touched, 0, row_ages + 1)
        keep = ~in_tile                          # foreign access: no-op write
        tags_scr[pl.ds(row, 1), :] = jnp.where(keep, row_tags, new_tags)
        age_scr[pl.ds(row, 1), :] = jnp.where(keep, row_ages, new_ages)
        return (hits + jnp.where(in_tile & hit, 1, 0),
                misses + jnp.where(in_tile & ~hit, 1, 0))

    # the last chunk stops at the trace's end (its padding is never read)
    n = jnp.minimum(chunk, trace_len - c * chunk)
    h, m = jax.lax.fori_loop(0, n, step, (cnt_scr[0], cnt_scr[1]))
    cnt_scr[0] = h
    cnt_scr[1] = m

    @pl.when(c == pl.num_programs(2) - 1)
    def _emit():
        out_ref[0, 0] = h
        out_ref[0, 1] = m


def ladder_tiles(num_sets_ladder, sets_tile: int):
    """Static (tile set-count, tile base, rung id) triples covering a ladder.

    One entry per grid cell of ``cache_sim_ladder``: rung ``l`` with ``ns``
    sets contributes ``ceil(ns / tile)`` tiles (no divisibility requirement —
    the kernel masks accesses outside ``[base, base + tile)``).
    """
    ladder = tuple(int(n) for n in num_sets_ladder)
    if not ladder or min(ladder) < 1:
        raise ValueError(f"bad set-count ladder {ladder!r}")
    tile = min(int(sets_tile), max(ladder))
    ns_of, base_of, rung_of = [], [], []
    for l, ns in enumerate(ladder):
        for base in range(0, ns, tile):
            ns_of.append(ns)
            base_of.append(base)
            rung_of.append(l)
    return tile, tuple(ns_of), tuple(base_of), tuple(rung_of)


def cache_sim_ladder(traces, num_sets_ladder, *, ways: int,
                     sets_tile: int = 2048, chunk: int = TRACE_CHUNK,
                     interpret: bool = False):
    """Simulate every (trace, ladder rung) pair in one Pallas launch.

    ``traces`` is (W, T) int32 line ids; ``num_sets_ladder`` a static tuple
    of per-rung set counts. Returns (W, L, 2) int32 [hits, misses].

    Grid (W, tiles, trace chunks), chunks innermost: each access's line id
    is a scalar read from SMEM, which holds one double-buffered chunk of
    the trace at a time, so the trace length is unbounded by SMEM.
    """
    traces = jnp.asarray(traces, jnp.int32)
    W, T = traces.shape
    tile, ns_of, base_of, rung_of = ladder_tiles(num_sets_ladder, sets_tile)
    G = len(ns_of)
    chunk = max(1, min(int(chunk), T))
    nc = -(-T // chunk)
    traces = jnp.pad(traces, ((0, 0), (0, nc * chunk - T)))
    kernel = functools.partial(_ladder_kernel, sets_tile=tile, ways=ways,
                               chunk=chunk, trace_len=T)
    smem = pltpu.MemorySpace.SMEM
    # trace rows and outputs carry a unit axis so that their blocks' two
    # minor dims equal the array's, as Mosaic requires
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # per-tile set count, base
        grid=(W, G, nc),
        in_specs=[pl.BlockSpec((None, 1, chunk),
                               lambda w, g, c, ns, base: (w, 0, c),
                               memory_space=smem)],
        out_specs=pl.BlockSpec((None, None, 1, 2),
                               lambda w, g, c, ns, base: (w, g, 0, 0),
                               memory_space=smem),
        scratch_shapes=[
            pltpu.VMEM((tile, ways), jnp.int32),
            pltpu.VMEM((tile, ways), jnp.int32),
            pltpu.SMEM((2,), jnp.int32),         # running hits, misses
        ],
    )
    counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, G, 1, 2), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(ns_of, jnp.int32), jnp.asarray(base_of, jnp.int32),
      traces[:, None, :])[:, :, 0]
    # tile -> rung reduction (pure bookkeeping; rung ids are static)
    seg = jnp.asarray(rung_of, jnp.int32)
    per_rung = jax.ops.segment_sum(counts.transpose(1, 0, 2), seg,
                                   num_segments=len(num_sets_ladder))
    return per_rung.transpose(1, 0, 2)
