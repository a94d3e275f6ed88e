"""Jit'd public wrappers for the Pallas kernels.

The backend picks the mode, and no caller can override it: on the CPU the
kernels run in interpret mode, which executes the kernel body in Python
for correctness tests; on a TPU they always compile to Mosaic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import cache_sim as _cs
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rglru_scan as _rg
from repro.kernels import sampling as _sm
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "logit_cap", "bq",
                                   "bk"))
def flash_attention(q, k, v, *, causal=True, window=0, logit_cap=0.0,
                    bq=128, bk=128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               logit_cap=logit_cap, bq=bq, bk=bk,
                               interpret=_interpret())


@partial(jax.jit, static_argnames=("logit_cap", "bk"))
def decode_attention(q, k, v, pos, window, *, logit_cap=0.0, bk=128):
    """Blocked serve-decode attention (cache already holds the new row).

    q (B,H,hd); k/v (B,L,K,hd); pos (B,) i32; window i32 scalar (may be
    traced; <= 0 = global) -> (B,H,hd)."""
    return _da.decode_attention(q, k, v, pos, window, logit_cap=logit_cap,
                                bk=bk, interpret=_interpret())


@partial(jax.jit, static_argnames=("logit_cap", "bk"))
def decode_attention_fused(q, k, v, new_k, new_v, pos, window, *,
                           logit_cap=0.0, bk=128):
    """Fused per-row KV scatter + blocked decode attention.

    Writes new_k/new_v (B,K,hd) at each row's own pos[b] inside the
    launch (aliased caches, no separate dynamic_update_slice pass) and
    returns (o, k_cache, v_cache)."""
    return _da.decode_attention_fused(
        q, k, v, new_k, new_v, pos, window, logit_cap=logit_cap, bk=bk,
        interpret=_interpret())


@partial(jax.jit, static_argnames=("logit_cap",))
def paged_decode_attention(q, k, v, page_table, pos, window, *,
                           logit_cap=0.0):
    """Paged serve-decode attention (pool already holds the new row).

    q (B,H,hd); k/v pools (P,ps,K,hd); page_table (B,nb) i32; pos (B,)
    i32; window i32 scalar (may be traced; <= 0 = global) -> (B,H,hd)."""
    return _pa.paged_decode_attention(
        q, k, v, page_table, pos, window, logit_cap=logit_cap,
        interpret=_interpret())


@partial(jax.jit, static_argnames=("logit_cap",))
def paged_decode_attention_fused(q, k, v, new_k, new_v, page_table, pos,
                                 window, *, logit_cap=0.0):
    """Fused through-the-page-table KV scatter + paged decode attention.

    Writes new_k/new_v (B,K,hd) into each row's boundary page at
    pos[b] % ps inside the launch (aliased pools) and returns
    (o, k_pool, v_pool)."""
    return _pa.paged_decode_attention_fused(
        q, k, v, new_k, new_v, page_table, pos, window,
        logit_cap=logit_cap, interpret=_interpret())


@partial(jax.jit, static_argnames=("bv",))
def fused_sample(logits, temps, key, *, bv=2048):
    """One-launch greedy/temperature next-token sample.

    logits (B,V); temps (B,) (<= 0 greedy, bitwise == argmax; > 0
    in-kernel Gumbel-max); key (2,) uint32 -> (B,) int32."""
    return _sm.fused_sample(logits, temps, key, bv=bv,
                            interpret=_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, dtA, Bmat, Cmat, *, chunk=128):
    return _ssd.ssd_scan(x, dt, dtA, Bmat, Cmat, chunk=chunk,
                         interpret=_interpret())


@partial(jax.jit, static_argnames=("block", "width_tile"))
def rglru_scan(a, b, *, block=256, width_tile=512):
    return _rg.rglru_scan_kernel(a, b, block=block, width_tile=width_tile,
                                 interpret=_interpret())


@partial(jax.jit, static_argnames=("num_sets", "ways", "sets_tile"))
def cache_sim(set_ids, tags, *, num_sets, ways, sets_tile=128):
    return _cs.cache_sim(set_ids, tags, num_sets=num_sets, ways=ways,
                         sets_tile=sets_tile, interpret=_interpret())


@partial(jax.jit, static_argnames=("num_sets", "ways", "sets_tile"))
def cache_sim_ladder(traces, *, num_sets, ways, sets_tile=2048):
    """Batched ladder engine; ``num_sets`` is a static tuple of rung set
    counts. Returns (W, L, 2) int32 [hits, misses]."""
    return _cs.cache_sim_ladder(traces, num_sets, ways=ways,
                                sets_tile=sets_tile,
                                interpret=_interpret())
