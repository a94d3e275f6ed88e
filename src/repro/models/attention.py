"""GQA attention: flash-style chunked jnp implementation (XLA path) with
causal/local masking, logit soft-capping, RoPE, and KV-cache prefill/decode.

The Pallas TPU kernels in ``repro.kernels.flash_attention`` (prefill
shapes) and ``repro.kernels.decode_attention`` (the batched-serve decode
tick, selected with ``impl="pallas_decode"``) implement the same contracts
for the hardware target; ``repro.kernels.ref`` oracles match this module.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models.common import ParamDef, ParamDefs, Params, rope, softcap

NEG_INF = -2.0e38


def attn_param_defs(cfg: ModelConfig, cross: bool = False) -> ParamDefs:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    defs: ParamDefs = {
        "wq": ParamDef((D, H, hd), ("qkv_in", "heads", "head_dim")),
        "wk": ParamDef((D, K, hd), ("qkv_in", "kv_heads", "head_dim")),
        "wv": ParamDef((D, K, hd), ("qkv_in", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, D), ("heads", "head_dim", "qkv_in")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((K, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((K, hd), ("kv_heads", "head_dim"), init="zeros")
    return defs


def _mask_bias(q_pos, k_pos, *, causal: bool, window, kv_len) -> jax.Array:
    """Additive mask bias (0 or NEG_INF). q_pos (Sq,), k_pos (Bk,).

    ``window`` may be a python int (0 = global) or a traced scalar (scanned
    stacks with per-layer windows; <= 0 means global).
    """
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if isinstance(window, int):
        if window > 0:
            ok &= k_pos[None, :] > q_pos[:, None] - window
    elif window is not None:
        w = jnp.asarray(window)
        ok &= (w <= 0) | (k_pos[None, :] > q_pos[:, None] - w)
    if kv_len is not None:
        ok &= k_pos[None, :] < kv_len
    ok &= k_pos[None, :] >= 0  # ring-buffer slots may carry pos = -1 (empty)
    return jnp.where(ok, 0.0, NEG_INF)


def _blocked_kv(k, v, kv_block):
    B, Skv, K, hd = k.shape
    nblk = -(-Skv // kv_block)
    pad = nblk * kv_block - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, kv_block, K, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, kv_block, K, hd).transpose(1, 0, 2, 3, 4)
    return kb, vb, nblk


def _flash_fwd_scan(q, k, v, win, qoff, kvlen, causal, logit_cap, kv_block,
                    p_bf16=False):
    """Forward flash scan. win/qoff/kvlen are f32 scalars (may be traced).

    Returns (out f32 (B,Sq,K,G,hd), lse (B,Sq,K,G)).
    """
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = hd ** -0.5
    qr = q.reshape(B, Sq, K, G, hd).astype(jnp.float32) * scale
    q_pos = qoff + jnp.arange(Sq, dtype=jnp.float32)
    kb, vb, _ = _blocked_kv(k, v, kv_block)

    def body(carry, blk):
        m, l, acc, j = carry
        kj, vj = blk
        logits = jnp.einsum("bskgh,btkh->bskgt", qr, kj.astype(jnp.float32))
        logits = softcap(logits, logit_cap)
        k_pos = j * kv_block + jnp.arange(kv_block, dtype=jnp.float32)
        bias = _mask_bias(q_pos, k_pos, causal=causal, window=win,
                          kv_len=kvlen)
        logits = logits + bias[None, :, None, None, :]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = p.astype(jnp.bfloat16) if p_bf16 else p
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgt,btkh->bskgh", pv, vj.astype(pv.dtype)
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new, j + 1.0), None

    m0 = jnp.full((B, Sq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, K, G, hd), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, jnp.float32(0)),
                                     (kb, vb))
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def flash_attention_jnp(q, k, v, win, qoff, kvlen, causal, logit_cap,
                        kv_block, p_bf16=False):
    """Flash attention with a flash-style backward (blockwise recompute).

    Forward saves only (q, k, v, O, LSE); backward re-streams KV blocks,
    recomputes P, and accumulates dq/dk/dv — the FlashAttention-2 algorithm
    expressed in XLA. The Pallas kernel (repro.kernels.flash_attention) is
    the TPU-native version of this same contract. win/qoff/kvlen are f32
    scalar arrays (traced-safe: per-layer windows and decode positions).
    """
    out, _ = _flash_fwd_scan(q, k, v, win, qoff, kvlen, causal, logit_cap,
                             kv_block, p_bf16)
    B, Sq, H, hd = q.shape
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _flash_fwd_rule(q, k, v, win, qoff, kvlen, causal, logit_cap, kv_block,
                    p_bf16=False):
    out, lse = _flash_fwd_scan(q, k, v, win, qoff, kvlen, causal, logit_cap,
                               kv_block, p_bf16)
    B, Sq, H, hd = q.shape
    o = out.reshape(B, Sq, H, hd).astype(q.dtype)
    return o, (q, k, v, out, lse, win, qoff, kvlen)


def _flash_bwd_rule(causal, logit_cap, kv_block, p_bf16, res, do):
    q, k, v, out, lse, win, qoff, kvlen = res
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = hd ** -0.5
    qr = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    dor = do.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    delta = jnp.sum(dor * out, axis=-1)                 # (B,Sq,K,G)
    q_pos = qoff + jnp.arange(Sq, dtype=jnp.float32)
    kb, vb, nblk = _blocked_kv(k, v, kv_block)

    def body(carry, blk):
        dq_acc, j = carry
        kj, vj = blk                                    # (B,Bk,K,hd)
        kjf, vjf = kj.astype(jnp.float32), vj.astype(jnp.float32)
        s_raw = jnp.einsum("bskgh,btkh->bskgt", qr * scale, kjf)
        s = softcap(s_raw, logit_cap)
        k_pos = j * kv_block + jnp.arange(kv_block, dtype=jnp.float32)
        bias = _mask_bias(q_pos, k_pos, causal=causal, window=win,
                          kv_len=kvlen)
        p = jnp.exp(s + bias[None, :, None, None, :] - lse[..., None])
        if p_bf16:
            p = p.astype(jnp.bfloat16).astype(jnp.float32)
        dp = jnp.einsum("bskgh,btkh->bskgt", dor, vjf)
        ds = p * (dp - delta[..., None])
        if logit_cap:
            # d softcap(s_raw) = 1 - tanh^2(s_raw/cap)
            t = jnp.tanh(s_raw / logit_cap)
            ds = ds * (1.0 - t * t)
        dq_blk = jnp.einsum("bskgt,btkh->bskgh", ds, kjf) * scale
        dk_blk = jnp.einsum("bskgt,bskgh->btkh", ds, qr) * scale
        dv_blk = jnp.einsum("bskgt,bskgh->btkh", p, dor)
        return (dq_acc + dq_blk, j + 1.0), (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Sq, K, G, hd), jnp.float32)
    body = jax.checkpoint(body)
    with jax.named_scope("flash_attention_bwd"):
        (dq, _), (dkb, dvb) = jax.lax.scan(body, (dq0, jnp.float32(0)),
                                           (kb, vb))
    dq = dq.reshape(B, Sq, H, hd).astype(q.dtype)
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, nblk * kv_block, K, hd)
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, nblk * kv_block, K, hd)
    dk = dk[:, :Skv].astype(k.dtype)
    dv = dv[:, :Skv].astype(v.dtype)
    zero = jnp.zeros((), jnp.float32)
    return dq, dk, dv, zero, zero, zero


flash_attention_jnp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def chunked_attention(
    q: jax.Array,          # (B, Sq, H, hd)
    k: jax.Array,          # (B, Skv, K, hd)
    v: jax.Array,          # (B, Skv, K, hd)
    *,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    q_offset=0,            # int or traced scalar: position of q[0]
    kv_len=None,           # valid prefix length of k/v (decode cache)
    kv_block: int = 512,
    p_bf16: bool = False,  # bf16 probability matrices (halves P traffic)
) -> jax.Array:
    """Flash-style attention (custom-vjp; see flash_attention_jnp)."""
    Skv = k.shape[1]
    win = jnp.asarray(0 if window is None else window, jnp.float32)
    qoff = jnp.asarray(q_offset, jnp.float32)
    kvlen = jnp.asarray(Skv if kv_len is None else kv_len, jnp.float32)
    with jax.named_scope("flash_attention"):
        return flash_attention_jnp(q, k, v, win, qoff, kvlen, causal,
                                   logit_cap, kv_block, p_bf16)


def decode_attention(q, k, v, *, pos, window=0, logit_cap=0.0) -> jax.Array:
    """Single-new-token attention with PER-ROW cache positions (serving).

    q: (B, 1, H, hd); k/v: (B, L, K, hd) full cache buffers; pos: (B,) int32
    — row b attends key indices <= pos[b] (and inside its local window when
    ``window`` > 0; ``window`` may be a python int or a traced per-layer
    scalar). Rows are fully independent: the mask never admits entries past
    a row's own position, so stale KV from freed slots or not-yet-written
    future positions cannot leak into any live sequence — the invariant the
    serve engine's slot isolation rests on (DESIGN.md §11).
    """
    B, Sq, H, hd = q.shape
    L, K = k.shape[1], k.shape[2]
    G = H // K
    qr = q.reshape(B, K, G, hd).astype(jnp.float32) * hd ** -0.5
    logits = jnp.einsum("bkgh,btkh->bkgt", qr, k.astype(jnp.float32))
    logits = softcap(logits, logit_cap)
    k_idx = jnp.arange(L, dtype=jnp.int32)
    ok = k_idx[None, :] <= pos[:, None]
    if isinstance(window, int):
        if window > 0:
            ok &= k_idx[None, :] > pos[:, None] - window
    elif window is not None:
        w = jnp.asarray(window, jnp.int32)
        ok &= (w <= 0) | (k_idx[None, :] > pos[:, None] - w)
    logits = logits + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def ring_decode_attention(q, k, v, *, q_pos, k_positions, window=0,
                          logit_cap=0.0) -> jax.Array:
    """Single-new-token attention over PER-ROW ring-buffer caches (the
    hybrid family's batched-serve decode tick).

    q: (B, 1, H, hd); k/v: (B, W, K, hd) ring buffers; q_pos: (B,) int32
    per-row query positions; k_positions: (B, W) int32 per-row slot
    positions (-1 = empty slot).  Row b attends slots with
    ``0 <= k_positions[b, t] <= q_pos[b]`` inside its local window —
    the per-row generalization of ``naive_attention``'s shared
    ``k_positions`` vector, keeping the ring's empty-slot guard
    (``pos >= 0``) so a freshly reset ring contributes nothing.  Rows
    are fully independent, the same slot-isolation invariant as
    ``decode_attention`` (DESIGN.md §11/§17).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qr = q.reshape(B, K, G, hd).astype(jnp.float32) * hd ** -0.5
    logits = jnp.einsum("bkgh,btkh->bkgt", qr, k.astype(jnp.float32))
    logits = softcap(logits, logit_cap)
    kp = jnp.asarray(k_positions, jnp.int32)
    qp = jnp.asarray(q_pos, jnp.int32)
    ok = (kp <= qp[:, None]) & (kp >= 0)
    if isinstance(window, int):
        if window > 0:
            ok &= kp > qp[:, None] - window
    elif window is not None:
        w = jnp.asarray(window, jnp.int32)
        ok &= (w <= 0) | (kp > qp[:, None] - w)
    logits = logits + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def paged_suffix_attention(q, k, v, *, q_pos, window=0,
                           logit_cap=0.0) -> jax.Array:
    """Suffix-prefill attention over a row-linearized paged cache.

    q (B,S,H,hd) suffix queries; k/v (B,L,K,hd) caches gathered through
    each row's page table that ALREADY hold the suffix rows at their
    positions; q_pos (B,S) global query positions — row-varying because
    each suffix starts at that row's shared-prefix length (DESIGN.md
    §15).  Generalizes ``decode_attention`` to S queries per row: query
    (b, s) attends ``k_idx <= q_pos[b, s]`` inside its window, which is
    both the causal mask within the suffix and the guard that hides
    TRASH-page rows past the row's own depth.
    """
    B, S, H, hd = q.shape
    L, K = k.shape[1], k.shape[2]
    G = H // K
    qr = q.reshape(B, S, K, G, hd).astype(jnp.float32) * hd ** -0.5
    logits = jnp.einsum("bskgh,btkh->bskgt", qr, k.astype(jnp.float32))
    logits = softcap(logits, logit_cap)
    k_idx = jnp.arange(L, dtype=jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    ok = k_idx[None, None, :] <= q_pos[:, :, None]
    if isinstance(window, int):
        if window > 0:
            ok &= k_idx[None, None, :] > q_pos[:, :, None] - window
    elif window is not None:
        w = jnp.asarray(window, jnp.int32)
        ok &= (w <= 0) | (k_idx[None, None, :] > q_pos[:, :, None] - w)
    logits = logits + jnp.where(ok, 0.0, NEG_INF)[:, :, None, None, :]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgt,btkh->bskgh", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def naive_attention(q, k, v, *, causal=True, window=0, logit_cap=0.0,
                    q_offset=0, kv_len=None, k_positions=None) -> jax.Array:
    """Reference O(S^2)-memory attention (oracle, tiny smoke configs, and
    ring-buffer decode where key slots carry explicit positions)."""
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    qr = q.reshape(B, Sq, K, G, hd).astype(jnp.float32) * hd ** -0.5
    logits = jnp.einsum("bskgh,btkh->bskgt", qr, k.astype(jnp.float32))
    logits = softcap(logits, logit_cap)
    k_pos = k_positions if k_positions is not None else jnp.arange(Skv)
    bias = _mask_bias(q_offset + jnp.arange(Sq), k_pos,
                      causal=causal, window=window, kv_len=kv_len)
    logits = logits + bias[None, :, None, None, :]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgt,btkh->bskgh", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def attention_block(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                      # (B, S, D)
    *,
    positions: jax.Array,              # (B, S) or (S,)
    causal: bool = True,
    window: int = 0,
    cache: Optional[Dict[str, jax.Array]] = None,  # {"k","v"}: (B,Smax,K,hd)
    cache_pos=None,                    # decode: scalar write index
    kv_source: Optional[jax.Array] = None,  # cross-attention source (B,Skv,D)
    return_kv: bool = False,           # prefill: return computed k/v as cache
    impl: str = "chunked",
    page_table=None,                   # paged serve: (B, nb) int32
    kv_write_mask=None,                # paged suffix prefill: (B, S) bool
    layer=None,                        # pallas_paged: index into stacked cache
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """One attention op incl. projections, RoPE, cache handling.

    With ``impl="pallas_paged"``, ``cache`` holds the layer-stacked pools
    (L, P, ps, K, hd), ``layer`` (int32, may be traced) picks this layer,
    and the returned cache is the updated stacks."""
    B, S, D = x.shape
    src = kv_source if kv_source is not None else x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.rope_theta and kv_source is None:
        pos = positions if positions.ndim > 1 else positions[None, :]
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)

    new_cache = None
    kv_len = None
    q_offset = 0
    if cache is not None and cache_pos is not None \
            and jnp.ndim(cache_pos) >= 1:
        # batched-serve decode: row b writes its k/v at its OWN position
        # cache_pos[b] (never another row's — the seed engine's shared
        # scalar position broadcast every write across all slots) and
        # attends its own prefix via the per-row mask in decode_attention.
        cp = jnp.asarray(cache_pos, jnp.int32)
        if page_table is not None:
            # paged serve (DESIGN.md §15): KV lives in a shared physical
            # page pool (P, ps, K, hd) per layer; row b's logical page i
            # maps to page_table[b, i].  The LAST pool page is the
            # reserved TRASH target — masked/out-of-range writes land
            # there (always finite values, so masked softmax terms stay
            # exact zeros) and the per-row mask keeps it unreadable.
            pt = jnp.asarray(page_table, jnp.int32)
            if impl == "pallas_paged":
                if S != 1:
                    raise ValueError(
                        "attn_impl='pallas_paged' is the single-token "
                        "decode kernel; suffix prefill uses the jnp "
                        "gather path (attn_impl='paged')")
                win = jnp.asarray(0 if window is None else window,
                                  jnp.int32)
                # cache holds the layer-stacked pools: the kernel writes
                # layer `layer` in place and returns the whole stacks
                o, ck, cv = kernel_ops.paged_decode_attention_fused(
                    q[:, 0], cache["k"], cache["v"],
                    k[:, 0].astype(cache["k"].dtype),
                    v[:, 0].astype(cache["v"].dtype),
                    pt, cp, win, layer, logit_cap=cfg.attn_softcap)
                out = o[:, None]
            else:
                Pn, ps = cache["k"].shape[0], cache["k"].shape[1]
                nbl = pt.shape[1]
                trash = Pn - 1
                # jnp gather path (= the kernel's parity oracle): scatter
                # this step's S rows through the page table, gather each
                # row's pages into a linear (B, nb*ps) cache, attend with
                # per-row positions.  cp (B,) is each row's FIRST write
                # position (suffix start; decode is the S == 1 case).
                wp = cp[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
                valid = jnp.ones((B, S), bool) if kv_write_mask is None \
                    else jnp.asarray(kv_write_mask, bool)
                valid &= wp < nbl * ps
                rows = jnp.arange(B)[:, None]
                page = jnp.where(
                    valid,
                    pt[rows, jnp.clip(wp // ps, 0, nbl - 1)], trash)
                rowi = wp % ps
                ck = cache["k"].at[page, rowi].set(
                    k.astype(cache["k"].dtype))
                cv = cache["v"].at[page, rowi].set(
                    v.astype(cache["v"].dtype))
                lin_shape = (B, nbl * ps) + cache["k"].shape[2:]
                lin_k = ck[pt].reshape(lin_shape)
                lin_v = cv[pt].reshape(lin_shape)
                if S == 1:
                    out = decode_attention(q, lin_k, lin_v, pos=cp,
                                           window=window,
                                           logit_cap=cfg.attn_softcap)
                else:
                    out = paged_suffix_attention(
                        q, lin_k, lin_v, q_pos=wp, window=window,
                        logit_cap=cfg.attn_softcap)
            y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            return y, {"k": ck, "v": cv}
        if impl == "pallas_decode":
            # Pallas hot path: the KV scatter happens INSIDE the kernel
            # launch (aliased cache blocks), replacing the separate
            # per-layer .at[rows, cp].set pass; the jnp path below is
            # the parity oracle (kernels run interpret=True on CPU).
            win = jnp.asarray(0 if window is None else window, jnp.int32)
            o, ck, cv = kernel_ops.decode_attention_fused(
                q[:, 0], cache["k"], cache["v"],
                k[:, 0].astype(cache["k"].dtype),
                v[:, 0].astype(cache["v"].dtype),
                cp, win, logit_cap=cfg.attn_softcap)
            out = o[:, None]
        else:
            rows = jnp.arange(B)
            ck = cache["k"].at[rows, cp].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, cp].set(
                v[:, 0].astype(cache["v"].dtype))
            out = decode_attention(q, ck, cv, pos=cp, window=window,
                                   logit_cap=cfg.attn_softcap)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, {"k": ck, "v": cv}
    if cache is not None and cache_pos is not None:
        # decode: write this step's k/v at cache_pos, attend over prefix
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(
            cache["k"].dtype), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(
            cache["v"].dtype), cache_pos, axis=1)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        kv_len = cache_pos + 1
        q_offset = cache_pos
    elif return_kv:
        new_cache = {"k": k, "v": v}  # prefill: engine pads to max_len

    if impl == "pallas_decode":
        raise ValueError(
            "attn_impl='pallas_decode' is the batched-serve decode kernel "
            "(per-row cache_pos vectors); use 'chunked' or 'naive' for "
            "train/prefill/scalar-decode")
    if impl.startswith("chunked"):
        out = chunked_attention(
            q, k, v, causal=causal and kv_source is None, window=window,
            logit_cap=cfg.attn_softcap, q_offset=q_offset, kv_len=kv_len,
            p_bf16=impl.endswith("bf16"))
    else:
        out = naive_attention(
            q, k, v, causal=causal and kv_source is None, window=window,
            logit_cap=cfg.attn_softcap, q_offset=q_offset, kv_len=kv_len)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def cache_defs(cfg: ModelConfig, batch: int, max_len: int,
               layers: int) -> ParamDefs:
    """KV cache ParamDefs (stacked over layers)."""
    K, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (layers, batch, max_len, K, hd)
    axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {
        "k": ParamDef(shape, axes, init="zeros"),
        "v": ParamDef(shape, axes, init="zeros"),
    }


def paged_cache_defs(cfg: ModelConfig, num_pages: int, page_size: int,
                     layers: int) -> ParamDefs:
    """Paged KV pool ParamDefs (stacked over layers; DESIGN.md §15).

    One physical pool per layer, ``(num_pages, page_size, K, hd)``;
    slots address it through per-slot page tables held by the serve
    engine.  ``num_pages`` INCLUDES the reserved trailing TRASH page
    (index ``num_pages - 1``) that absorbs masked writes.
    """
    K, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (layers, num_pages, page_size, K, hd)
    axes = ("layers", "kv_pages", "kv_page_rows", "kv_heads", "head_dim")
    return {
        "k": ParamDef(shape, axes, init="zeros"),
        "v": ParamDef(shape, axes, init="zeros"),
    }
