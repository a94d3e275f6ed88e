"""Model assembly: decoder LMs (dense / MoE / VLM / SSM / hybrid) and the
Whisper-style encoder-decoder. Layer stacks are scanned (homogeneous archs)
or unrolled (whisper, recurrentgemma) per ``cfg.scan_layers``.

Modes:
  train   — full-sequence forward, logits for CE loss
  prefill — full-sequence forward, returns per-layer KV/state cache
  decode  — one token against an existing cache (``serve_step``)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamDef, ParamDefs, Params, layer_norm,
                                 rms_norm, sinusoidal_positions, softcap,
                                 stacked, subtree)
from repro.sharding import constrain

_ACT = ("batch", "seq", "embed_act")  # canonical activation sharding


def _prefix(pre: str, defs: ParamDefs) -> ParamDefs:
    return {f"{pre}/{k}": v for k, v in defs.items()}


# ---------------------------------------------------------------------------
# per-layer param defs
# ---------------------------------------------------------------------------


def _decoder_layer_defs(cfg: ModelConfig) -> ParamDefs:
    D = cfg.d_model
    defs: ParamDefs = {"ln1/g": ParamDef((D,), (None,), init="zeros")}
    if cfg.family == "ssm":
        defs.update(_prefix("ssm", ssm_mod.ssm_param_defs(cfg)))
        return defs
    defs.update(_prefix("attn", attn_mod.attn_param_defs(cfg)))
    defs["ln2/g"] = ParamDef((D,), (None,), init="zeros")
    if cfg.is_moe:
        defs.update(_prefix("moe", moe_mod.moe_param_defs(cfg)))
    else:
        defs.update(_prefix("mlp", mlp_mod.mlp_param_defs(cfg)))
    return defs


def _hybrid_layer_defs(cfg: ModelConfig, kind: str) -> ParamDefs:
    D = cfg.d_model
    defs: ParamDefs = {"ln1/g": ParamDef((D,), (None,), init="zeros"),
                       "ln2/g": ParamDef((D,), (None,), init="zeros")}
    if kind == "R":
        defs.update(_prefix("rec", rglru_mod.rglru_param_defs(cfg)))
    else:
        defs.update(_prefix("attn", attn_mod.attn_param_defs(cfg)))
    defs.update(_prefix("mlp", mlp_mod.mlp_param_defs(cfg)))
    return defs


def hybrid_pattern(cfg: ModelConfig):
    pat = cfg.block_pattern or "A"
    return [pat[i % len(pat)] for i in range(cfg.num_layers)]


def _encdec_layer_defs(cfg: ModelConfig, cross: bool) -> ParamDefs:
    D = cfg.d_model
    defs: ParamDefs = {
        "ln1/g": ParamDef((D,), (None,), init="ones"),
        "ln1/b": ParamDef((D,), (None,), init="zeros"),
        "ln2/g": ParamDef((D,), (None,), init="ones"),
        "ln2/b": ParamDef((D,), (None,), init="zeros"),
    }
    defs.update(_prefix("attn", attn_mod.attn_param_defs(cfg)))
    defs.update(_prefix("mlp", mlp_mod.mlp_param_defs(cfg)))
    if cross:
        defs["lnx/g"] = ParamDef((D,), (None,), init="ones")
        defs["lnx/b"] = ParamDef((D,), (None,), init="zeros")
        defs.update(_prefix("xattn", attn_mod.attn_param_defs(cfg, cross=True)))
    return defs


def model_param_defs(cfg: ModelConfig, max_seq: int) -> ParamDefs:
    D, V = cfg.d_model, cfg.vocab_size
    defs: ParamDefs = {
        "emb/tok": ParamDef((V, D), ("vocab", "embed"), scale=0.02),
        "final_ln/g": ParamDef((D,), (None,), init="zeros"),
    }
    if not cfg.tie_embeddings:
        defs["emb/out"] = ParamDef((D, V), ("embed", "vocab"),
                                   scale=D ** -0.5)
    if cfg.family == "encdec":
        # whisper uses LayerNorm: gamma is multiplicative (init ones)
        defs["final_ln/g"] = ParamDef((D,), (None,), init="ones")
        defs["final_ln/b"] = ParamDef((D,), (None,), init="zeros")
        defs["enc_ln/g"] = ParamDef((D,), (None,), init="ones")
        defs["enc_ln/b"] = ParamDef((D,), (None,), init="zeros")
        defs["pos/dec"] = ParamDef((max_seq, D), ("seq", "embed"), scale=0.02)
        enc = _encdec_layer_defs(cfg, cross=False)
        dec = _encdec_layer_defs(cfg, cross=True)
        for i in range(cfg.enc_layers):
            defs.update(_prefix(f"enc_{i}", enc))
        for i in range(cfg.dec_layers):
            defs.update(_prefix(f"dec_{i}", dec))
        return defs
    if cfg.family == "hybrid":
        for i, kind in enumerate(hybrid_pattern(cfg)):
            defs.update(_prefix(f"layer_{i}", _hybrid_layer_defs(cfg, kind)))
        return defs
    layer = _decoder_layer_defs(cfg)
    if cfg.scan_layers:
        defs.update(stacked(layer, cfg.num_layers, "blocks"))
    else:
        for i in range(cfg.num_layers):
            defs.update(_prefix(f"layer_{i}", layer))
    return defs


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer local-attention window (0 = global), shape (L,) int32."""
    if cfg.alt_local_global:
        w = [cfg.local_window if i % 2 == 0 else 0
             for i in range(cfg.num_layers)]
    else:
        w = [cfg.local_window] * cfg.num_layers
    return jnp.asarray(w, jnp.int32)


# ---------------------------------------------------------------------------
# cache defs
# ---------------------------------------------------------------------------


def cache_param_defs(cfg: ModelConfig, batch: int, max_len: int) -> ParamDefs:
    if cfg.family == "ssm":
        return ssm_mod.ssm_state_defs(cfg, batch, cfg.num_layers)
    if cfg.family == "hybrid":
        pat = hybrid_pattern(cfg)
        n_rec = sum(1 for k in pat if k == "R")
        n_attn = len(pat) - n_rec
        W = min(cfg.local_window or max_len, max_len)
        defs = {f"rec/{k}": v for k, v in
                rglru_mod.rglru_state_defs(cfg, batch, n_rec).items()}
        K, hd = cfg.num_kv_heads, cfg.head_dim
        defs["attn/k"] = ParamDef((n_attn, batch, W, K, hd),
                                  ("stack", "batch", "kv_seq", "kv_heads",
                                   "head_dim"), init="zeros")
        defs["attn/v"] = ParamDef((n_attn, batch, W, K, hd),
                                  ("stack", "batch", "kv_seq", "kv_heads",
                                   "head_dim"), init="zeros")
        defs["attn/pos"] = ParamDef((n_attn, batch, W),
                                    ("stack", "batch", "kv_seq"),
                                    init="const", const=-1, dtype="int32")
        return defs
    if cfg.family == "encdec":
        K, hd = cfg.num_kv_heads, cfg.head_dim
        defs: ParamDefs = {}
        for i in range(cfg.dec_layers):
            defs[f"dec_{i}/k"] = ParamDef(
                (batch, max_len, K, hd),
                ("batch", "kv_seq", "kv_heads", "head_dim"), init="zeros")
            defs[f"dec_{i}/v"] = ParamDef(
                (batch, max_len, K, hd),
                ("batch", "kv_seq", "kv_heads", "head_dim"), init="zeros")
        # per-row encoder-output bank (StateBank kind "enc"): row b holds
        # slot b's encoder output, written at admission and read by every
        # decode tick's cross-attention — whisper decodes slot-isolated
        defs["enc/out"] = ParamDef(
            (batch, max_len, cfg.d_model), ("batch", "kv_seq", "embed"),
            init="zeros")
        return defs
    return attn_mod.cache_defs(cfg, batch, max_len, cfg.num_layers)


def paged_cache_param_defs(cfg: ModelConfig, num_pages: int,
                           page_size: int) -> ParamDefs:
    """Paged-pool KV cache defs (dense/moe/vlm serve; DESIGN.md §15)."""
    if cfg.family in ("ssm", "hybrid", "encdec"):
        raise ValueError(
            f"paged KV serving not supported for family '{cfg.family}' "
            "(recurrent state / ring buffers are not paged)")
    return attn_mod.paged_cache_defs(cfg, num_pages, page_size,
                                     cfg.num_layers)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _maybe_remat(fn, cfg: ModelConfig, mode: str):
    if mode != "train" or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _decoder_layer(cfg: ModelConfig, p: Params, x, *, positions, window,
                   cache=None, cache_pos=None, return_kv=False, impl,
                   page_table=None, kv_write_mask=None, layer=None):
    """Dense/MoE/VLM/SSM layer body. Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    x = constrain(x, _ACT)
    if cfg.family == "ssm":
        h, new_state = ssm_mod.ssm_block(
            cfg, subtree(p, "ssm"), rms_norm(x, p["ln1/g"]),
            state=cache)
        return constrain(x + h, _ACT), new_state, aux
    h, new_cache = attn_mod.attention_block(
        cfg, subtree(p, "attn"), rms_norm(x, p["ln1/g"]),
        positions=positions, window=window, cache=cache,
        cache_pos=cache_pos, return_kv=return_kv, impl=impl,
        page_table=page_table, kv_write_mask=kv_write_mask, layer=layer)
    x = constrain(x + h, _ACT)
    z = rms_norm(x, p["ln2/g"])
    if cfg.is_moe:
        m, aux = moe_mod.moe_block(cfg, subtree(p, "moe"), z)
    else:
        m = mlp_mod.mlp_block(cfg, subtree(p, "mlp"), z)
    return constrain(x + m, _ACT), new_cache, aux


def _embed(cfg: ModelConfig, params: Params, tokens, vision_embeds=None):
    x = params["emb/tok"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.family == "vlm" and vision_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, vision_embeds.astype(x.dtype), (0, 0, 0))
    return constrain(x, _ACT)


def _unembed(cfg: ModelConfig, params: Params, x):
    x = constrain(rms_norm(x, params["final_ln/g"]), _ACT)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["emb/tok"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["emb/out"])
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


def _rows_at(x, last_index):
    """(B, S, D) -> (B, 1, D) at position ``last_index[b]`` of each row;
    ``x`` itself when no index is given."""
    if last_index is None:
        return x
    idx = jnp.asarray(last_index, jnp.int32)[:, None, None]
    return jnp.take_along_axis(x, idx, axis=1)


def decoder_forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,                  # (B, S) int32
    *,
    mode: str = "train",                # train | prefill | decode
    cache: Optional[Params] = None,     # flat cache dict (stacked over layers)
    cache_pos=None,                     # decode: scalar position
    vision_embeds: Optional[jax.Array] = None,
    attn_impl: str = "chunked",
    page_table=None,                    # paged serve: (B, nb) int32
    kv_write_mask=None,                 # paged suffix prefill: (B, S) bool
    last_index=None,                    # decode: (B,) int32, head rows
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (logits, new_cache, aux_loss).

    ``cache_pos`` in decode mode is either a scalar (whole batch at one
    position — the dry-run/training-eval convention) or a (B,) int32 vector
    of PER-ROW positions (the serve engine's continuous-batching tick, where
    every slot sits at a different depth; see attention.decode_attention).
    With vector positions ``attn_impl="pallas_decode"`` selects the Pallas
    blocked decode kernel with the fused in-launch KV scatter
    (kernels.decode_attention; per-layer windows ride through the layer
    scan as traced scalars); the default jnp path is its parity oracle.

    With ``page_table`` set, ``cache`` is the per-layer physical page
    pool and vector ``cache_pos`` holds each row's FIRST write position;
    ``S > 1`` is the paged *suffix prefill* (positions ``cache_pos[b] +
    s``, writes masked by ``kv_write_mask``), ``S == 1`` the paged
    decode tick, ``attn_impl="pallas_paged"`` its Pallas kernel
    (DESIGN.md §15).

    With ``last_index`` in decode mode, only position ``last_index[b]``
    of row b goes through the final norm and the output head, and the
    logits are (B, 1, V): the paged suffix prefill samples each row's
    first token from its last suffix position and needs no other.
    """
    B, S = tokens.shape
    x = _embed(cfg, params, tokens, vision_embeds)
    if mode == "decode":
        if cache_pos is not None and jnp.ndim(cache_pos) >= 1:
            positions = (jnp.asarray(cache_pos, jnp.int32)[:, None]
                         + jnp.arange(S, dtype=jnp.int32)[None, :])  # (B, S)
        else:
            positions = jnp.full((1,), cache_pos, jnp.int32)
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
    windows = layer_windows(cfg)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        return _ssm_forward(cfg, params, x, mode=mode, cache=cache)

    if cfg.scan_layers:
        blocks = subtree(params, "blocks")

        if mode == "train":
            def body(xc, xs):
                lp, w = xs
                y, _, aux = _decoder_layer(cfg, lp, xc, positions=positions,
                                           window=w, impl=attn_impl)
                return y, aux
            body = _maybe_remat(body, cfg, mode)
            x, auxs = jax.lax.scan(body, x, (blocks, windows))
            return _unembed(cfg, params, x), None, auxs.sum()

        if mode == "prefill":
            def body(xc, xs):
                lp, w = xs
                y, kv, aux = _decoder_layer(cfg, lp, xc, positions=positions,
                                            window=w, return_kv=True,
                                            impl=attn_impl)
                return y, (kv["k"], kv["v"], aux)
            x, (ck, cv, auxs) = jax.lax.scan(body, x, (blocks, windows))
            return (_unembed(cfg, params, x), {"k": ck, "v": cv}, auxs.sum())

        # decode (page_table/kv_write_mask are layer-invariant: closed
        # over).  The stacked cache rides in the carry and each layer
        # writes its own slice back in place: scanned as xs -> ys it would
        # need a second full-size cache buffer for the ys.  The paged
        # Pallas kernel takes the whole stack and the layer index and
        # aliases the stack whole, so nothing is sliced: XLA cannot alias
        # a slice of the carry into the kernel, and would copy the
        # layer's pool out and back on every layer of every tick.
        whole = attn_impl == "pallas_paged" and page_table is not None

        def body(carry, xs):
            xc, ck, cv = carry
            lp, w, i = xs
            c = {"k": ck, "v": cv} if whole else {"k": ck[i], "v": cv[i]}
            y, kv, _ = _decoder_layer(cfg, lp, xc, positions=positions,
                                      window=w, cache=c,
                                      cache_pos=cache_pos, impl=attn_impl,
                                      page_table=page_table,
                                      kv_write_mask=kv_write_mask,
                                      layer=i if whole else None)
            if whole:
                return (y, kv["k"], kv["v"]), None
            ck = jax.lax.dynamic_update_index_in_dim(ck, kv["k"], i, 0)
            cv = jax.lax.dynamic_update_index_in_dim(cv, kv["v"], i, 0)
            return (y, ck, cv), None
        layers = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
        (x, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]), (blocks, windows, layers))
        return (_unembed(cfg, params, _rows_at(x, last_index)),
                {"k": ck, "v": cv}, aux_total)

    # unrolled homogeneous stack
    new_cache: Dict[str, jax.Array] = {}
    for i in range(cfg.num_layers):
        lp = subtree(params, f"layer_{i}")
        c_i = None
        if cache is not None:
            c_i = {"k": cache["k"][i], "v": cache["v"][i]}

        def layer_fn(lp_, x_, w=int(windows[i]), c=c_i):
            return _decoder_layer(
                cfg, lp_, x_, positions=positions, window=w, cache=c,
                cache_pos=cache_pos, return_kv=(mode == "prefill"),
                impl=attn_impl, page_table=page_table,
                kv_write_mask=kv_write_mask)

        x, kv, aux = _maybe_remat(layer_fn, cfg, mode)(lp, x)
        aux_total += aux
        if kv is not None:
            new_cache.setdefault("k", []).append(kv["k"])
            new_cache.setdefault("v", []).append(kv["v"])
    out_cache = None
    if new_cache:
        out_cache = {k: jnp.stack(v) for k, v in new_cache.items()}
    return (_unembed(cfg, params, _rows_at(x, last_index)), out_cache,
            aux_total)


def _ssm_forward(cfg, params, x, *, mode, cache):
    blocks = subtree(params, "blocks")

    if mode == "train":
        def body(xc, lp):
            y, _, aux = _decoder_layer(cfg, lp, xc, positions=None,
                                       window=0, impl="chunked")
            return y, aux
        body = _maybe_remat(body, cfg, mode)
        x, auxs = jax.lax.scan(body, x, blocks)
        return _unembed(cfg, params, x), None, auxs.sum()

    if mode == "prefill":
        def body2(xc, lp):
            h, st = ssm_mod.ssm_block(cfg, subtree(lp, "ssm"),
                                      rms_norm(xc, lp["ln1/g"]), state=None)
            return constrain(xc + h, _ACT), (st["conv"], st["ssm"])
        x, (conv, ssm) = jax.lax.scan(body2, x, blocks)
        return _unembed(cfg, params, x), {"conv": conv, "ssm": ssm}, jnp.zeros((), jnp.float32)

    # decode
    def body(xc, xs):
        lp, conv_l, ssm_l = xs
        h, st = ssm_mod.ssm_block(cfg, subtree(lp, "ssm"),
                                  rms_norm(xc, lp["ln1/g"]),
                                  state={"conv": conv_l, "ssm": ssm_l})
        return constrain(xc + h, _ACT), (st["conv"], st["ssm"])
    x, (conv, ssm) = jax.lax.scan(body, x, (blocks, cache["conv"],
                                            cache["ssm"]))
    return (_unembed(cfg, params, x), {"conv": conv, "ssm": ssm},
            jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# hybrid (recurrentgemma) forward — unrolled heterogeneous stack
# ---------------------------------------------------------------------------


def hybrid_forward(cfg: ModelConfig, params: Params, tokens, *, mode="train",
                   cache=None, cache_pos=None, attn_impl="chunked"):
    """``cache_pos`` in decode mode is a scalar (whole batch at one
    position — the dry-run convention) or a (B,) int32 vector of PER-ROW
    positions (batched serve): each row then writes its k/v into its OWN
    ring slot ``cache_pos[b] % W`` and attends through
    ``attention.ring_decode_attention``'s per-row position mask, so serve
    slots at different depths stay isolated (DESIGN.md §17)."""
    vec = cache_pos is not None and jnp.ndim(cache_pos) >= 1
    cp_vec = jnp.asarray(cache_pos, jnp.int32) if vec else None
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    pat = hybrid_pattern(cfg)
    if mode == "decode":
        positions = (cp_vec[:, None] if vec
                     else jnp.full((1,), cache_pos, jnp.int32))
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
    W = cfg.local_window
    r_i = a_i = 0
    new_rec_h, new_rec_conv = [], []
    new_k, new_v, new_pos = [], [], []

    def attn_ring_decode(lp, z, idx):
        """Local attention against a ring-buffer cache of size W."""
        k_l, v_l, pos_l = (cache["attn/k"][idx], cache["attn/v"][idx],
                           cache["attn/pos"][idx])
        p = subtree(lp, "attn")
        q = jnp.einsum("bsd,dhk->bshk", z, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", z, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", z, p["wv"])
        if cfg.rope_theta:
            pos2d = positions if positions.ndim > 1 else positions[None, :]
            q = attn_mod.rope(q, pos2d, cfg.rope_theta)
            k = attn_mod.rope(k, pos2d, cfg.rope_theta)
        Wr = k_l.shape[1]
        if vec:
            rows = jnp.arange(B)
            slot = jnp.mod(cp_vec, Wr)
            k_l = k_l.at[rows, slot].set(k[:, 0].astype(k_l.dtype))
            v_l = v_l.at[rows, slot].set(v[:, 0].astype(v_l.dtype))
            pos_l = pos_l.at[rows, slot].set(cp_vec)
            out = attn_mod.ring_decode_attention(
                q, k_l, v_l, q_pos=cp_vec, k_positions=pos_l, window=W,
                logit_cap=cfg.attn_softcap)
            y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            return y, (k_l, v_l, pos_l)
        slot = jnp.mod(cache_pos, Wr)
        k_l = jax.lax.dynamic_update_slice_in_dim(
            k_l, k.astype(k_l.dtype), slot, axis=1)
        v_l = jax.lax.dynamic_update_slice_in_dim(
            v_l, v.astype(v_l.dtype), slot, axis=1)
        pos_l = jax.lax.dynamic_update_slice_in_dim(
            pos_l, jnp.full((B, 1), cache_pos, jnp.int32), slot, axis=1)
        out = attn_mod.naive_attention(
            q, k_l, v_l, causal=True, window=W, logit_cap=cfg.attn_softcap,
            q_offset=cache_pos, k_positions=pos_l[0])
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, (k_l, v_l, pos_l)

    def _train_layer(lp_, x_, kind):
        """One hybrid layer, train mode (no cache) — rematerializable."""
        z_ = rms_norm(x_, lp_["ln1/g"])
        if kind == "R":
            h_, _ = rglru_mod.rglru_block(cfg, subtree(lp_, "rec"), z_)
        else:
            h_, _ = attn_mod.attention_block(
                cfg, subtree(lp_, "attn"), z_, positions=positions,
                window=W, impl=attn_impl)
        x_ = constrain(x_ + h_, _ACT)
        return constrain(
            x_ + mlp_mod.mlp_block(cfg, subtree(lp_, "mlp"),
                                   rms_norm(x_, lp_["ln2/g"])), _ACT)

    if mode == "train":
        for i, kind in enumerate(pat):
            lp = subtree(params, f"layer_{i}")
            fn = _maybe_remat(lambda lp_, x_, k=kind: _train_layer(lp_, x_, k),
                              cfg, mode)
            x = fn(lp, x)
        return _unembed(cfg, params, x), None, jnp.zeros((), jnp.float32)

    for i, kind in enumerate(pat):
        lp = subtree(params, f"layer_{i}")
        z = rms_norm(x, lp["ln1/g"])
        if kind == "R":
            st = None
            if cache is not None:
                st = {"h": cache["rec/h"][r_i], "conv": cache["rec/conv"][r_i]}
            h, st_new = rglru_mod.rglru_block(cfg, subtree(lp, "rec"), z,
                                              state=st)
            if st_new is not None:
                new_rec_h.append(st_new["h"])
                new_rec_conv.append(st_new["conv"])
            r_i += 1
        else:
            if mode == "decode":
                h, (k_l, v_l, pos_l) = attn_ring_decode(lp, z, a_i)
                new_k.append(k_l)
                new_v.append(v_l)
                new_pos.append(pos_l)
            else:
                h, kv = attn_mod.attention_block(
                    cfg, subtree(lp, "attn"), z, positions=positions,
                    window=W, return_kv=(mode == "prefill"), impl=attn_impl)
                if kv is not None:
                    # fold the last-W keys into the ring layout
                    ks, vs = kv["k"][:, -W:], kv["v"][:, -W:]
                    kpos = jnp.maximum(jnp.arange(S - min(W, S), S), -1)
                    pad = W - min(W, S)
                    if pad:
                        ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        kpos = jnp.pad(kpos, (0, pad), constant_values=-1)
                    # ring layout: slot = pos % W; empty (pos=-1) entries go
                    # to the unused tail slots so they never clobber real kv
                    slots = jnp.where(kpos >= 0, jnp.mod(kpos, W),
                                      jnp.arange(W))
                    k_r = jnp.zeros_like(ks).at[:, slots].set(ks)
                    v_r = jnp.zeros_like(vs).at[:, slots].set(vs)
                    p_r = jnp.full((B, W), -1, jnp.int32).at[:, slots].set(
                        jnp.where(kpos >= 0, kpos, -1)[None, :])
                    new_k.append(k_r)
                    new_v.append(v_r)
                    new_pos.append(p_r)
            a_i += 1
        x = constrain(x + h, _ACT)
        x = constrain(x + mlp_mod.mlp_block(cfg, subtree(lp, "mlp"),
                                            rms_norm(x, lp["ln2/g"])), _ACT)

    new_cache = None
    if new_rec_h or new_k:
        new_cache = {}
        if new_rec_h:
            new_cache["rec/h"] = jnp.stack(new_rec_h)
            new_cache["rec/conv"] = jnp.stack(new_rec_conv)
        if new_k:
            new_cache["attn/k"] = jnp.stack(new_k)
            new_cache["attn/v"] = jnp.stack(new_v)
            new_cache["attn/pos"] = jnp.stack(new_pos)
    return _unembed(cfg, params, x), new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# encoder-decoder (whisper) forward
# ---------------------------------------------------------------------------


def _encdec_layer(cfg, p, x, *, positions, causal, enc_out=None, cache=None,
                  cache_pos=None, return_kv=False, impl):
    h, kv = attn_mod.attention_block(
        cfg, subtree(p, "attn"), layer_norm(x, p["ln1/g"], p["ln1/b"]),
        positions=positions, causal=causal, window=0, cache=cache,
        cache_pos=cache_pos, return_kv=return_kv, impl=impl)
    x = constrain(x + h, _ACT)
    if enc_out is not None:
        h, _ = attn_mod.attention_block(
            cfg, subtree(p, "xattn"), layer_norm(x, p["lnx/g"], p["lnx/b"]),
            positions=positions, kv_source=enc_out, impl=impl)
        x = constrain(x + h, _ACT)
    x = constrain(x + mlp_mod.mlp_block(cfg, subtree(p, "mlp"),
                                        layer_norm(x, p["ln2/g"], p["ln2/b"])),
                  _ACT)
    return x, kv


def encoder_forward(cfg: ModelConfig, params: Params, frames: jax.Array,
                    attn_impl="chunked", train: bool = False) -> jax.Array:
    """frames: (B, Se, D) stub embeddings (conv frontend is a stub)."""
    B, Se, D = frames.shape
    x = frames.astype(jnp.dtype(cfg.dtype)) + sinusoidal_positions(
        Se, D).astype(jnp.dtype(cfg.dtype))[None]
    x = constrain(x, _ACT)
    positions = jnp.arange(Se, dtype=jnp.int32)
    for i in range(cfg.enc_layers):
        def layer_fn(lp_, x_):
            return _encdec_layer(cfg, lp_, x_, positions=positions,
                                 causal=False, impl=attn_impl)
        x, _ = _maybe_remat(layer_fn, cfg, "train" if train else "eval")(
            subtree(params, f"enc_{i}"), x)
    return layer_norm(x, params["enc_ln/g"], params["enc_ln/b"])


def encdec_forward(cfg: ModelConfig, params: Params, tokens, *, frames=None,
                   enc_out=None, mode="train", cache=None, cache_pos=None,
                   attn_impl="chunked"):
    """Decoder (+ optional encoder) forward. Returns (logits, cache, aux).

    ``cache_pos`` in decode mode is a scalar (dry-run convention) or a
    (B,) int32 vector of PER-ROW positions (batched serve): each row then
    takes its own learned-position slice ``pos/dec[cache_pos[b]]``, its
    self-attention KV writes land at its own row position (the per-layer
    ``dec_i/*`` banks have batch axis 0), and — when ``enc_out`` is not
    given — cross-attention reads the per-row ``enc/out`` bank from the
    cache, so each slot decodes against ITS OWN encoder output
    (DESIGN.md §17)."""
    if enc_out is None and frames is not None:
        enc_out = encoder_forward(cfg, params, frames, attn_impl,
                                  train=(mode == "train"))
    if enc_out is None and cache is not None and "enc/out" in cache:
        enc_out = cache["enc/out"]
    B, S = tokens.shape
    vec = cache_pos is not None and jnp.ndim(cache_pos) >= 1
    if mode == "decode":
        if vec:
            cp = jnp.asarray(cache_pos, jnp.int32)
            positions = cp[:, None]                       # (B, 1)
            pos_emb = params["pos/dec"][cp][:, None]      # (B, 1, D)
        else:
            positions = jnp.full((1,), cache_pos, jnp.int32)
            pos_emb = jax.lax.dynamic_slice_in_dim(
                params["pos/dec"], cache_pos, 1, axis=0)[None]
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
        pos_emb = params["pos/dec"][:S][None]
    x = constrain(params["emb/tok"][tokens].astype(jnp.dtype(cfg.dtype))
                  + pos_emb, _ACT)
    new_cache: Dict[str, jax.Array] = {}
    for i in range(cfg.dec_layers):
        c_i = None
        if cache is not None:
            c_i = {"k": cache[f"dec_{i}/k"], "v": cache[f"dec_{i}/v"]}

        def layer_fn(lp_, x_, enc_, c=c_i):
            return _encdec_layer(
                cfg, lp_, x_, positions=positions, causal=True, enc_out=enc_,
                cache=c, cache_pos=cache_pos,
                return_kv=(mode == "prefill"), impl=attn_impl)

        x, kv = _maybe_remat(layer_fn, cfg, mode)(
            subtree(params, f"dec_{i}"), x, enc_out)
        if kv is not None:
            new_cache[f"dec_{i}/k"] = kv["k"]
            new_cache[f"dec_{i}/v"] = kv["v"]
    if new_cache and cache is not None and "enc/out" in cache:
        # pass the enc bank through unchanged so the decode cache pytree
        # keeps a stable structure (the serve window donates it as a carry)
        new_cache["enc/out"] = cache["enc/out"]
    x = layer_norm(x, params["final_ln/g"], params["final_ln/b"])
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["emb/tok"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["emb/out"])
    return (logits.astype(jnp.float32), new_cache or None,
            jnp.zeros((), jnp.float32))
