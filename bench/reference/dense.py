"""Plain float32 forward of a dense decoder: the benchmark's reference.

Pre-norm decoder as published for Phi-3 and Qwen2 (and Llama, which
both follow): RMSNorm (``x / rms(x) * g``), grouped-query attention with
optional QKV bias and RoPE in the rotate-half form, SwiGLU MLP, final
RMSNorm and an untied output head.  Straight ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: no kernel, no cache,
no batching of requests.  It imports nothing of the program under test;
its weights come from ``bench/weights.py`` by the same seed.

It runs layer by layer, drawing each layer's weights on the device, so
that a full-width model fits next to nothing else.  ``quant="fp8"``
computes the same forward with every matmul operand rounded to
float8_e4m3fn (per output channel for weights, per token for
activations): the lower-precision control that the check must reject.
``quant="bf16"`` rounds every matmul operand to bfloat16 instead, as the
served program's operands are: a witness, not a control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 256          # query rows per attention block
HEAD_BLOCK = 256       # rows per block of the output head
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _fp8(x, axes):
    """Round ``x`` to float8_e4m3fn with a scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _operand(x, quant, axes):
    if quant == "fp8":
        return _fp8(x, axes)
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """Rotate-half RoPE; x (B,T,N,hd), positions (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """q (B,T,H,hd), k/v (B,T,K,hd) -> (B,T,H,hd); query head h reads kv
    head h // (H/K)."""
    B, T, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    blk = min(T, Q_BLOCK)
    qg = q.reshape(B, T, K, G, hd) * hd ** -0.5
    kpos = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, blk, axis=1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, k)
        ok = kpos[None, :] <= (start + jnp.arange(blk))[:, None]
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", p, v)

    out = jax.lax.map(block, jnp.arange(0, T, blk))     # (n,B,blk,K,G,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, H, hd)
    return out


def layer(m, w, h, quant=None):
    """One decoder layer on the residual stream h (B,T,D), float32."""
    eps = m["rms_norm_eps"]
    T = h.shape[1]
    pos = jnp.arange(T)
    x = _operand(rms_norm(h, w["attn_norm"], eps), quant, (-1,))
    q = jnp.einsum("btd,dhk->bthk", x, _operand(w["wq"], quant, (0,)))
    k = jnp.einsum("btd,dhk->bthk", x, _operand(w["wk"], quant, (0,)))
    v = jnp.einsum("btd,dhk->bthk", x, _operand(w["wv"], quant, (0,)))
    if m["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    o = causal_attention(q, k, v)
    o = _operand(o, quant, (-2, -1))
    h = h + jnp.einsum("bthk,hkd->btd", o, _operand(w["wo"], quant, (0, 1)))
    x = _operand(rms_norm(h, w["mlp_norm"], eps), quant, (-1,))
    gate = x @ _operand(w["w_gate"], quant, (0,))
    up = x @ _operand(w["w_up"], quant, (0,))
    a = _operand(jax.nn.silu(gate) * up, quant, (-1,))
    return h + a @ _operand(w["w_down"], quant, (0,))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.lru_cache(maxsize=None)
def _programs(mkey, quant):
    m = dict(mkey)

    @jax.jit
    def embed(root, tokens):
        return _f32(W.draw_globals(m, root))["embed"][tokens]

    @jax.jit
    def step(root, i, h):
        with jax.default_matmul_precision("highest"):
            return layer(m, _f32(W.draw_layer(m, root, i)), h, quant)

    @jax.jit
    def head(root, hrows, tokens):
        """hrows (N,D) final residual rows -> per row: best logit, the
        logit of ``tokens``, and the argmax."""
        g = _f32(W.draw_globals(m, root))
        with jax.default_matmul_precision("highest"):
            x = _operand(rms_norm(hrows, g["final_norm"], m["rms_norm_eps"]),
                         quant, (-1,))
            lg = x @ _operand(g["lm_head"], quant, (0,))
        best = lg.max(axis=-1)
        at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
        return best, at, jnp.argmax(lg, axis=-1).astype(jnp.int32)

    return embed, step, head


def _mkey(m):
    return tuple(sorted(m.items()))


def final_residual(m, seed, tokens, quant=None):
    """tokens (B,T) int32 -> residual stream after the last layer."""
    embed, step, _ = _programs(_mkey(m), quant)
    root = W.root_key(seed)
    h = embed(root, jnp.asarray(tokens, jnp.int32))
    for i in range(m["num_layers"]):
        h = step(root, jnp.int32(i), h)
    return h


def head_rows(m, seed, hrows, tokens, quant=None):
    """(best, logit of tokens, argmax) for each row, in blocks of rows."""
    _, _, head = _programs(_mkey(m), quant)
    root = W.root_key(seed)
    n = hrows.shape[0]
    pad = -n % HEAD_BLOCK
    hrows = jnp.pad(hrows, ((0, pad), (0, 0)))
    tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    outs = [head(root, hrows[i:i + HEAD_BLOCK], tokens[i:i + HEAD_BLOCK])
            for i in range(0, n + pad, HEAD_BLOCK)]
    return tuple(np.concatenate([np.asarray(o[j]) for o in outs])[:n]
                 for j in range(3))
