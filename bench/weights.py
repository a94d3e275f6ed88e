"""Seeded weights of a dense decoder, in the published layout.

The benchmark makes the weights itself, so that the plain reference can
draw the very same values without taking anything from the program under
test.  Every tensor is drawn from its own key,
``fold_in(fold_in(root, group), leaf)``, where ``group`` is the layer
index (or ``GLOBAL`` for the embedding, final norm and head), so one
layer can be drawn alone and equals its slice of the whole draw bit for
bit.  Values are uniform with the stated standard deviation: a uniform
draw is plain arithmetic on random bits, so it rounds alike in any
program that computes it.

Layout (the reference's names; ``D`` hidden, ``H``/``K`` query/kv heads,
``hd`` head size, ``F`` MLP width, ``V`` vocabulary):

  per layer: attn_norm (D), wq (D,H,hd), wk (D,K,hd), wv (D,K,hd),
             [bq (H,hd), bk (K,hd), bv (K,hd)], wo (H,hd,D),
             mlp_norm (D), w_gate (D,F), w_up (D,F), w_down (F,D)
  global:    embed (V,D), final_norm (D), lm_head (D,V)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GLOBAL = 1 << 20          # key group of the non-layer tensors
NORM_SPREAD = 0.1         # norm gains are 1 +- this, uniformly
BIAS_STD = 0.5

# Stable leaf ids: a tensor's values do not change when others are added.
_LEAF_IDS = {"attn_norm": 0, "wq": 1, "wk": 2, "wv": 3, "bq": 4, "bk": 5,
             "bv": 6, "wo": 7, "mlp_norm": 8, "w_gate": 9, "w_up": 10,
             "w_down": 11, "embed": 12, "final_norm": 13, "lm_head": 14}


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, including seeds past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def layer_specs(m: dict) -> dict:
    """name -> (shape, kind, std) of one layer; ``m`` is a config's
    ``model`` block."""
    D, H, K = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd, F = m["head_dim"], m["d_ff"]
    specs = {
        "attn_norm": ((D,), "norm", None),
        "wq": ((D, H, hd), "weight", D ** -0.5),
        "wk": ((D, K, hd), "weight", D ** -0.5),
        "wv": ((D, K, hd), "weight", D ** -0.5),
        "wo": ((H, hd, D), "weight", (H * hd) ** -0.5),
        "mlp_norm": ((D,), "norm", None),
        "w_gate": ((D, F), "weight", D ** -0.5),
        "w_up": ((D, F), "weight", D ** -0.5),
        "w_down": ((F, D), "weight", F ** -0.5),
    }
    if m["qkv_bias"]:
        specs.update(bq=((H, hd), "weight", BIAS_STD),
                     bk=((K, hd), "weight", BIAS_STD),
                     bv=((K, hd), "weight", BIAS_STD))
    return specs


def global_specs(m: dict) -> dict:
    D, V = m["d_model"], m["vocab_size"]
    return {"embed": ((V, D), "weight", 1.0),
            "final_norm": ((D,), "norm", None),
            "lm_head": ((D, V), "weight", D ** -0.5)}


def _draw(key, name, shape, kind, std, dtype):
    k = jax.random.fold_in(key, _LEAF_IDS[name])
    u = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
    if kind == "norm":
        return (1.0 + NORM_SPREAD * u).astype(dtype)
    return (u * jnp.float32(std * math.sqrt(3.0))).astype(dtype)


def draw_layer(m: dict, root: jax.Array, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's tensors (``layer`` may be traced)."""
    key = jax.random.fold_in(root, layer)
    return {n: _draw(key, n, *spec, dtype)
            for n, spec in layer_specs(m).items()}


def draw_globals(m: dict, root: jax.Array, dtype=jnp.bfloat16) -> dict:
    key = jax.random.fold_in(root, GLOBAL)
    return {n: _draw(key, n, *spec, dtype)
            for n, spec in global_specs(m).items()}


def draw_stacked(m: dict, root: jax.Array, dtype=jnp.bfloat16):
    """(layers stacked on a leading axis, globals), traceable in one jit."""
    idx = jnp.arange(m["num_layers"], dtype=jnp.int32)
    layers = jax.vmap(lambda i: draw_layer(m, root, i, dtype))(idx)
    return layers, draw_globals(m, root, dtype)
