"""The program's dense decoder, built from a benchmark configuration and
given the benchmark's weights.

The program is assembled from its public pieces (``get_config``,
``build_model``); its parameter tree is filled from ``bench/weights.py``
in one jitted call on the device, in the served dtype.  The program's
RMSNorm scales by ``1 + g``, so its gains are the published gains minus
one (exact in bfloat16 for gains near 1).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import weights as W

# model-block key -> program config field
_SIZES = {"num_layers": "num_layers", "d_model": "d_model",
          "num_heads": "num_heads", "num_kv_heads": "num_kv_heads",
          "head_dim": "head_dim", "d_ff": "d_ff", "vocab_size": "vocab_size",
          "qkv_bias": "qkv_bias", "rope_theta": "rope_theta"}

_LAYER = {"attn_norm": "ln1/g", "mlp_norm": "ln2/g", "wq": "attn/wq",
          "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo", "bq": "attn/bq",
          "bk": "attn/bk", "bv": "attn/bv", "w_gate": "mlp/w_gate",
          "w_up": "mlp/w_up", "w_down": "mlp/w_down"}
_GLOBAL = {"embed": "emb/tok", "lm_head": "emb/out", "final_norm": "final_ln/g"}
_GAINS = {"attn_norm", "mlp_norm", "final_norm"}


def program_config(conf: dict):
    """The registry config with the benchmark's overrides, checked
    against the sizes the benchmark's reference uses."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["overrides"])
    m = conf["model"]
    wrong = {k: (m[k], getattr(cfg, f)) for k, f in _SIZES.items()
             if m[k] != getattr(cfg, f)}
    if wrong or cfg.family != "dense" or cfg.tie_embeddings \
            or cfg.mlp_act != "silu" or not cfg.gated_mlp \
            or cfg.local_window or cfg.attn_softcap or cfg.final_softcap:
        raise ValueError(f"program config {cfg.arch} departs from "
                         f"{conf['name']}: {wrong or cfg}")
    return cfg


def build_model(conf: dict, max_len: int):
    from repro.models import build_model as build
    return build(program_config(conf), max_seq=max_len)


def draw_params(conf: dict, model, seed: int) -> dict:
    """The program's parameter tree, drawn on the device in one call."""
    m = conf["model"]
    dtype = jnp.dtype(model.cfg.dtype)

    def gain(name, a):
        return (a.astype(jnp.float32) - 1.0).astype(dtype) \
            if name in _GAINS else a

    def draw(root):
        layers, glob = W.draw_stacked(m, root, dtype)
        out = {f"blocks/{_LAYER[n]}": gain(n, a) for n, a in layers.items()}
        out.update({_GLOBAL[n]: gain(n, a) for n, a in glob.items()})
        return out

    want = {k: d.shape for k, d in model.param_defs.items()}
    got = {k: a.shape for k, a in
           jax.eval_shape(draw, W.root_key(0)).items()}
    if want != got:
        raise ValueError(f"parameter tree mismatch: program {want} "
                         f"vs benchmark {got}")
    return jax.jit(draw)(W.root_key(seed))
