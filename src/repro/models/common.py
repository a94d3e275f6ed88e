"""Parameter system + shared neural-net primitives.

Parameters live in a FLAT dict keyed by '/'-separated path; a parallel dict
maps each path to its logical-axes tuple (consumed by ``repro.sharding``).
Layer stacks that are scanned carry a leading "layers" dim.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]
Axes = Dict[str, Tuple[Optional[str], ...]]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | const
    scale: Optional[float] = None  # None -> 1/sqrt(fan_in) for normal
    const: float = 0.0
    dtype: Optional[str] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


ParamDefs = Dict[str, ParamDef]


def _fan_in(shape: Tuple[int, ...]) -> int:
    # heuristically treat all but the last dim as fan-in for >=2D weights
    if len(shape) <= 1:
        return shape[0] if shape else 1
    return int(np.prod(shape[:-1]))


def materialize(defs: ParamDefs, key: jax.Array, dtype: str,
                shardings: Optional[Dict[str, jax.sharding.Sharding]] = None
                ) -> Params:
    """Draw every tensor in one jitted program, directly in its dtype.

    No tensor is drawn in float32 and cast, and with ``shardings`` (path
    -> Sharding) each lands where it will live, so no device ever holds
    a second copy; without, all land on the default device.
    """
    names = sorted(defs)

    def draw(key):
        keys = jax.random.split(key, max(len(names), 1))
        out = {}
        for name, k in zip(names, keys):
            d = defs[name]
            dt = jnp.dtype(d.dtype or dtype)
            if d.init == "zeros":
                out[name] = jnp.zeros(d.shape, dt)
            elif d.init == "ones":
                out[name] = jnp.ones(d.shape, dt)
            elif d.init == "const":
                out[name] = jnp.full(d.shape, d.const, dt)
            else:
                scale = (d.scale if d.scale is not None
                         else _fan_in(d.shape) ** -0.5)
                out[name] = jax.random.normal(k, d.shape, dt) * jnp.asarray(
                    scale, dt)
        return out

    return jax.jit(draw, out_shardings=shardings)(key)


def abstract(defs: ParamDefs, dtype: str) -> Params:
    return {
        name: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or dtype))
        for name, d in defs.items()
    }


def axes_of(defs: ParamDefs) -> Axes:
    return {name: d.axes for name, d in defs.items()}


def stacked(defs: ParamDefs, n: int, prefix: str) -> ParamDefs:
    """Stack per-layer defs with a leading scanned "layers" dim."""
    return {
        f"{prefix}/{k}": dataclasses.replace(
            d, shape=(n,) + d.shape, axes=("layers",) + d.axes)
        for k, d in defs.items()
    }


def subtree(params: Params, prefix: str) -> Params:
    pre = prefix + "/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1).astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    pos = np.arange(length)[:, None]
    div = np.exp(-np.log(10000.0) * np.arange(0, dim, 2) / dim)
    out = np.zeros((length, dim), np.float32)
    out[:, 0::2] = np.sin(pos * div)
    out[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(out)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  final_cap: float = 0.0) -> jax.Array:
    logits = softcap(logits.astype(jnp.float32), final_cap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
