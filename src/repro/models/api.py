"""Public model API: build_model(cfg) -> Model with init/loss/prefill/decode,
plus ``input_specs(cfg, shape)`` producing ShapeDtypeStruct stand-ins for
every (architecture x input-shape) dry-run cell.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tf
from repro.models.common import (Axes, ParamDefs, Params, abstract, axes_of,
                                 cross_entropy, materialize)


@dataclasses.dataclass(frozen=True)
class StateBank:
    """One named per-slot state bank — the serve stack's cache contract.

    The serve engines treat a model's decode cache as a *pytree of
    banks*: the flat dict returned by ``Model.cache_defs`` / carried
    through ``decode_step``, with one ``StateBank`` describing each
    array.  The canonical bank contract is:

      * Every bank has a slot axis at ``batch_axis``; row ``b`` belongs
        exclusively to serve slot ``b``.  A decode step only reads and
        writes its own row — rows are computationally independent, so a
        row-masked merge/reset leaves every other slot's state bitwise
        unchanged (the invariant behind continuous batching, preemption,
        quarantine, and the hypothesis isolation tests).
      * ``kind`` fixes the lifecycle the engine applies to the bank:

        - ``"kv"``: positioned KV rows with a sequence axis at
          ``seq_axis``.  Prefill scatters positions ``[0, len)`` along
          that axis; decode writes at the row's own position and reads
          are position-guarded (``decode_attention``), so stale entries
          from a freed slot are unreadable and no reset is needed.
        - ``"recurrent"``: positionless recurrent state (SSD conv/state,
          RG-LRU hidden state).  Every decode step rewrites the whole
          row, so the engine must merge decode results under the active
          mask (frozen rows stay bitwise frozen), prefill is a masked
          per-token scan, and slot admit/free re-initializes the row.
        - ``"ring"``: ring-buffer KV whose slot-position entries (or the
          ``pos`` bank guarding them) wrap modulo the window.  Treated
          like ``"recurrent"`` — a new occupant could otherwise read a
          stale in-window entry — plus reads honor the ``pos >= 0``
          empty-slot guard.
        - ``"enc"``: encoder output written once per row at admission
          and passed through decode unchanged (whisper cross-attention
          source).  Reset by full-row overwrite at the next admit.

      * All banks with a ``seq_axis`` satisfy ``batch_axis < seq_axis``
        (the engines' generic masked scatter relies on it).
    """

    name: str
    kind: str            # "kv" | "recurrent" | "ring" | "enc"
    batch_axis: int
    seq_axis: Optional[int] = None

    def __post_init__(self):
        if self.kind not in ("kv", "recurrent", "ring", "enc"):
            raise ValueError(f"unknown bank kind {self.kind!r}")
        if self.seq_axis is not None and self.batch_axis >= self.seq_axis:
            raise ValueError(
                f"bank {self.name!r}: batch_axis {self.batch_axis} must "
                f"precede seq_axis {self.seq_axis}")


# Which serve engines can host each family (satellite of DESIGN.md §17):
# "dense" = Engine/EngineReference slot caches, "paged" = PagedEngine page
# pools.  Paged stays KV-decoder-only by design — pages hold positioned KV
# rows, which recurrent/ring/encoder banks do not have.
_FAMILY_SERVE_MODES: Dict[str, frozenset] = {
    "dense": frozenset({"dense", "paged"}),
    "moe": frozenset({"dense", "paged"}),
    "vlm": frozenset({"dense", "paged"}),
    "ssm": frozenset({"dense"}),
    "hybrid": frozenset({"dense"}),
    "encdec": frozenset({"dense"}),
}


def serve_families(mode: str) -> Tuple[str, ...]:
    """Families servable under engine ``mode`` ("dense" | "paged")."""
    return tuple(sorted(f for f, m in _FAMILY_SERVE_MODES.items()
                        if mode in m))


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    max_seq: int
    param_defs: ParamDefs

    # ---- params ---------------------------------------------------------
    def init(self, key: jax.Array, shardings=None) -> Params:
        """Seeded random parameters; ``shardings`` (path -> Sharding)
        places each where it lives (see ``materialize``)."""
        return materialize(self.param_defs, key, self.cfg.dtype, shardings)

    def abstract_params(self) -> Params:
        return abstract(self.param_defs, self.cfg.dtype)

    def param_axes(self) -> Axes:
        return axes_of(self.param_defs)

    # ---- cache ----------------------------------------------------------
    def cache_defs(self, batch: int, max_len: int) -> ParamDefs:
        return tf.cache_param_defs(self.cfg, batch, max_len)

    def init_cache(self, batch: int, max_len: int) -> Params:
        return materialize(self.cache_defs(batch, max_len),
                           jax.random.PRNGKey(0), self.cfg.dtype)

    def abstract_cache(self, batch: int, max_len: int) -> Params:
        return abstract(self.cache_defs(batch, max_len), self.cfg.dtype)

    def cache_axes(self, batch: int, max_len: int) -> Axes:
        return axes_of(self.cache_defs(batch, max_len))

    # ---- paged cache (serve; DESIGN.md §15) -----------------------------
    def paged_cache_defs(self, num_pages: int, page_size: int) -> ParamDefs:
        """Per-layer physical KV page pools; ``num_pages`` includes the
        reserved trailing TRASH page."""
        return tf.paged_cache_param_defs(self.cfg, num_pages, page_size)

    def init_paged_cache(self, num_pages: int, page_size: int) -> Params:
        return materialize(self.paged_cache_defs(num_pages, page_size),
                           jax.random.PRNGKey(0), self.cfg.dtype)

    def paged_cache_axes(self, num_pages: int, page_size: int) -> Axes:
        return axes_of(self.paged_cache_defs(num_pages, page_size))

    # ---- forward --------------------------------------------------------
    def forward(self, params: Params, batch: Dict[str, jax.Array], *,
                mode: str = "train", cache: Optional[Params] = None,
                cache_pos=None, attn_impl: str = "chunked",
                page_table=None, kv_write_mask=None, last_index=None):
        cfg = self.cfg
        if last_index is not None and (
                mode != "decode" or cfg.family in ("encdec", "hybrid", "ssm")):
            raise ValueError("last_index picks the head rows of a "
                             "dense/moe/vlm decode step")
        if cfg.family == "encdec":
            if page_table is not None:
                raise ValueError("paged KV serving requires a dense/moe/vlm "
                                 "decoder (encdec has ring-buffer caches)")
            return tf.encdec_forward(
                cfg, params, batch["tokens"], frames=batch.get("frames"),
                enc_out=batch.get("enc_out"), mode=mode, cache=cache,
                cache_pos=cache_pos, attn_impl=attn_impl)
        if cfg.family == "hybrid":
            if page_table is not None:
                raise ValueError("paged KV serving requires a dense/moe/vlm "
                                 "decoder (hybrid has recurrent state)")
            return tf.hybrid_forward(
                cfg, params, batch["tokens"], mode=mode, cache=cache,
                cache_pos=cache_pos, attn_impl=attn_impl)
        return tf.decoder_forward(
            cfg, params, batch["tokens"], mode=mode, cache=cache,
            cache_pos=cache_pos, vision_embeds=batch.get("vision_embeds"),
            attn_impl=attn_impl, page_table=page_table,
            kv_write_mask=kv_write_mask, last_index=last_index)

    def loss(self, params: Params, batch: Dict[str, jax.Array], *,
             attn_impl: str = "chunked") -> jax.Array:
        logits, _, aux = self.forward(params, batch, mode="train",
                                      attn_impl=attn_impl)
        return cross_entropy(logits, batch["labels"],
                             self.cfg.final_softcap) + aux

    def prefill(self, params: Params, batch: Dict[str, jax.Array], *,
                attn_impl: str = "chunked"):
        logits, cache, _ = self.forward(params, batch, mode="prefill",
                                        attn_impl=attn_impl)
        return logits, cache

    def decode_step(self, params: Params, cache: Params, batch:
                    Dict[str, jax.Array], pos, *, attn_impl: str = "chunked",
                    page_table=None, kv_write_mask=None, last_index=None):
        """One decode step. ``pos`` is a scalar write position for the whole
        batch, or a (B,) int32 vector of per-row positions (continuous
        batching: every serve slot decodes at its own depth — or, for
        recurrent banks, its own step count — in one fused step).  All
        families accept the vector form; see ``StateBank``.

        With ``page_table`` (B, nb) the cache is the paged pool and
        ``pos`` each row's first write position; tokens (B, S) with
        S > 1 is the paged suffix prefill (writes masked by
        ``kv_write_mask``; see DESIGN.md §15).  ``last_index`` (B,)
        unembeds only that position of each row: logits (B, 1, V)."""
        logits, new_cache, _ = self.forward(
            params, batch, mode="decode", cache=cache, cache_pos=pos,
            attn_impl=attn_impl, page_table=page_table,
            kv_write_mask=kv_write_mask, last_index=last_index)
        return logits, new_cache

    # ---- serve capability metadata (DESIGN.md §17) ----------------------
    @property
    def serve_modes(self) -> frozenset:
        """Per-engine serve capability: ``"dense"`` = the slot-cache
        engines (Engine / EngineReference), ``"paged"`` = PagedEngine.
        Every family serves batched through its state banks; only the
        stacked-KV decoder families additionally page."""
        return _FAMILY_SERVE_MODES[self.cfg.family]

    @property
    def supports_batched_serve(self) -> bool:
        """True when the slot-cache serve engines accept this model
        (derived from ``serve_modes``; kept for callers of the old
        single-bool API)."""
        return "dense" in self.serve_modes

    def state_banks(self) -> Dict[str, "StateBank"]:
        """The model's slot-state banks, keyed exactly like
        ``cache_defs``/``decode_step`` caches (contract: StateBank)."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return {n: StateBank(n, "recurrent", batch_axis=1)
                    for n in ("conv", "ssm")}
        if cfg.family == "hybrid":
            banks = {n: StateBank(n, "recurrent", batch_axis=1)
                     for n in ("rec/h", "rec/conv")}
            for n in ("attn/k", "attn/v", "attn/pos"):
                banks[n] = StateBank(n, "ring", batch_axis=1, seq_axis=2)
            return banks
        if cfg.family == "encdec":
            banks = {}
            for i in range(cfg.dec_layers):
                for n in (f"dec_{i}/k", f"dec_{i}/v"):
                    banks[n] = StateBank(n, "kv", batch_axis=0, seq_axis=1)
            banks["enc/out"] = StateBank("enc/out", "enc", batch_axis=0)
            return banks
        return {n: StateBank(n, "kv", batch_axis=1, seq_axis=2)
                for n in ("k", "v")}

    def encode_prompt(self, params: Params, tokens: jax.Array,
                      lens: jax.Array) -> jax.Array:
        """Encoder forward over stub frames built from prompt tokens
        (whisper's conv frontend is a stub, so frames = token embeddings
        masked by ``arange(Se) < lens``).

        tokens (B, Se) int32 right-padded prompts, lens (B,) int32 valid
        lengths.  Returns (B, Se, d_model) encoder output for the
        ``enc/out`` bank.  The encoder is bidirectional with NO padding
        mask, so the output depends on the padded length Se: serve
        callers MUST pad to one fixed Se (the engines use max_len) so
        every engine compiles the identical program and per-row encoder
        outputs stay bitwise comparable across them.
        """
        if self.cfg.family != "encdec":
            raise ValueError(
                f"encode_prompt is encdec-only (family {self.cfg.family!r})")
        emb = params["emb/tok"][tokens].astype(jnp.dtype(self.cfg.dtype))
        m = jnp.arange(tokens.shape[1])[None, :] < lens[:, None]
        frames = emb * m[:, :, None].astype(emb.dtype)
        return tf.encoder_forward(self.cfg, params, frames)


def build_model(cfg: ModelConfig, max_seq: int = 4096) -> Model:
    return Model(cfg=cfg, max_seq=max_seq,
                 param_defs=tf.model_param_defs(cfg, max_seq))


# ---------------------------------------------------------------------------
# input specs (dry-run stand-ins; no device allocation)
# ---------------------------------------------------------------------------

# encoder frame count used for decode-mode whisper cells (encoder runs once
# at prefill; decode attends to its output)
WHISPER_DECODE_ENC_LEN = 1536


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for one (arch x shape) cell.

    train   -> {tokens, labels [, frames | vision_embeds]}
    prefill -> {tokens [, frames | vision_embeds]}
    decode  -> {tokens (B,1) [, enc_out]}  (the KV cache spec comes from
               Model.abstract_cache(batch, seq_len))
    """
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        specs = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
        if cfg.family == "encdec":
            specs["frames"] = sds((B, S, cfg.d_model), dt)
        if cfg.family == "vlm":
            specs["vision_embeds"] = sds((B, cfg.vision_tokens,
                                          cfg.d_model), dt)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": sds((B, S), i32)}
        if cfg.family == "encdec":
            specs["frames"] = sds((B, S, cfg.d_model), dt)
        if cfg.family == "vlm":
            specs["vision_embeds"] = sds((B, cfg.vision_tokens,
                                          cfg.d_model), dt)
        return specs
    # decode: one new token against a cache of length S
    specs = {"tokens": sds((B, 1), i32)}
    if cfg.family == "encdec":
        specs["enc_out"] = sds((B, WHISPER_DECODE_ENC_LEN, cfg.d_model), dt)
    return specs


def make_inputs(cfg: ModelConfig, shape: ShapeConfig,
                key: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """Concrete random inputs matching input_specs (smoke tests/examples)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    out = {}
    for name, spec in input_specs(cfg, shape).items():
        key, sub = jax.random.split(key)
        if spec.dtype == jnp.int32:
            out[name] = jax.random.randint(sub, spec.shape, 0,
                                           cfg.vocab_size, jnp.int32)
        else:
            out[name] = jax.random.normal(sub, spec.shape, jnp.float32
                                          ).astype(spec.dtype)
    return out
