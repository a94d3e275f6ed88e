"""Device-resident continuous-batching serve engine.

Every piece of per-slot decode state — last token, write position,
temperature, active flag, remaining-token budget — lives as a (slots,)
device array that never leaves the device between host syncs, and one
jitted, cache-donating window fuses K engine ticks (decode + sampling +
termination + slot-free masking).  The state machine (DESIGN.md §11):

  admit  (host, at sync points): free slots x queued requests -> ONE
         batched chunked prefill through ``model.prefill``; whole prompt
         KV blocks land in the assigned cache rows via a masked scatter
         that leaves every other row bit-identical.  (The seed path
         prefilled one token at a time and broadcast each token's KV into
         EVERY slot's cache at that position — the corruption regression-
         tested in tests/test_serve_engine.py.)  The same program samples
         each request's first token from its last prompt position's
         logits and writes the admitted rows of the slot-state arrays.
  decode (device, K fused ticks): ``jax.lax.scan`` over ticks inside one
         jit; each tick decodes all slots at their OWN positions
         (attention.decode_attention), samples greedy/temperature,
         advances budgets, and masks finished slots — a finished row
         emits -1 and stops mutating its state.  Cache and state are
         donated through the window, so they stay device-resident.
  drain  (host, every K ticks): the (K, slots) token/finish buffers come
         back in one transfer; outputs append, finished slots free, new
         requests admit.

The engine also closes the loop to the paper: the compiled tick's roofline
terms (launch/roofline.py) accumulate into dry-run-shaped records
(``serve_records``) so ``core.crosslayer.analyze_serve`` scores SRAM vs
STT/SOT-MRAM tiers on the engine's REAL decode traffic — decode is the
memory-bound regime where DeepNVM++ predicts MRAM pays off most.

``EngineReference`` keeps the seed per-tick path (per-token prefill, one
host round-trip per tick) as the correctness oracle.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import pages_per_block
from repro.models.api import Model, serve_families
from repro.serve.paged import (PagePool, PagePoolExhausted, RadixTree,
                               pages_for)
from repro.serve.resilience import (DONE, FAILED, PENDING, QUEUED, RUNNING,
                                    SHED, TERMINAL_STATES, TIMED_OUT,
                                    ShedPolicy, WindowWatchdog)

# rows of the paged suffix prefill: one admission prefills at most this
# many requests (the rest stay queued), so its program, its attention
# transient and its work scale with the admitted, not with the slots
PREFILL_ROWS = 8


class UnsupportedFamilyError(ValueError):
    """Raised at ENGINE CONSTRUCTION for a model family the engine cannot
    serve, naming the family and the supported set (DESIGN.md §17) —
    instead of a generic ValueError deep inside a forward pass
    mid-request.  Subclasses ValueError so pre-existing callers that
    catch broadly keep working."""

    def __init__(self, family: str, supported, engine: str,
                 detail: str = ""):
        self.family = family
        self.supported = tuple(sorted(supported))
        msg = (f"{engine} does not support model family {family!r} "
               f"(supported families: {', '.join(self.supported)})")
        if detail:
            msg += f"; {detail}"
        super().__init__(msg)


def _where_rows(mask, new, old, axis):
    """Row-masked merge: keep ``new`` where ``mask`` (a (B,) bool over the
    bank's slot axis ``axis``) else ``old``, other axes broadcast."""
    m = mask.reshape(tuple(-1 if d == axis else 1
                           for d in range(old.ndim)))
    return jnp.where(m, new, old)


def _reset_rows(cache, mask, banks, resets):
    """Re-initialize the GUARDED (recurrent/ring) bank rows selected by
    ``mask``; kv/enc banks and every unselected row stay bitwise intact.
    ``resets[name]`` is the bank's init fill value (e.g. -1 for the ring
    position bank, 0 elsewhere)."""
    out = dict(cache)
    for n, b in banks.items():
        if b.kind not in ("recurrent", "ring"):
            continue
        out[n] = _where_rows(mask, jnp.full_like(out[n], resets[n]),
                             out[n], b.batch_axis)
    return out


@dataclasses.dataclass
class Request:
    """One serve request, carrying its own latency record.

    Tick-domain semantics (canonical for BOTH engines; parity-enforced in
    tests/test_serve_engine.py so tick-domain TTFT/TPOT is comparable
    across ``Engine`` and ``EngineReference``):

      * ``engine.ticks`` counts completed DECODE ticks since reset.
        Admission (prefill) happens at host sync points and does not
        advance the tick clock.
      * A request admitted at tick ``T`` gets ``admit_tick = T``.  Its
        prefill-sampled first token t0 is emitted at tick ``T`` as well
        (``first_token_tick = T``): the admission sync point and the
        window's first decode tick share a tick, exactly as in the seed
        per-tick ``step()``.
      * Decode token ``i`` (0-indexed in ``output``, ``i >= 1``) is
        emitted at tick ``T + i - 1``, so ``done_tick`` — the tick of the
        FINAL emitted token — is ``T + len(output) - 2`` for multi-token
        outputs and ``T`` for a request that terminates at prefill
        (``max_new_tokens == 1``, immediate eos, or a full cache).

    Wall-clock stamps (``*_time``, ``time.perf_counter`` seconds) are
    taken when the host actually OBSERVES the event: ``first_token_time``
    when the admission prefill's tokens land on the host, ``done_time``
    at the drain that surfaces the final token — so wall-clock TTFT/TPOT
    include the K-tick drain cadence a client would really see.
    ``dequeue_time`` is when admission took the request off the queue
    for the prefill that served it, so ``submit_time -> dequeue_time``
    is its queue wait, ``dequeue_time -> first_token_time`` its
    admission and ``first_token_time -> done_time`` its decode.
    ``arrival`` is the intended arrival time in ticks for traffic-
    generator workloads (``serve/workload.py``); tick-domain latencies
    are measured from it when set, else from ``submit_tick``.

    Terminal-state semantics (canonical; DESIGN.md §16).  ``state``
    walks ``PENDING -> QUEUED -> RUNNING`` and ends in EXACTLY one of:

      * ``DONE`` — served to completion.  The only state that sets
        ``done=True``; ``output`` is the full bitwise-deterministic
        greedy answer.
      * ``SHED`` — rejected by admission control: queue-depth
        backpressure at submit, or page-pool defers past
        ``ShedPolicy.max_defers``.  ``output`` is empty.
      * ``TIMED_OUT`` — ``deadline`` (absolute engine tick) expired
        while queued (empty output) or mid-decode (``output`` is a
        prefix of the request's reference output — greedy decoding is
        schedule-independent, so partial work is still exact).
      * ``FAILED`` — malformed at submit (``_check_request``) or the
        health-check quarantine retry budget ran out.

    A terminal request never transitions again (``_finalize`` is
    idempotent); ``done_tick``/``done_time`` stamp the tick/wall time
    the terminal state was reached, whatever it was, and ``reason``
    says why for the non-DONE states.  Requeued work (quarantine
    retries, preemption, crash-resubmission) resumes from
    ``prompt + output``: recomputation from a clean prefix is invisible
    in the final tokens.
    """
    uid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    done_tick: Optional[int] = None   # engine tick of the final token
    arrival: Optional[float] = None   # intended arrival (ticks; traffic gen)
    submit_tick: Optional[int] = None
    submit_time: Optional[float] = None
    admit_tick: Optional[int] = None
    admit_time: Optional[float] = None
    dequeue_time: Optional[float] = None
    first_token_tick: Optional[int] = None
    first_token_time: Optional[float] = None
    done_time: Optional[float] = None
    state: str = PENDING
    reason: Optional[str] = None      # why SHED / TIMED_OUT / FAILED
    deadline: Optional[float] = None  # absolute engine tick; opt-in
    retries: int = 0                  # health-check quarantine requeues
    preemptions: int = 0              # preempt_slot requeues
    defers: int = 0                   # pool-exhausted admission defers

    @property
    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    def _mark_admitted(self, tick: int, now: float,
                       dequeued: float) -> None:
        """Stamp admission == first-token emission (see class docstring)
        and the ``dequeued`` time the admission took the request off the
        queue; every engine routes through here so the tick domains
        cannot drift.  Stamps only the FIRST admission: a requeued
        request (retry / preemption) keeps its original TTFT and queue
        wait."""
        self.state = RUNNING
        if self.admit_tick is None:
            self.admit_tick = self.first_token_tick = tick
            self.admit_time = self.first_token_time = now
            self.dequeue_time = dequeued

    def _finalize(self, state: str, tick: int, now: float,
                  reason: Optional[str] = None) -> None:
        """Enter a terminal state exactly once (later calls are no-ops).
        ``done_tick``/``done_time`` stamp the terminal event for every
        state; ``done`` flips only for DONE so telemetry percentiles
        keep meaning served-to-completion."""
        if self.terminal:
            return
        self.state = state
        self.reason = reason
        if state == DONE:
            self.done = True
        self.done_tick = tick
        self.done_time = now

    def _mark_done(self, tick: int, now: float) -> None:
        self._finalize(DONE, tick, now)


def _sample_tokens(logits: jax.Array, temps: jax.Array,
                   key: jax.Array) -> jax.Array:
    """Greedy / temperature sampling over (B, V) f32 logits -> (B,) i32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _check_request(req: Request, max_len: int) -> None:
    if not req.prompt:
        raise ValueError(f"request {req.uid}: empty prompt")
    if len(req.prompt) > max_len:
        raise ValueError(
            f"request {req.uid}: prompt length {len(req.prompt)} exceeds "
            f"max_len {max_len}")
    if req.max_new_tokens < 1:
        raise ValueError(f"request {req.uid}: max_new_tokens must be >= 1")


def _unfinished(engine) -> int:
    """Requests not yet done: still queued or still occupying a slot."""
    return len(engine._queue) + sum(
        r is not None for r in engine.slot_req)


def _soft_submit(engine, req: Request) -> bool:
    """Shared submit path: NEVER raises for a bad request.  A malformed
    request is marked ``FAILED`` with the validation message as its
    ``reason`` and the engine keeps serving (the caller's loop cannot be
    wedged by one bad client); queue-depth backpressure sheds instead of
    queueing unboundedly.  Returns True iff the request was queued."""
    now = time.perf_counter()
    try:
        _check_request(req, engine.max_len)
    except ValueError as e:
        req._finalize(FAILED, engine.ticks, now, reason=str(e))
        engine._rstats["failed"] += 1
        return False
    if req.submit_tick is None:
        req.submit_tick = engine.ticks
        req.submit_time = now
    pol = engine.shed_policy
    if (pol.max_queue_depth is not None
            and len(engine._queue) >= pol.max_queue_depth):
        req._finalize(
            SHED, engine.ticks, now,
            reason=(f"queue depth {len(engine._queue)} at limit "
                    f"{pol.max_queue_depth}"))
        engine._rstats["shed"] += 1
        return False
    req.state = QUEUED
    engine._queue.append(req)
    return True


def _drop_expired(engine) -> None:
    """Shed queued requests whose deadline already passed — they would
    only waste prefill work to time out mid-decode anyway."""
    if not engine._queue or not engine.shed_policy.enforce_deadlines:
        return
    keep: Deque[Request] = collections.deque()
    now = time.perf_counter()
    while engine._queue:
        r = engine._queue.popleft()
        if r.deadline is not None and engine.ticks > r.deadline:
            r._finalize(
                TIMED_OUT, engine.ticks, now,
                reason=(f"deadline {r.deadline:g} expired in queue at "
                        f"tick {engine.ticks}"))
            engine._rstats["timed_out"] += 1
        else:
            keep.append(r)
    engine._queue = keep


def _drain_until_done(engine, max_ticks: int) -> int:
    """Shared run loop: step until queue + slots are empty or the tick
    budget is spent (both engines share exit semantics by construction).

    The budget is K-granular and NEVER overshoots: a window only runs if
    its full ``ticks_per_sync`` ticks fit inside ``max_ticks`` (the seed
    checked only at window boundaries, so ``run(max_ticks)`` could spend
    up to ``ticks_per_sync - 1`` extra ticks and then return silently
    with unfinished work).  When K does not divide ``max_ticks`` the last
    partial window is NOT run — at most ``floor(max_ticks / K) * K``
    ticks are spent.  Returns the number of unfinished requests.
    """
    start = engine.ticks
    k = engine.ticks_per_sync
    while engine._queue or any(r is not None for r in engine.slot_req):
        if engine.ticks - start + k > max_ticks:
            break
        n = engine.step()
        if n == 0:
            if not engine._queue:
                break
            if engine._last_admitted == 0:
                # resource stall: no slot active and nothing admissible
                # (e.g. chaos-held page pool).  Advance the tick clock so
                # deadlines can expire and the budget check above fires —
                # run() always terminates instead of spinning forever.
                engine.ticks += k
    return _unfinished(engine)


class Engine:
    """Fused continuous-batching engine (see module docstring).

    ``ticks_per_sync`` (K) is the drain cadence: larger K amortizes host
    round-trips over more decode ticks but delays slot reuse to window
    boundaries.  K=1 reproduces the seed's per-tick admission schedule
    (used by the tick-parity tests).  ``record_traffic`` compiles each
    executable a second time to harvest roofline terms for
    ``serve_records``/``nvm_verdicts``.
    """

    DECODE_ATTN_IMPLS = ("xla", "pallas_decode")
    SAMPLE_IMPLS = ("xla", "pallas")

    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 eos_id: Optional[int] = None, seed: int = 0,
                 ticks_per_sync: int = 8, record_traffic: bool = True,
                 attn_impl: str = "xla", tracer=None,
                 sample_impl: str = "xla",
                 shed_policy: Optional[ShedPolicy] = None,
                 watchdog: Optional[WindowWatchdog] = None,
                 fault_plan=None):
        if "dense" not in model.serve_modes:
            raise UnsupportedFamilyError(
                model.cfg.family, serve_families("dense"), "Engine")
        if attn_impl == "pallas_decode" \
                and model.cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                "attn_impl='pallas_decode' requires a stacked-KV decoder "
                f"family (dense/moe/vlm); family {model.cfg.family!r} "
                "decodes through its state banks on the XLA path")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.seed = seed
        self.ticks_per_sync = int(ticks_per_sync)
        if self.ticks_per_sync < 1:
            raise ValueError("ticks_per_sync must be >= 1")
        self.record_traffic = record_traffic
        # decode-tick attention: "xla" = jnp decode_attention (full-cache
        # broadcast; the parity oracle), "pallas_decode" = blocked Pallas
        # kernel with fused in-launch KV scatter (DESIGN.md §13)
        if attn_impl not in self.DECODE_ATTN_IMPLS:
            raise ValueError(
                f"attn_impl {attn_impl!r} not in {self.DECODE_ATTN_IMPLS}")
        self.attn_impl = attn_impl
        # token sampling: "xla" = argmax + jax.random.categorical (the
        # parity oracle), "pallas" = one-launch fused kernel
        # (kernels/sampling.py; greedy rows bitwise == argmax)
        if sample_impl not in self.SAMPLE_IMPLS:
            raise ValueError(
                f"sample_impl {sample_impl!r} not in {self.SAMPLE_IMPLS}")
        self.sample_impl = sample_impl
        # optional serve.telemetry.Tracer: records the spans that tile
        # each step (admission phases, prefill, decode window, host
        # drain) for chrome://tracing export (DESIGN.md §14)
        self.tracer = tracer
        # resilience layer (DESIGN.md §16): admission control, bounded
        # window retry, per-slot output health checks (always on), and an
        # optional chaos FaultPlan whose on_site() hooks fire at the named
        # sites
        self.shed_policy = shed_policy if shed_policy is not None \
            else ShedPolicy()
        self.watchdog = watchdog if watchdog is not None else WindowWatchdog()
        self.fault_plan = fault_plan
        self._vocab = int(model.cfg.vocab_size)
        self._decode_attn_impl = (
            "pallas_decode" if attn_impl == "pallas_decode" else "chunked")
        # state-bank metadata (DESIGN.md §17): the per-bank slot/seq axes
        # drive the generic masked scatter, the guarded set names the
        # banks (recurrent/ring) whose rows must be merged under the
        # active mask every tick and re-initialized on slot admit/free
        self._banks = model.state_banks()
        defs = model.cache_defs(slots, max_len)
        self._bank_reset = {
            n: (d.const if d.init == "const" else 0)
            for n, d in defs.items()}
        self._guarded = frozenset(
            n for n, b in self._banks.items()
            if b.kind in ("recurrent", "ring"))
        self._window_jit = jax.jit(self._window, donate_argnums=(1, 2))
        self._deact_jit = jax.jit(
            lambda st, m: dict(st, active=st["active"] & ~m))
        self._prefill_jit = jax.jit(self._prefill_prog,
                                    donate_argnums=(1, 2))
        if self._guarded:
            self._reset_jit = jax.jit(
                lambda c, m: _reset_rows(c, m, self._banks,
                                         self._bank_reset),
                donate_argnums=(0,))
        if model.cfg.family == "encdec":
            # standalone fixed-shape encoder program: BOTH engines call it
            # with (slots, max_len) tokens so the compiled executable — and
            # therefore each row's enc/out bank content — is bitwise
            # identical across Engine and EngineReference
            self._encode_jit = jax.jit(
                lambda p, t, l: model.encode_prompt(p, t, l))
        self._traffic: Dict[str, object] = {"decode": None, "prefill": {}}
        self.reset()

    # ---- state ----------------------------------------------------------
    def _fresh_cache(self):
        """Cache buffers for ``reset`` (PagedEngine swaps in page pools)."""
        return self.model.init_cache(self.slots, self.max_len)

    def reset(self, seed: Optional[int] = None) -> None:
        """Clear cache, slot state, and queue (compiled fns are kept)."""
        self.cache = self._fresh_cache()
        self.key = jax.random.PRNGKey(self.seed if seed is None else seed)
        self.slot_req: List[Optional[Request]] = [None] * self.slots
        self._queue: Deque[Request] = collections.deque()
        self._state = {            # device-resident (slots,) slot state
            "last": jnp.zeros(self.slots, jnp.int32),
            "pos": jnp.zeros(self.slots, jnp.int32),
            "active": jnp.zeros(self.slots, bool),
            "remaining": jnp.zeros(self.slots, jnp.int32),
            "temps": jnp.zeros(self.slots, jnp.float32),
        }
        self.ticks = 0
        self._t_step = self._t_mark = 0.0   # step entry / last boundary
        self._counts = {"decode_ticks": 0, "prefill_calls": {}}
        self._poison_host = np.zeros(self.slots, bool)   # chaos NaN operand
        self._degraded = False      # sticky eager-window fallback mode
        self._last_admitted = 0     # run-loop stall detection
        self._rstats = {"failed": 0, "shed": 0, "timed_out": 0,
                        "quarantined": 0, "retried": 0, "preempted": 0,
                        "window_retries": 0, "window_fallbacks": 0}

    # ---- device programs ------------------------------------------------
    def _sample_batch(self, lg, temps, sub):
        """Traced sampling dispatch: the two-step XLA path or the fused
        one-launch Pallas kernel (greedy rows bitwise-equal; temperature
        rows same distribution, different draw — kernels/sampling.py)."""
        if self.sample_impl == "pallas":
            from repro.kernels import ops as kernel_ops
            return kernel_ops.fused_sample(lg, temps, sub)
        return _sample_tokens(lg, temps, sub)

    def _decode_kwargs(self, extra) -> dict:
        """Extra ``decode_step`` kwargs built from ``_extra_window_args``
        operands (PagedEngine threads its page table through here)."""
        return {}

    def _window(self, params, cache, state, key, poison, *extra):
        """K fused engine ticks: decode + sample + terminate + mask.

        ``poison`` is a (slots,) bool chaos operand: True rows get their
        logits replaced with NaN for this window (``jnp.where`` with an
        all-False mask is a bitwise no-op, so clean runs are unchanged).
        The per-tick ``ok`` output is the window health check — finite
        logits per row — that the host drain uses to quarantine only the
        offending slots (DESIGN.md §16)."""
        eos_id, max_len = self.eos_id, self.max_len
        decode_kw = self._decode_kwargs(extra)

        def tick(carry, _):
            cache, last, pos, active, remaining, temps, key = carry
            safe_pos = jnp.clip(pos, 0, max_len - 1)
            logits, new_cache = self.model.decode_step(
                params, cache, {"tokens": last[:, None]}, safe_pos,
                attn_impl=self._decode_attn_impl, **decode_kw)
            if self._guarded:
                # recurrent/ring banks advance every step regardless of
                # position, so freeze inactive rows explicitly (KV banks
                # need no merge: reads are position-guarded).  Uses the
                # PRE-update active mask: a row finishing THIS tick keeps
                # this tick's state, matching the reference engine.
                new_cache = {
                    n: (_where_rows(active, new_cache[n], cache[n],
                                    self._banks[n].batch_axis)
                        if n in self._guarded else new_cache[n])
                    for n in new_cache}
            cache = new_cache
            lg = logits[:, -1].astype(jnp.float32)
            lg = jnp.where(poison[:, None], jnp.float32(jnp.nan), lg)
            ok = jnp.isfinite(lg).all(axis=-1)
            key, sub = jax.random.split(key)
            tok = self._sample_batch(lg, temps, sub)
            fin = (remaining - 1 <= 0) | (pos + 1 >= max_len)
            if eos_id is not None:
                fin = fin | (tok == eos_id)
            fin = active & fin
            emit = jnp.where(active, tok, -1)
            last = jnp.where(active, tok, last)
            pos = jnp.where(active, pos + 1, pos)
            remaining = jnp.where(active, remaining - 1, remaining)
            active = active & ~fin
            carry = (cache, last, pos, active, remaining, temps, key)
            return carry, (emit, fin, ok)

        carry = (cache, state["last"], state["pos"], state["active"],
                 state["remaining"], state["temps"], key)
        carry, (toks, fins, oks) = jax.lax.scan(
            tick, carry, None, length=self.ticks_per_sync)
        cache, last, pos, active, remaining, temps, key = carry
        state = {"last": last, "pos": pos, "active": active,
                 "remaining": remaining, "temps": temps}
        return cache, state, key, toks, fins, oks

    def _scatter_bank(self, name, old, new, valid):
        """Masked scatter of a prefill bank: write ``new`` (seq length P)
        into ``old`` where ``valid[row, col]``, along the bank's declared
        batch/seq axes.  Rows not being admitted — in particular rows
        mid-decode — are preserved bit-exactly.  Relies on the StateBank
        contract ``batch_axis < seq_axis`` so the (B, P) mask reshapes
        into the bank's layout directly."""
        bank = self._banks[name]
        ba, sa = bank.batch_axis, bank.seq_axis
        P = new.shape[sa]
        mask = valid.reshape(tuple(
            old.shape[d] if d == ba else (P if d == sa else 1)
            for d in range(old.ndim)))
        idx = tuple(slice(0, P) if d == sa else slice(None)
                    for d in range(old.ndim))
        return old.at[idx].set(
            jnp.where(mask, new.astype(old.dtype), old[idx]))

    def _prefill_scan(self, params, cache, tokens, lens, admit):
        """Masked per-token decode scan: the prefill path for recurrent
        families (ssm/hybrid), whose positionless banks cannot scatter a
        full-sequence prefill cache.  Admitted rows' guarded banks reset
        to init, then every prompt token runs one decode step and the
        result merges ONLY into rows still inside their prompt
        (``admit & (t < lens)``) — other slots, including rows
        mid-decode, stay bitwise untouched, and the final state left in
        each admitted slot is exactly what the reference engine's
        per-token loop computes (rows are computationally independent).
        Returns (cache, last_lg) with each admitted row's logits captured
        at its last prompt position."""
        B, P = tokens.shape
        cache = _reset_rows(cache, admit, self._banks, self._bank_reset)
        lg0 = jnp.zeros((B, self._vocab), jnp.float32)

        def body(carry, xs):
            cache, lg_keep = carry
            tok_t, t = xs
            pos = jnp.full((B,), t, jnp.int32)
            logits, new = self.model.decode_step(
                params, cache, {"tokens": tok_t[:, None]}, pos,
                attn_impl=self._decode_attn_impl)
            live = admit & (t < lens)
            cache = {n: _where_rows(live, new[n], cache[n],
                                    self._banks[n].batch_axis)
                     for n in cache}
            lg = logits[:, -1].astype(jnp.float32)
            lg_keep = jnp.where((t == lens - 1)[:, None], lg, lg_keep)
            return (cache, lg_keep), None

        (cache, last_lg), _ = jax.lax.scan(
            body, (cache, lg0),
            (tokens.T, jnp.arange(P, dtype=jnp.int32)))
        return cache, last_lg

    def _prefill_tail(self, cache, state, lens, admit, max_new, temps_in,
                      key, last_lg):
        """Shared prefill epilogue: sample each admitted row's first
        token, apply the immediate-termination rule, and write the
        admitted rows of the slot state (shared by the dense scatter,
        recurrent scan, and paged suffix paths)."""
        ok0 = jnp.isfinite(last_lg).all(axis=-1)
        key, sub = jax.random.split(key)
        t0 = self._sample_batch(last_lg, temps_in, sub)
        done0 = (max_new - 1 <= 0) | (lens >= self.max_len)
        if self.eos_id is not None:
            done0 = done0 | (t0 == self.eos_id)
        state = {
            "last": jnp.where(admit, t0, state["last"]),
            "pos": jnp.where(admit, lens, state["pos"]),
            "active": jnp.where(admit, ~done0, state["active"]),
            "remaining": jnp.where(admit, max_new - 1, state["remaining"]),
            "temps": jnp.where(admit, temps_in, state["temps"]),
        }
        return cache, state, key, t0, done0, ok0

    def _prefill_prog(self, params, cache, state, tokens, lens, admit,
                      max_new, temps_in, key, *extra):
        """Batched prefill into assigned slots, dispatched per family.

        tokens: (slots, P) right-padded prompts (rows not being admitted
        carry zeros and a False ``admit`` flag).  KV families run ONE
        full-sequence ``model.prefill`` whose banks scatter where
        ``admit[row] & (col < lens[row])``; encdec additionally writes
        the admitted rows of the ``enc/out`` bank from the pre-computed
        encoder operand in ``extra`` before prefilling against it;
        recurrent families (ssm/hybrid) run the masked per-token scan
        (``_prefill_scan``).  In every case non-admitted cache rows —
        in particular rows mid-decode — are preserved bit-exactly.
        Returns (cache, state, key, t0, done0, ok0) — ``ok0`` is the
        admission-time health verdict (finite last-position logits), the
        prefill leg of the window health check."""
        fam = self.model.cfg.family
        if fam in ("ssm", "hybrid"):
            cache, last_lg = self._prefill_scan(
                params, cache, tokens, lens, admit)
            return self._prefill_tail(cache, state, lens, admit, max_new,
                                      temps_in, key, last_lg)
        batch = {"tokens": tokens}
        if fam == "encdec":
            cache = dict(cache)
            cache["enc/out"] = _where_rows(
                admit, extra[0].astype(cache["enc/out"].dtype),
                cache["enc/out"], self._banks["enc/out"].batch_axis)
            batch["enc_out"] = cache["enc/out"]
        P = tokens.shape[1]
        # admission chunks are short (P <= max_len); the O(P^2) reference
        # attention beats the flash-scan machinery there, and parity is on
        # greedy argmax, not bitwise logits
        logits, fresh = self.model.prefill(params, batch, attn_impl="naive")
        valid = admit[:, None] & (jnp.arange(P)[None, :] < lens[:, None])
        cache = {name: (self._scatter_bank(name, cache[name], fresh[name],
                                           valid)
                        if name in fresh else cache[name])
                 for name in cache}
        idx = jnp.clip(lens - 1, 0, P - 1)
        last_lg = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1)[:, 0].astype(jnp.float32)
        return self._prefill_tail(cache, state, lens, admit, max_new,
                                  temps_in, key, last_lg)

    # ---- traffic accounting --------------------------------------------
    def _analyze(self, jitted, *args):
        """Roofline terms of the compiled executable (None when traffic is
        not recorded).  A failure raises: a compile the analysis could not
        make is one the launch would not make either, and a phase missing
        from ``serve_records()`` would silently change the NVM verdicts."""
        if not self.record_traffic:
            return None
        from repro.launch import roofline as rf
        return rf.analyze(jitted.lower(*args).compile())

    # ---- step timeline (tracer attached) --------------------------------
    def _leaf(self, name: str, cat: str, end: Optional[float] = None,
              args: Optional[dict] = None) -> float:
        """Record the next leaf span of ``step``: from the previous
        boundary to ``end`` (a stamp the caller already took) or to now.
        Leaves abut, so together they tile the step.  Returns the new
        boundary."""
        if end is None:
            end = time.perf_counter()
        self.tracer.span(name, cat, self._t_mark, end, args=args)
        self._t_mark = end
        return end

    def _trace_admit(self, leaf: str, uids: List[int], queued: int,
                     decoding: int, deferred: int = 0, shed: int = 0,
                     capped: int = 0) -> None:
        """Close an admission: the ``leaf`` span of the bookkeeping after
        the prefill, then the ``admit`` span over every admission leaf
        since step entry.  ``decoding`` counts the slots already running
        a request, whose decode waits on this admission; ``capped`` the
        requests that a free slot could have taken but the prefill's row
        limit left queued."""
        t = self._leaf(leaf, leaf)
        self.tracer.span("admit", "admit", self._t_step, t, args={
            "tick": self.ticks, "queued": queued, "admitted": len(uids),
            "deferred": deferred, "shed": shed, "uids": uids,
            "decoding": decoding, "capped": capped})

    # ---- admission ------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request; never raises.  Malformed requests finalize as
        ``FAILED`` (reason on the request), backpressure sheds — see
        ``_soft_submit``.  Returns True iff queued."""
        return _soft_submit(self, req)

    def _admit(self) -> int:
        """Admit queued requests into free slots with one batched prefill.

        Requeued requests (quarantine retries, preemptions, crash
        resubmissions) resume from ``prompt + output``: the effective
        prompt re-prefills their already-emitted tokens, and the decode
        budget shrinks by what was already produced — greedy decoding is
        schedule-independent, so the continuation is bitwise what an
        uninterrupted run would have emitted."""
        self._last_admitted = 0
        queued = len(self._queue)
        _drop_expired(self)
        free = [i for i in range(self.slots) if self.slot_req[i] is None]
        take = min(len(free), len(self._queue))
        if take == 0:
            return 0
        pairs = [(free[i], self._queue.popleft()) for i in range(take)]
        t_deq = time.perf_counter()
        if self.tracer is not None:
            self._leaf("plan", "plan", end=t_deq)
        eff = {s: list(r.prompt) + list(r.output) for s, r in pairs}
        P = min(self.max_len,
                _next_pow2(max(len(e) for e in eff.values())))
        tokens = np.zeros((self.slots, P), np.int32)
        lens = np.zeros(self.slots, np.int32)
        admit = np.zeros(self.slots, bool)
        max_new = np.ones(self.slots, np.int32)
        temps = np.zeros(self.slots, np.float32)
        for s, r in pairs:
            tokens[s, :len(eff[s])] = eff[s]
            lens[s] = len(eff[s])
            admit[s] = True
            max_new[s] = r.max_new_tokens - len(r.output)
            temps[s] = r.temperature
        extra = ()
        if self.model.cfg.family == "encdec":
            # encoder rows for the enc/out bank: ALWAYS padded to max_len
            # (never the per-wave pow2 P) so the encoder executable — and
            # each row's output — is identical across admission waves and
            # across engines (see Model.encode_prompt)
            toks_full = np.zeros((self.slots, self.max_len), np.int32)
            for s, r in pairs:
                toks_full[s, :len(eff[s])] = eff[s]
            extra = (self._encode_jit(self.params, jnp.asarray(toks_full),
                                      jnp.asarray(lens)),)
        args = (self.params, self.cache, self._state, jnp.asarray(tokens),
                jnp.asarray(lens), jnp.asarray(admit), jnp.asarray(max_new),
                jnp.asarray(temps), self.key, *extra)
        if P not in self._traffic["prefill"]:
            self._traffic["prefill"][P] = self._analyze(
                self._prefill_jit, *args)
        t_launch = time.perf_counter()
        if self.tracer is not None:
            self._leaf("prefill_inputs", "prefill_inputs", end=t_launch)
        self.cache, self._state, self.key, t0, done0, ok0 = \
            self._prefill_jit(*args)
        self._counts["prefill_calls"][P] = \
            self._counts["prefill_calls"].get(P, 0) + 1
        t0, done0, ok0 = np.asarray(t0), np.asarray(done0), np.asarray(ok0)
        now = time.perf_counter()   # t0/done0 observed on the host
        if self.tracer is not None:
            self._leaf(f"prefill P={P}", "prefill", end=now,
                       args={"tick": self.ticks, "admitted": take,
                             "padded_len": P,
                             "uids": [r.uid for _, r in pairs]})
        bad0: Dict[int, int] = {}
        for s, r in pairs:
            self.slot_req[s] = r
            r._mark_admitted(self.ticks, now, t_deq)
            if not ok0[s]:
                bad0[s] = 0      # poisoned prefill: discard t0, requeue
                continue
            r.output.append(int(t0[s]))
            if done0[s]:
                r._mark_done(self.ticks, now)
                self._release_slot(s)
                self.slot_req[s] = None
        self._last_admitted = take
        if bad0:
            self._quarantine(bad0, now)
        if self.tracer is not None:
            self._trace_admit("slots", [r.uid for _, r in pairs], queued,
                              self.slots - len(free))
        return take

    def _release_slot(self, s: int) -> None:
        """Hook called when slot ``s``'s request finishes, just before the
        slot frees (PagedEngine returns the slot's page references).
        Guarded (recurrent/ring) banks re-initialize ONLY that slot's
        rows — positioned KV needs no reset (reads are pos-guarded), but
        positionless state would otherwise leak into the next occupant's
        prefill scan."""
        if self._guarded:
            mask = np.zeros(self.slots, bool)
            mask[s] = True
            self.cache = self._reset_jit(self.cache, jnp.asarray(mask))

    def _pre_window(self) -> Optional[dict]:
        """Hook called right before a decode window launches (PagedEngine
        uploads a dirty page table and measures page sharing).  Returns
        extra args for the ``pre_window`` span when a tracer is
        attached."""
        return None

    def _extra_window_args(self) -> tuple:
        """Extra device operands for ``_window`` (PagedEngine: the page
        table)."""
        return ()

    # ---- resilience -----------------------------------------------------
    def _fire_faults(self, site: str) -> None:
        """Chaos hook: let the attached FaultPlan act at a named site."""
        if self.fault_plan is not None:
            self.fault_plan.on_site(site, self)

    def _deactivate_slots(self, slots) -> None:
        """Clear the device active flag for ``slots`` (quarantine /
        preemption / mid-decode timeout) without touching other rows."""
        mask = np.zeros(self.slots, bool)
        mask[list(slots)] = True
        self._state = self._deact_jit(self._state, jnp.asarray(mask))

    def _stash_prefix(self, s: int, req: Request) -> None:
        """Hook before a preempted slot releases: PagedEngine re-inserts
        the already-written prefix into the radix tree so the requeued
        request re-admits cheaply."""

    def _after_quarantine(self, n: int) -> None:
        """Hook after ``n`` slots were quarantined (PagedEngine flushes
        the radix tree — shared-KV provenance is suspect)."""

    def preempt_slot(self, s: int) -> Request:
        """Kick the request in slot ``s`` back to the FRONT of the queue,
        freeing the slot for other work.  The request resumes from
        ``prompt + output`` on re-admission, so no emitted token is lost
        and greedy continuations stay bitwise-deterministic."""
        r = self.slot_req[s]
        if r is None:
            raise ValueError(f"slot {s} is not occupied")
        self._stash_prefix(s, r)
        self._deactivate_slots([s])
        self._release_slot(s)
        self.slot_req[s] = None
        r.preemptions += 1
        self._rstats["preempted"] += 1
        r.state = QUEUED
        self._queue.appendleft(r)
        return r

    def resilience_stats(self) -> dict:
        """Terminal-state / retry / watchdog counters since reset."""
        return dict(self._rstats, degraded=self._degraded)

    def _launch_window(self, args):
        """Run the decode window under the watchdog: the jitted window
        retries with backoff (an injected stall or poisoned compile
        raises BEFORE the jit call consumes its donated buffers, so the
        operands stay alive), then degrades to the eager interpreted
        window — sticky, because a launch path that failed
        ``max_attempts`` times is not worth re-probing every window."""
        if self._degraded:
            return self._window(*args)

        def primary():
            self._fire_faults("window_launch")
            return self._window_jit(*args)

        def fallback():
            self._rstats["window_fallbacks"] += 1
            self._degraded = True
            return self._window(*args)

        def on_retry(attempt, err):
            self._rstats["window_retries"] += 1

        return self.watchdog.call(primary, fallback=fallback,
                                  label="decode_window", on_retry=on_retry)

    def _quarantine(self, bad: dict, now: float) -> None:
        """Requeue (or fail) slots whose window output flunked the health
        check.  Tokens from the bad tick on were already discarded by the
        drain, so the request's ``output`` is a clean prefix and the
        retry re-prefills it — recomputed greedy tokens are bitwise
        identical, so a retried request's final answer matches an
        unfaulted run."""
        hit = []
        for s in sorted(bad):
            r = self.slot_req[s]
            if r is None:     # finished on a tick before the fault
                continue
            hit.append(s)
            self._rstats["quarantined"] += 1
            self._release_slot(s)
            self.slot_req[s] = None
            r.retries += 1
            if r.retries > self.shed_policy.max_retries:
                r._finalize(
                    FAILED, self.ticks, now,
                    reason=(f"window health check failed {r.retries} "
                            "times (retry budget exhausted)"))
                self._rstats["failed"] += 1
            else:
                self._rstats["retried"] += 1
                r.state = QUEUED
                self._queue.appendleft(r)
        if hit:
            self._deactivate_slots(hit)
            self._after_quarantine(len(hit))

    def _expire_running(self, now: float) -> None:
        """Mid-decode deadline enforcement: release slots whose request
        ran past its deadline, keeping the partial output (a prefix of
        the reference answer)."""
        if not self.shed_policy.enforce_deadlines:
            return
        hit = []
        for s, r in enumerate(self.slot_req):
            if r is None or r.deadline is None or self.ticks <= r.deadline:
                continue
            hit.append(s)
            self._release_slot(s)
            self.slot_req[s] = None
            r._finalize(
                TIMED_OUT, self.ticks, now,
                reason=(f"deadline {r.deadline:g} expired mid-decode at "
                        f"tick {self.ticks}"))
            self._rstats["timed_out"] += 1
        if hit:
            self._deactivate_slots(hit)

    # ---- engine loop ----------------------------------------------------
    def step(self) -> int:
        """One sync window: admit + K fused ticks + drain.  Returns the
        number of sequences active during the window.

        With a tracer attached, the step's leaf spans tile it from entry
        to return: the admission leaves under an ``admit`` span (when
        anything is admitted), then ``pre_window``, ``decode_window``
        and ``host_drain`` (DESIGN.md §14)."""
        if self.tracer is not None:
            self._t_step = self._t_mark = time.perf_counter()
        self._fire_faults("pre_admit")
        self._admit()
        n_active = sum(r is not None for r in self.slot_req)
        if n_active == 0:
            if self.tracer is not None and not self._last_admitted:
                # a scan that admitted nothing: no slot, or no pages
                self._leaf("plan", "plan", args={
                    "tick": self.ticks, "queued": len(self._queue)})
            return 0
        pre_args = self._pre_window()
        self._fire_faults("pre_window")
        # copy before transfer: on CPU jnp.asarray may alias the numpy
        # buffer, and the one-shot clear below would race the async
        # window launch, silently dropping the injected poison
        poison = jnp.asarray(np.array(self._poison_host))
        extra = self._extra_window_args()
        args = (self.params, self.cache, self._state, self.key, poison,
                *extra)
        if self._traffic["decode"] is None and self.record_traffic:
            self._traffic["decode"] = self._analyze(self._window_jit, *args)
        tick = self.ticks
        t_launch = time.perf_counter()
        if self.tracer is not None:
            uids = [r.uid for r in self.slot_req if r is not None]
            self._leaf("pre_window", "pre_window", end=t_launch,
                       args=dict(pre_args or {}, tick=tick))
        self.cache, self._state, self.key, toks, fins, oks = \
            self._launch_window(args)
        if self._poison_host.any():
            self._poison_host[:] = False   # chaos poison is one-shot
        toks, fins = np.asarray(toks), np.asarray(fins)   # ONE host sync
        oks = np.asarray(oks)
        now = time.perf_counter()   # window results observed on the host
        self._counts["decode_ticks"] += self.ticks_per_sync
        # window health check: first tick per slot whose emitted token is
        # untrustworthy (non-finite logits or out-of-vocab sample)
        bad: Dict[int, int] = {}
        for s in range(self.slots):
            if self.slot_req[s] is None:
                continue
            for t in range(self.ticks_per_sync):
                if toks[t, s] < 0:
                    continue
                if not oks[t, s] or toks[t, s] >= self._vocab:
                    bad[s] = t
                    break
        for t in range(self.ticks_per_sync):
            for s in range(self.slots):
                r = self.slot_req[s]
                if r is None or toks[t, s] < 0:
                    continue
                if s in bad and t >= bad[s]:
                    continue    # discard everything from the bad tick on
                r.output.append(int(toks[t, s]))
                if fins[t, s]:
                    # tick domain keeps the in-window position; the wall
                    # clock is the drain that surfaced the token (Request
                    # docstring)
                    r._mark_done(self.ticks + t, now)
                    self._release_slot(s)
                    self.slot_req[s] = None
        self.ticks += self.ticks_per_sync
        if bad:
            self._quarantine(bad, now)
        self._expire_running(now)
        if self.tracer is not None:
            self._leaf("decode_window", "decode", end=now,
                       args={"tick": tick, "K": self.ticks_per_sync,
                             "active": n_active, "uids": uids})
            self._leaf("host_drain", "host", args={"tick": tick})
            self.tracer.counter("active_slots", {"active": n_active},
                                t_launch)
        return n_active

    def run(self, max_ticks: int = 10_000) -> int:
        """Run to completion within a K-granular tick budget; returns the
        number of unfinished requests (0 when everything completed)."""
        return _drain_until_done(self, max_ticks)

    # ---- serve-mode NVM verdicts ---------------------------------------
    def serve_records(self, mesh: Optional[str] = None) -> List[dict]:
        """Dry-run-shaped records of the engine's measured traffic: one
        record per serve phase with PER-TICK (decode) / PER-CALL (prefill)
        roofline terms of the compiled executables, consumable by
        ``core.crosslayer.analyze_serve`` — the serve-mode answer to the
        paper's "would an MRAM tier help THIS workload" question."""
        mesh = mesh or f"{jax.device_count()}dev"
        arch = self.model.cfg.arch
        fam = self.model.cfg.family

        def terms(rl, div):
            return {"flops_per_device": rl.flops_per_device / div,
                    "bytes_per_device": rl.bytes_per_device / div,
                    "collective_bytes": rl.collective_bytes / div,
                    "compute_s": rl.compute_s / div,
                    "memory_s": rl.memory_s / div,
                    "collective_s": rl.collective_s / div}

        # Recurrent-bank traffic is write-heavier than KV decode: every
        # tick rewrites the full conv/SSD/RG-LRU state in place, where KV
        # decode appends one row and *reads* the rest.  Tag ssm/hybrid
        # records with their own read/write split so analyze_serve scores
        # the write-asymmetric NVM tiers on the bank regime they actually
        # see (ISSUE 10 tentpole (d)).
        extra: dict = {"family": fam}
        if fam in ("ssm", "hybrid"):
            from repro.core.crosslayer import RECURRENT_READ_FRACTION
            extra["read_fraction"] = RECURRENT_READ_FRACTION

        recs = []
        rl = self._traffic["decode"]
        if rl is not None and self._counts["decode_ticks"]:
            recs.append({
                "arch": arch, "mesh": mesh, "kind": "decode",
                "shape": f"serve_{fam}_decode_b{self.slots}_l{self.max_len}",
                "attn_impl": self.attn_impl,
                "ticks": self._counts["decode_ticks"],
                "roofline": terms(rl, self.ticks_per_sync), **extra})
        for P, rl in sorted(self._traffic["prefill"].items()):
            calls = self._counts["prefill_calls"].get(P, 0)
            if rl is None or not calls:
                continue
            recs.append({
                "arch": arch, "mesh": mesh, "kind": "prefill",
                "shape": f"serve_{fam}_prefill_p{P}_b{self.slots}",
                "calls": calls, "roofline": terms(rl, 1), **extra})
        return recs

    def nvm_verdicts(self, tier_mb: Optional[float] = None):
        """SRAM/STT/SOT tier verdicts on the engine's measured traffic."""
        from repro.core.crosslayer import analyze_serve
        kw = {} if tier_mb is None else {"tier_mb": tier_mb}
        return analyze_serve(self.serve_records(), **kw)


class PagedEngine(Engine):
    """Paged-KV continuous-batching engine with radix-tree prefix sharing
    (DESIGN.md §15).

    Device KV lives in per-layer physical page pools of shape
    ``(num_pages + 1, page_size, K, hd)`` — the trailing page is TRASH,
    the scatter sink for masked/inactive rows — and every slot carries a
    ``(nb,)`` row of one shared ``(slots, nb)`` int32 page table
    (``nb = max_len // page_size``).  Host-side bookkeeping is
    ``serve/paged.py``: a refcounted ``PagePool`` plus a path-compressed
    ``RadixTree`` of served prompts pinning the pages that hold their KV.

    Admission walks the tree for the longest stored prefix of each
    prompt (capped at ``len(prompt) - 1`` so at least one suffix token
    always prefills and produces t0 logits), maps the shared full pages
    by bumping refcounts, copy-on-writes the boundary page when the
    suffix starts mid-page, and reserves the slot's FULL page span
    ``ceil(min(L + max_new, max_len) / page_size)`` up front — decode
    never allocates mid-flight.  Only the unshared suffix runs through
    the (batched, masked) paged prefill, whose rows are the admitted
    requests alone (at most ``PREFILL_ROWS`` a step; the rest stay
    queued); finished prompts insert into the tree so later requests can
    share them.  When the pool runs short, LRU tree leaves evict; if
    still short, admission defers to a later sync point (deadlock-free:
    a lone request needs at most ``nb`` pages and full eviction frees
    everything).

    Decode runs the same fused K-tick window as ``Engine`` with the page
    table as an extra operand: ``attn_impl="xla"`` takes the jnp
    gather path (the parity oracle), ``"pallas_paged"`` the Pallas
    kernel with the table as a scalar-prefetch operand and fused KV
    append (kernels/paged_attention.py).  Greedy outputs are bitwise
    equal to ``Engine``/``EngineReference`` on the same request set
    (tests/test_paged_cache.py).

    ``serve_records()`` annotates the decode record with the measured
    ``unique_page_fraction`` — unique physical pages read per window
    over total mapped page reads — which
    ``core.crosslayer.analyze_serve`` uses to scale KV traffic: shared
    pages are one physical working set, so the NVM verdicts see the
    paged engine's REAL (deduplicated) decode traffic.
    """

    DECODE_ATTN_IMPLS = ("xla", "pallas_paged")

    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 page_size: int = 8, num_pages: Optional[int] = None, **kw):
        if "paged" not in model.serve_modes:
            raise UnsupportedFamilyError(
                model.cfg.family, serve_families("paged"), "PagedEngine",
                detail="the paged engine is KV-decoder-only by design: "
                       "pages hold positioned KV rows, and recurrent/ring/"
                       "encoder banks have no page-addressable layout — "
                       "use Engine for this family")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size "
                f"{page_size}")
        self.page_size = int(page_size)
        self.nb = max_len // self.page_size
        # default pool = dense capacity (slots x nb); prefix sharing then
        # strictly lowers pages-in-use.  TRASH is the extra device page at
        # index num_pages, never managed by the host pool.
        self.num_pages = int(num_pages) if num_pages is not None \
            else slots * self.nb
        if self.num_pages < self.nb:
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold one full-length "
                f"request ({self.nb} pages)")
        self.trash = self.num_pages
        self.rows = min(slots, PREFILL_ROWS)
        super().__init__(model, params, slots=slots, max_len=max_len, **kw)
        # decode through the paged branch of attention_block: plain jnp
        # gather under "xla", fused Pallas kernel under "pallas_paged"
        self._decode_attn_impl = (
            "pallas_paged" if self.attn_impl == "pallas_paged" else "xla")
        cfg = model.cfg
        self._kernel_ppb = pages_per_block(
            self.nb, self.page_size, cfg.num_kv_heads, cfg.head_dim,
            cfg.dtype)
        self._cow_jit = jax.jit(
            lambda c, src, dst: {
                k: v.at[:, dst].set(v[:, src]) for k, v in c.items()},
            donate_argnums=(0,))
        self._scrub_jit = jax.jit(
            lambda c, idx: {
                k: v.at[:, idx].set(0) for k, v in c.items()},
            donate_argnums=(0,))

    # ---- state ----------------------------------------------------------
    def _fresh_cache(self):
        return self.model.init_paged_cache(self.num_pages + 1,
                                           self.page_size)

    def reset(self, seed: Optional[int] = None) -> None:
        super().reset(seed)
        self.pool = PagePool(self.num_pages, self.page_size)
        self.tree = RadixTree(self.pool)
        self._slot_pages: List[List[int]] = [[] for _ in range(self.slots)]
        self._pt_host = np.full((self.slots, self.nb), self.trash, np.int32)
        self._pt_dev = jnp.asarray(self._pt_host)
        self._pt_dirty = False
        self.stats = {"prefix_hits": 0, "prefix_tokens": 0,
                      "prompt_tokens": 0, "cow_copies": 0, "deferred": 0,
                      "evicted_pages": 0, "inserted_nodes": 0,
                      "tree_flushes": 0}
        self._last_shortage = (0, 0)   # (pages wanted, pages free)
        self._upf_sum = 0.0
        self._upf_windows = 0

    def paged_stats(self) -> dict:
        """Counters + pool gauges for launch printouts and benchmarks."""
        pt = max(1, self.stats["prompt_tokens"])
        return {**self.stats,
                "pages_hwm": self.pool.hwm,
                "pages_in_use": self.pool.in_use,
                "free_pages": self.pool.free_pages,
                "radix_nodes": self.tree.num_nodes,
                "prefix_hit_rate": self.stats["prefix_tokens"] / pt}

    # ---- window plumbing -------------------------------------------------
    def _decode_kwargs(self, extra) -> dict:
        return {"page_table": extra[0]}

    def _extra_window_args(self) -> tuple:
        return (self._pt_dev,)

    def _pre_window(self) -> Optional[dict]:
        if self._pt_dirty:
            self._pt_dev = jnp.asarray(self._pt_host)
            self._pt_dirty = False
        # unique-page fraction of this window's decode reads: row b at
        # position p reads its first ceil((p+1)/ps) mapped pages
        mapped: List[int] = []
        blocks = 0
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            pos = len(r.prompt) + len(r.output) - 1
            n = pages_for(min(pos + 1, self.max_len), self.page_size)
            mapped.extend(self._pt_host[s, :n].tolist())
            blocks += -(-n // self._kernel_ppb)
        if not mapped:
            return None
        unique = len(set(mapped))
        frac = unique / len(mapped)
        self._upf_sum += frac
        self._upf_windows += 1
        if self.tracer is None:
            return None
        # grid_pages: the (slots x table width) pages a kernel stepping
        # every slot over every page would visit; the paged kernel walks
        # the ``mapped`` ones, in ``kernel_blocks`` DMA blocks a
        # global-attention layer call at the window's first tick
        return {"mapped": len(mapped), "unique": unique,
                "unique_page_fraction": frac,
                "grid_pages": self.slots * self.nb,
                "kernel_blocks": blocks}

    def _release_slot(self, s: int) -> None:
        for p in self._slot_pages[s]:
            self.pool.release(p)
        self._slot_pages[s] = []
        self._pt_host[s] = self.trash
        self._pt_dirty = True

    # ---- resilience -----------------------------------------------------
    def _stash_prefix(self, s: int, req: Request) -> None:
        """Preemption keeps the work: the slot's already-written KV —
        positions ``[0, L + len(output) - 1)``, i.e. the effective prompt
        minus the not-yet-written last token — goes into the radix tree
        under its token string, so the requeued request's next ``_plan``
        matches it and re-admission prefills only one suffix token."""
        written = len(req.prompt) + len(req.output) - 1
        if written < 1:
            return
        toks = (list(req.prompt) + list(req.output))[:written]
        self.stats["inserted_nodes"] += self.tree.insert(
            toks, self._slot_pages[s][:pages_for(written, self.page_size)])

    def _after_quarantine(self, n: int) -> None:
        # a health-check failure means some KV content is untrustworthy,
        # and shared prefix pages could re-poison every retry: flush the
        # tree (conservative — only costs re-prefill on the next misses)
        self.stats["tree_flushes"] += 1
        self.tree.clear()
        # scrub the now-free pages on device: a recycled page is only
        # partially overwritten by its next prefill (rows past the new
        # occupant's length keep old bytes), and corrupt residue there
        # can leak into attention — zeroing restores the fresh-cache
        # contract for everything the flush just released
        free = sorted(self.pool._free)
        if free:
            self.cache = self._scrub_jit(
                self.cache, jnp.asarray(free, jnp.int32))

    # ---- admission ------------------------------------------------------
    def _plan(self, req: Request) -> Optional[dict]:
        """Reserve every page request ``req`` will ever touch, sharing
        tree-held prefix pages.  Returns None (nothing mutated net) when
        the pool stays short even after LRU eviction — the shortfall is
        kept in ``_last_shortage`` so the shed path can say how many
        pages were missing.  Requeued requests plan against their
        effective prompt ``prompt + output`` (resume, not restart)."""
        ps = self.page_size
        prompt = list(req.prompt) + list(req.output)
        L = len(prompt)
        remaining = req.max_new_tokens - len(req.output)
        # cap the match one token short of the prompt: the suffix must be
        # non-empty so the admission prefill computes t0 logits
        matched, shared = self.tree.match(prompt[:L - 1])
        n_full = matched // ps
        boundary = matched % ps != 0
        held = shared[:n_full + (1 if boundary else 0)]
        for p in held:            # pin before eviction can free them
            self.pool.share(p)
        total = pages_for(min(L + remaining, self.max_len), ps)
        need = total - n_full     # boundary page is CoW'd, so it's "new"
        if self.pool.free_pages < need:
            self.stats["evicted_pages"] += self.tree.evict(need)
        try:
            new = self.pool.alloc(need)
        except PagePoolExhausted as e:
            for p in held:        # roll back the pins; admission defers
                self.pool.release(p)
            self._last_shortage = (e.requested, e.free)
            return None
        self.stats["prompt_tokens"] += L
        self.stats["prefix_tokens"] += matched
        self.stats["prefix_hits"] += 1 if matched else 0
        cow = None
        if boundary:
            # suffix starts mid-page: private copy of the shared boundary
            # page (new[0] covers logical page n_full), pin released after
            # the device copy in _admit
            cow = (held[n_full], new[0])
            self.stats["cow_copies"] += 1
            self.pool.cow_copies += 1
        return {"matched": matched, "L": L, "prompt": prompt, "cow": cow,
                "pages": shared[:n_full] + new, "total": total,
                "boundary_pin": held[n_full] if boundary else None}

    def _admit(self) -> int:
        """Paged admission is a shed-or-defer scan, never head-of-line
        blocking: a request whose page reservation cannot be met steps
        aside (keeping its queue position) so later requests that DO fit
        can run, and sheds outright once it has been passed over
        ``ShedPolicy.max_defers`` times.  Combined with the run-loop
        stall guard this makes pool exhaustion a latency event, not a
        deadlock."""
        self._last_admitted = 0
        queued = len(self._queue)
        _drop_expired(self)
        free = [s for s in range(self.slots) if self.slot_req[s] is None]
        decoding = self.slots - len(free)
        pol = self.shed_policy
        pairs = []
        deferred: List[Request] = []
        shed = 0
        while free and self._queue and len(pairs) < self.rows:
            r = self._queue.popleft()
            plan = self._plan(r)
            if plan is None:
                self.stats["deferred"] += 1
                r.defers += 1
                if pol.max_defers is not None and r.defers > pol.max_defers:
                    want, have = self._last_shortage
                    r._finalize(
                        SHED, self.ticks, time.perf_counter(),
                        reason=(f"page pool exhausted on {r.defers} "
                                f"admission attempts (last shortfall: "
                                f"wanted {want} pages, {have} free)"))
                    self._rstats["shed"] += 1
                    shed += 1
                else:
                    deferred.append(r)
                continue
            pairs.append((free.pop(0), r, plan))
        capped = min(len(free), len(self._queue))
        for r in reversed(deferred):
            self._queue.appendleft(r)
        if not pairs:
            return 0
        t_admit = time.perf_counter()   # the admitted leave the queue
        if self.tracer is not None:
            self._leaf("plan", "plan", end=t_admit)
        # batched CoW device copies, then drop the boundary pins
        cows = [p["cow"] for _, _, p in pairs if p["cow"] is not None]
        if cows:
            srcs, dsts = zip(*cows)
            self.cache = self._cow_jit(self.cache,
                                       jnp.asarray(srcs, jnp.int32),
                                       jnp.asarray(dsts, jnp.int32))
            for _, _, p in pairs:
                if p["boundary_pin"] is not None:
                    self.pool.release(p["boundary_pin"])
            if self.tracer is not None:
                self._leaf("cow", "cow", args={"copies": len(cows)})
        # page tables: the slot holds one reference per mapped page
        for s, r, p in pairs:
            self._slot_pages[s] = list(p["pages"])
            row = np.full(self.nb, self.trash, np.int32)
            row[:p["total"]] = p["pages"]
            self._pt_host[s] = row
        self._pt_dev = jnp.asarray(self._pt_host)
        self._pt_dirty = False
        # batched suffix prefill (only unshared tokens run the model), one
        # row per admitted request; padding rows write only to TRASH and
        # scatter into no slot (index ``slots`` is dropped)
        R = self.rows
        S = min(self.max_len,
                _next_pow2(max(p["L"] - p["matched"] for _, _, p in pairs)))
        tokens = np.zeros((R, S), np.int32)
        mask = np.zeros((R, S), bool)
        slot = np.full(R, self.slots, np.int32)
        starts = np.zeros(R, np.int32)
        suf_lens = np.zeros(R, np.int32)
        full_lens = np.zeros(R, np.int32)
        max_new = np.ones(R, np.int32)
        temps = np.zeros(R, np.float32)
        for i, (s, r, p) in enumerate(pairs):
            suf = p["prompt"][p["matched"]:]
            tokens[i, :len(suf)] = suf
            mask[i, :len(suf)] = True
            slot[i] = s
            starts[i] = p["matched"]
            suf_lens[i] = len(suf)
            full_lens[i] = p["L"]
            max_new[i] = r.max_new_tokens - len(r.output)
            temps[i] = r.temperature
        args = (self.params, self.cache, self._state, jnp.asarray(tokens),
                self._pt_dev, jnp.asarray(slot), jnp.asarray(starts),
                jnp.asarray(suf_lens), jnp.asarray(full_lens),
                jnp.asarray(max_new), jnp.asarray(temps), self.key,
                jnp.asarray(mask))
        if S not in self._traffic["prefill"]:
            self._traffic["prefill"][S] = self._analyze(
                self._prefill_jit, *args)
        t_launch = time.perf_counter()
        if self.tracer is not None:
            self._leaf("prefill_inputs", "prefill_inputs", end=t_launch)
        self.cache, self._state, self.key, t0, done0, ok0 = \
            self._prefill_jit(*args)
        self._counts["prefill_calls"][S] = \
            self._counts["prefill_calls"].get(S, 0) + 1
        t0, done0, ok0 = np.asarray(t0), np.asarray(done0), np.asarray(ok0)
        now = time.perf_counter()
        if self.tracer is not None:
            self._leaf(
                f"prefill_chunk S={S}", "prefill", end=now,
                args={"tick": self.ticks, "admitted": len(pairs),
                      "rows": R, "padded_len": S,
                      "suffix_tokens": int(suf_lens.sum()),
                      "shared_tokens": int((full_lens - suf_lens).sum()),
                      "uids": [r.uid for _, r, _ in pairs]})
        bad0: Dict[int, int] = {}
        for i, (s, r, p) in enumerate(pairs):
            self.slot_req[s] = r
            r._mark_admitted(self.ticks, now, t_admit)
            if not ok0[i]:
                # poisoned prefill (a shared or recycled page carried
                # corrupt KV): discard t0 and requeue via quarantine —
                # the tree flush + page scrub below cleans the source
                bad0[s] = 0
                continue
            r.output.append(int(t0[i]))
            # register the full effective prompt's pages so later prompts
            # share them (the tree takes its own references; safe even if
            # this slot keeps decoding into the boundary page at rows
            # >= L, which the tree never vouches for)
            self.stats["inserted_nodes"] += self.tree.insert(
                p["prompt"], p["pages"][:pages_for(p["L"], self.page_size)])
            if done0[i]:
                r._mark_done(self.ticks, now)
                self._release_slot(s)
                self.slot_req[s] = None
        self._last_admitted = len(pairs)
        if bad0:
            self._quarantine(bad0, now)
        if self.tracer is not None:
            self._trace_admit("tree", [r.uid for _, r, _ in pairs], queued,
                              decoding, len(deferred), shed, capped)
        return len(pairs)

    def _prefill_prog(self, params, cache, state, tokens, pt, slot, starts,
                      suf_lens, full_lens, max_new, temps_in, key, mask):
        """Batched paged SUFFIX prefill of the admitted requests, one row
        each: decode-mode forward with S > 1 tokens per row starting at
        its ``starts`` (= matched prefix length), through row ``slot`` of
        the whole page table ``pt`` (gathered here, so the one upload at
        admission also serves the decode window).  ``mask`` routes every
        non-suffix write to the TRASH page, so rows mid-decode and the
        shared prefix pages stay bit-identical; per-row causal masking makes the suffix KV
        independent of other rows.  Only each row's last suffix position
        reaches the output head; t0 is sampled there and, with the row's
        state, scattered into slot ``slot`` (padding rows carry
        ``slots``, which the scatter drops)."""
        S = tokens.shape[1]
        rows_pt = jnp.take(pt, slot, axis=0, mode="fill",
                           fill_value=self.trash)
        logits, cache = self.model.decode_step(
            params, cache, {"tokens": tokens}, starts, attn_impl="xla",
            page_table=rows_pt, kv_write_mask=mask,
            last_index=jnp.clip(suf_lens - 1, 0, S - 1))
        last_lg = logits[:, 0].astype(jnp.float32)
        ok0 = jnp.isfinite(last_lg).all(axis=-1)
        key, sub = jax.random.split(key)
        t0 = self._sample_batch(last_lg, temps_in, sub)
        done0 = (max_new - 1 <= 0) | (full_lens >= self.max_len)
        if self.eos_id is not None:
            done0 = done0 | (t0 == self.eos_id)
        new = {"last": t0, "pos": full_lens, "active": ~done0,
               "remaining": max_new - 1, "temps": temps_in}
        state = {n: state[n].at[slot].set(new[n], mode="drop")
                 for n in state}
        return cache, state, key, t0, done0, ok0

    # ---- serve-mode NVM verdicts ---------------------------------------
    def serve_records(self, mesh: Optional[str] = None) -> List[dict]:
        """Engine records plus the measured ``unique_page_fraction`` on
        the decode record — ``analyze_serve`` scales KV-bound traffic by
        it, so the SRAM/STT/SOT verdicts see prefix sharing's traffic
        reduction (DESIGN.md §15)."""
        recs = super().serve_records(mesh)
        upf = (self._upf_sum / self._upf_windows
               if self._upf_windows else 1.0)
        for rec in recs:
            if rec["kind"] == "decode":
                rec["unique_page_fraction"] = upf
        return recs


class EngineReference:
    """The seed per-tick serving path, kept as the correctness oracle for
    ``Engine`` (DESIGN.md §11): prompts prefill one token at a time
    through ``decode_step``, every decode tick round-trips logits to the
    host, and sampling/termination run in per-request python.

    Two seed bugs are fixed so this is actually an oracle:
      * per-row position vectors replace the shared ``max(slot_pos)``
        scalar, so slots at different depths decode correctly;
      * prefill restores every non-target cache row after each token step
        instead of broadcasting the prefilling request's KV into ALL rows
        (``jnp.full((slots, 1), token)`` in the seed ``_step_slot``).
    Greedy outputs are parity-enforced against ``Engine`` in
    tests/test_serve_engine.py and tests/test_chaos_scenarios.py.

    Family support matches ``Engine`` (every ``serve_modes``-dense
    family): recurrent/ring banks get a per-row reset at admission and a
    bank-aware row restore during prefill, and encdec rows are encoded
    through the same fixed-shape program as ``Engine._encode_jit`` so
    enc/out content is bitwise identical across engines.
    """

    ticks_per_sync = 1   # per-tick engine: every step is its own window

    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 eos_id: Optional[int] = None, seed: int = 0,
                 shed_policy: Optional[ShedPolicy] = None):
        if "dense" not in model.serve_modes:
            raise UnsupportedFamilyError(
                model.cfg.family, serve_families("dense"),
                "EngineReference")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.seed = seed
        self.shed_policy = shed_policy if shed_policy is not None \
            else ShedPolicy()
        self._banks = model.state_banks()
        defs = model.cache_defs(slots, max_len)
        self._bank_reset = {n: (d.const if d.init == "const" else 0)
                            for n, d in defs.items()}
        self._guarded = frozenset(
            n for n, b in self._banks.items()
            if b.kind in ("recurrent", "ring"))
        self._decode = jax.jit(
            lambda p, c, b, pos: model.decode_step(p, c, b, pos))
        if model.cfg.family == "encdec":
            # the SAME fixed-shape encoder program as Engine._encode_jit,
            # so both engines' enc/out rows are bitwise identical
            self._encode = jax.jit(
                lambda p, t, l: model.encode_prompt(p, t, l))
        self.reset()

    def reset(self, seed: Optional[int] = None) -> None:
        self.cache = self.model.init_cache(self.slots, self.max_len)
        self.key = jax.random.PRNGKey(self.seed if seed is None else seed)
        self.slot_req: List[Optional[Request]] = [None] * self.slots
        self._queue: Deque[Request] = collections.deque()
        self._last = np.zeros(self.slots, np.int32)
        self._pos = np.zeros(self.slots, np.int32)
        self._active = np.zeros(self.slots, bool)
        self._remaining = np.zeros(self.slots, np.int32)
        self._temps = np.zeros(self.slots, np.float32)
        self.ticks = 0
        self._last_admitted = 0
        self._rstats = {"failed": 0, "shed": 0, "timed_out": 0,
                        "quarantined": 0, "retried": 0, "preempted": 0,
                        "window_retries": 0, "window_fallbacks": 0}

    # ---- admission ------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Same soft-fail semantics as ``Engine.submit``."""
        return _soft_submit(self, req)

    def resilience_stats(self) -> dict:
        return dict(self._rstats, degraded=False)

    def _admit(self) -> None:
        self._last_admitted = 0
        _drop_expired(self)
        for i in range(self.slots):
            if self.slot_req[i] is None and self._queue:
                self._prefill(i, self._queue.popleft())
                self._last_admitted += 1

    def _sample(self, logits_row: np.ndarray, temp: float) -> int:
        if temp > 0:
            self.key, sub = jax.random.split(self.key)
            scaled = jnp.asarray(logits_row, jnp.float32) / max(temp, 1e-6)
            return int(jax.random.categorical(sub, scaled))
        return int(np.argmax(logits_row))

    def _prefill(self, slot: int, req: Request) -> None:
        """Per-token prefill (the seed loop), slot-isolated.  Requeued
        requests (e.g. crash resubmission) resume from their effective
        prompt ``prompt + output``, mirroring ``Engine._admit``."""
        t_deq = time.perf_counter()
        self.slot_req[slot] = req
        eff = list(req.prompt) + list(req.output)
        sel = (jnp.arange(self.slots) == slot)
        if self._guarded:
            # recurrent/ring banks keep the PREVIOUS occupant's state in
            # this row (no position guard to mask it out) — reset the
            # admitted row before replaying the prompt, exactly like
            # Engine._prefill_scan
            self.cache = _reset_rows(self.cache, sel, self._banks,
                                     self._bank_reset)
        if self.model.cfg.family == "encdec":
            toks_full = np.zeros((self.slots, self.max_len), np.int32)
            toks_full[slot, :len(eff)] = eff
            lens = np.zeros(self.slots, np.int32)
            lens[slot] = len(eff)
            enc = self._encode(self.params, jnp.asarray(toks_full),
                               jnp.asarray(lens))
            cache = dict(self.cache)
            cache["enc/out"] = _where_rows(
                sel, enc.astype(cache["enc/out"].dtype),
                cache["enc/out"], self._banks["enc/out"].batch_axis)
            self.cache = cache
        lg = None
        for t, tok in enumerate(eff):
            toks = self._last.copy()
            toks[slot] = tok
            pos = np.clip(self._pos, 0, self.max_len - 1)
            pos[slot] = t
            old = self.cache
            logits, new = self._decode(
                self.params, old, {"tokens": jnp.asarray(toks[:, None])},
                jnp.asarray(pos))
            # only the target row may change (the seed broadcast every
            # prefill token's KV into all rows here); banks carry their
            # own batch axis, so route the row select through it
            self.cache = {
                n: _where_rows(sel, new[n], old[n],
                               self._banks[n].batch_axis)
                for n in new}
            lg = logits
        t0 = self._sample(np.asarray(lg)[slot, -1].astype(np.float32),
                          req.temperature)
        req._mark_admitted(self.ticks, time.perf_counter(), t_deq)
        req.output.append(t0)
        self._last[slot] = t0
        self._pos[slot] = len(eff)
        self._remaining[slot] = req.max_new_tokens - len(req.output)
        self._temps[slot] = req.temperature
        done = (self._remaining[slot] <= 0
                or (self.eos_id is not None and t0 == self.eos_id)
                or self._pos[slot] >= self.max_len)
        if done:
            req._mark_done(self.ticks, time.perf_counter())
            self.slot_req[slot] = None
            self._active[slot] = False
        else:
            self._active[slot] = True

    # ---- engine loop ----------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit + one batched decode + host sampling."""
        self._admit()
        active = np.nonzero(self._active)[0]
        if len(active) == 0:
            return 0
        pos = np.clip(self._pos, 0, self.max_len - 1)
        logits, self.cache = self._decode(
            self.params, self.cache,
            {"tokens": jnp.asarray(self._last[:, None])}, jnp.asarray(pos))
        lg = np.asarray(logits)[:, -1].astype(np.float32)
        for s in active:
            r = self.slot_req[s]
            tok = self._sample(lg[s], self._temps[s])
            r.output.append(tok)
            self._last[s] = tok
            self._pos[s] += 1
            self._remaining[s] -= 1
            done = (self._remaining[s] <= 0
                    or (self.eos_id is not None and tok == self.eos_id)
                    or self._pos[s] >= self.max_len)
            if done:
                r._mark_done(self.ticks, time.perf_counter())
                self.slot_req[s] = None
                self._active[s] = False
        self.ticks += 1
        return len(active)

    def run(self, max_ticks: int = 10_000) -> int:
        """Run to completion within the tick budget; returns the number of
        unfinished requests (0 when everything completed)."""
        return _drain_until_done(self, max_ticks)


# The seed engine's per-tick path lives on under this name (parity oracle
# + benchmark baseline), matching the *_reference convention of the sweep /
# cachesim / traffic engines.
engine_reference = EngineReference
