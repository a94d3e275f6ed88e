"""Fused serve engine: slot isolation, greedy parity vs the reference
per-tick path, sampling/termination semantics, and serve-mode NVM records.

The load-bearing invariant: with correct slot isolation a request's greedy
output depends only on its own prompt, so outputs must be identical under
any arrival pattern, any ticks_per_sync, and under ``EngineReference``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serve import (Engine, EngineReference, Request, Tracer,
                         latency_summary, mixed_requests, poisson_requests,
                         run_arrivals, run_staggered, staggered_groups,
                         validate_chrome_trace)

MAX_LEN = 48
SLOTS = 3


@pytest.fixture(scope="module")
def mp():
    cfg = reduced(get_config("llama3-8b"), dtype="float32")
    model = build_model(cfg, max_seq=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


def _workload(n=7, seed=0, **kw):
    kw.setdefault("prompt_lens", (2, 9))
    kw.setdefault("max_new", (2, 8))
    return mixed_requests(n, seed=seed, vocab=512, **kw)


def _eos_exit_token(outputs):
    """A token some eos-free output first emits at index >= 1.  With slot
    isolation that output's prefix is schedule-independent, so an eos run
    must end that request exactly there, after at least one decode tick
    (a token also emitted earlier in the same output would end it
    sooner, often at its first token)."""
    return next(o[i] for o in outputs.values() for i in range(1, len(o))
                if o[i] not in o[:i])


# --- per-row position vectors (the model-side contract) ---------------------


def test_vector_cache_pos_matches_per_row_scalar_decode(mp):
    model, params = mp
    B = 3
    key = jax.random.PRNGKey(1)
    cache = model.init_cache(B, 16)
    cache = {k: jax.random.normal(key, v.shape, v.dtype) * 0.1
             for k, v in cache.items()}
    pos = jnp.asarray([2, 5, 9], jnp.int32)
    toks = jnp.asarray([[7], [11], [13]], jnp.int32)
    lg_vec, cache_vec = model.decode_step(params, cache, {"tokens": toks},
                                          pos)
    for b in range(B):
        row_cache = {k: v[:, b:b + 1] for k, v in cache.items()}
        lg_row, row_new = model.decode_step(
            params, row_cache, {"tokens": toks[b:b + 1]}, int(pos[b]))
        np.testing.assert_allclose(np.asarray(lg_vec[b]),
                                   np.asarray(lg_row[0]),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(cache_vec["k"][:, b]),
                                   np.asarray(row_new["k"][:, 0]),
                                   atol=1e-5, rtol=1e-5)


def test_unsupported_families_fail_fast_with_structured_error(mp):
    """PagedEngine is KV-decoder-only by design and must say so at
    construction time via UnsupportedFamilyError; the slot-bank engines
    (Engine / EngineReference) accept every family."""
    from repro.serve import PagedEngine, UnsupportedFamilyError
    ssm = reduced(get_config("mamba2-1.3b"), dtype="float32")
    ssm_model = build_model(ssm, max_seq=16)
    with pytest.raises(UnsupportedFamilyError,
                       match="KV-decoder-only") as ei:
        PagedEngine(ssm_model, None, slots=1, max_len=16)
    assert ei.value.family == "ssm"
    assert "ssm" not in ei.value.supported
    assert {"dense", "moe", "vlm"} <= set(ei.value.supported)
    assert isinstance(ei.value, ValueError)   # old excepts keep working
    # the slot-bank engines accept recurrent families now ...
    eng = Engine(ssm_model, None, slots=1, max_len=16,
                 record_traffic=False)
    assert eng._guarded
    ref = EngineReference(ssm_model, None, slots=1, max_len=16)
    assert ref._guarded
    # ... but the fused-KV pallas decode kernel stays stacked-KV-only
    with pytest.raises(ValueError, match="pallas_decode"):
        Engine(ssm_model, None, slots=1, max_len=16,
               attn_impl="pallas_decode", record_traffic=False)
    enc = reduced(get_config("whisper-tiny"), dtype="float32")
    enc_model = build_model(enc, max_seq=16)
    with pytest.raises(UnsupportedFamilyError, match="encdec"):
        PagedEngine(enc_model, None, slots=1, max_len=16)


# --- slot isolation (the seed _prefill broadcast-corruption bug) ------------


@pytest.mark.parametrize("make", [
    lambda m, p: Engine(m, p, slots=SLOTS, max_len=MAX_LEN,
                        ticks_per_sync=2, record_traffic=False),
    lambda m, p: Engine(m, p, slots=SLOTS, max_len=MAX_LEN,
                        ticks_per_sync=2, record_traffic=False,
                        attn_impl="pallas_decode"),
    lambda m, p: EngineReference(m, p, slots=SLOTS, max_len=MAX_LEN),
], ids=["fused", "fused-pallas", "reference"])
def test_prefill_does_not_touch_other_slots(mp, make):
    """Prefill B while A is mid-decode: A's cache rows and final output
    must be exactly what they would have been with A running alone."""
    model, params = mp
    req_a = Request(uid=0, prompt=[5, 7, 11, 13], max_new_tokens=10)
    req_alone = Request(uid=0, prompt=list(req_a.prompt), max_new_tokens=10)
    alone = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                   ticks_per_sync=2, record_traffic=False)
    alone.submit(req_alone)
    alone.run()
    alone_out = list(req_alone.output)

    eng = make(model, params)
    eng.submit(req_a)
    eng.step()                      # A admitted into slot 0, decoding
    assert eng.slot_req[0] is req_a and not req_a.done
    rows_before = {k: np.array(np.asarray(v)[:, 0])
                   for k, v in eng.cache.items()}
    eng.submit(Request(uid=1, prompt=[101, 102, 103], max_new_tokens=4))
    eng._admit()                    # B prefills into slot 1
    rows_after = {k: np.array(np.asarray(v)[:, 0])
                  for k, v in eng.cache.items()}
    for k in rows_before:
        np.testing.assert_array_equal(rows_before[k], rows_after[k])
    eng.run()
    assert req_a.done
    assert list(req_a.output) == alone_out


def test_seed_broadcast_bug_shape_is_gone(mp):
    """The seed wrote jnp.full((slots, 1), token) per prefill token — every
    slot's cache row changed.  Directly assert the fused prefill leaves
    non-admitted rows bit-identical even with garbage in them."""
    model, params = mp
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=1, record_traffic=False)
    key = jax.random.PRNGKey(3)
    eng.cache = {k: jax.random.normal(key, v.shape, v.dtype)
                 for k, v in eng.cache.items()}
    before = {k: np.array(np.asarray(v)) for k, v in eng.cache.items()}
    eng.submit(Request(uid=0, prompt=[9, 8, 7], max_new_tokens=2))
    eng._admit()
    after = {k: np.asarray(v) for k, v in eng.cache.items()}
    for k in before:
        # slot 0 changed where the prompt landed ...
        assert not np.array_equal(before[k][:, 0, :4], after[k][:, 0, :4])
        # ... every other slot is untouched
        np.testing.assert_array_equal(before[k][:, 1:], after[k][:, 1:])


# --- greedy parity over mixed workloads -------------------------------------


def test_mixed_workload_greedy_parity_vs_reference(mp):
    """Staggered arrivals, uneven prompt/output lengths, eos exits: fused
    outputs == reference outputs, token for token, at K=1 and K=4."""
    model, params = mp
    # probe the same workload eos-free and pick an eos that ends one
    # request after a decode tick
    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    probe_out = run_staggered(ref, staggered_groups(_workload(seed=5), 2))
    eos = _eos_exit_token(probe_out)

    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN,
                          eos_id=eos)
    out_ref = run_staggered(ref, staggered_groups(_workload(seed=5), 2))
    assert any(o[-1] == eos and len(o) > 1 for o in out_ref.values()), \
        "workload must exercise an eos exit"
    for K in (1, 4):
        eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                     eos_id=eos, ticks_per_sync=K, record_traffic=False)
        out = run_staggered(eng, staggered_groups(_workload(seed=5), 2))
        assert out == out_ref, f"K={K} diverged from reference"


def test_mixed_workload_greedy_parity_pallas_engine(mp):
    """The Pallas decode kernel (fused KV scatter, interpret mode on CPU)
    behind attn_impl='pallas_decode': greedy outputs must match the
    reference per-tick engine token for token over staggered arrivals,
    uneven lengths, and eos exits, at K=1 and K=4."""
    model, params = mp
    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    probe_out = run_staggered(ref, staggered_groups(_workload(seed=5), 2))
    eos = _eos_exit_token(probe_out)

    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN,
                          eos_id=eos)
    out_ref = run_staggered(ref, staggered_groups(_workload(seed=5), 2))
    assert any(o[-1] == eos and len(o) > 1 for o in out_ref.values())
    for K in (1, 4):
        eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                     eos_id=eos, ticks_per_sync=K, record_traffic=False,
                     attn_impl="pallas_decode")
        out = run_staggered(eng, staggered_groups(_workload(seed=5), 2))
        assert out == out_ref, f"pallas K={K} diverged from reference"


def test_attn_impl_validated_and_recorded(mp):
    model, params = mp
    with pytest.raises(ValueError, match="attn_impl"):
        Engine(model, params, slots=1, max_len=8, attn_impl="triton")
    eng = Engine(model, params, slots=2, max_len=16, ticks_per_sync=2,
                 record_traffic=True, attn_impl="pallas_decode")
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=3))
    eng.run()
    decode = next(r for r in eng.serve_records() if r["kind"] == "decode")
    assert decode["attn_impl"] == "pallas_decode"


def test_traffic_analysis_failure_raises(mp, monkeypatch):
    """With record_traffic on, a failed analysis raises instead of leaving
    its phase out of serve_records() behind a warning."""
    from repro.launch import roofline
    model, params = mp

    def broken(compiled):
        raise RuntimeError("analysis broke")

    monkeypatch.setattr(roofline, "analyze", broken)
    eng = Engine(model, params, slots=2, max_len=16, ticks_per_sync=2,
                 record_traffic=True)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=3))
    with pytest.raises(RuntimeError, match="analysis broke"):
        eng.run()


def test_outputs_are_schedule_independent(mp):
    """Same requests, different arrival pattern -> identical outputs."""
    model, params = mp
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=3, record_traffic=False)
    out_a = run_staggered(eng, staggered_groups(_workload(seed=6), 1))
    eng.reset()
    out_b = run_staggered(eng, [list(_workload(seed=6))])
    assert out_a == out_b


# --- sampling ---------------------------------------------------------------


def test_temperature_zero_matches_manual_argmax(mp):
    model, params = mp
    prompt = [5, 7, 11]
    m = 5
    req = Request(uid=0, prompt=list(prompt), max_new_tokens=m)
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=2, record_traffic=False)
    eng.submit(req)
    eng.run()
    # manual greedy rollout through the scalar decode path
    cache = model.init_cache(1, MAX_LEN)
    seq, out = list(prompt), []
    for pos in range(len(prompt) + m - 1):
        tok = seq[pos] if pos < len(seq) else out[-1]
        lg, cache = model.decode_step(
            params, cache, {"tokens": jnp.full((1, 1), tok, jnp.int32)}, pos)
        if pos >= len(seq) - 1:
            out.append(int(jnp.argmax(lg[0, -1])))
    assert req.output == out


def test_temperature_sampling_reproducible_and_seeded(mp):
    model, params = mp
    def go(seed):
        eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN, seed=seed,
                     ticks_per_sync=2, record_traffic=False)
        reqs = _workload(5, seed=7, temperature=0.9, temperature_every=1)
        return run_staggered(eng, staggered_groups(reqs, 2))
    a, b, c = go(0), go(0), go(1)
    assert a == b, "same seed must reproduce temperature>0 outputs"
    assert a != c, "different seed should change temperature>0 outputs"
    assert all(0 <= t < 512 for o in a.values() for t in o)


# --- termination ------------------------------------------------------------


def test_max_new_tokens_exit_and_tick(mp):
    model, params = mp
    for m in (1, 4):
        req = Request(uid=0, prompt=[3, 4, 5], max_new_tokens=m)
        eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                     ticks_per_sync=1, record_traffic=False)
        eng.submit(req)
        eng.run()
        assert req.done and len(req.output) == m
        # t0 emits at the admission tick (which the first decode tick
        # shares, as in the seed step()), then m-1 decode ticks
        assert req.done_tick == (m - 2 if m > 1 else 0)
        assert eng.slot_req == [None] * SLOTS


def test_max_len_exit_caps_output(mp):
    model, params = mp
    short = 8
    prompt = [2, 3, 4, 5, 6]
    req = Request(uid=0, prompt=prompt, max_new_tokens=50)
    eng = Engine(model, params, slots=2, max_len=short,
                 ticks_per_sync=2, record_traffic=False)
    eng.submit(req)
    eng.run()
    # prefill fills len(prompt) positions; decode can write the remaining
    # max_len - len(prompt) positions, each emitting one token, plus t0
    assert req.done and len(req.output) == short - len(prompt) + 1


def test_eos_and_slot_free_tick_parity_vs_reference(mp):
    model, params = mp
    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    probe_out = run_staggered(
        ref, staggered_groups(_workload(6, seed=9, max_new=(3, 10)), 2))
    eos = _eos_exit_token(probe_out)

    def ticks_of(engine_cls, **kw):
        reqs = _workload(6, seed=9, max_new=(3, 10))
        eng = engine_cls(model, params, slots=SLOTS, max_len=MAX_LEN,
                         eos_id=eos, **kw)
        out = run_staggered(eng, staggered_groups(reqs, 2))
        return out, {r.uid: r.done_tick for r in reqs}

    out_ref, ticks_ref = ticks_of(EngineReference)
    out_fused, ticks_fused = ticks_of(
        Engine, ticks_per_sync=1, record_traffic=False)
    assert out_fused == out_ref
    assert ticks_fused == ticks_ref, \
        "K=1 slot-free ticks must match the per-tick reference"
    # eos path exercised: some request stopped early on the eos token
    assert any(o[-1] == eos and len(o) > 1 for o in out_ref.values())


def test_tick_stamp_parity_vs_reference(mp):
    """Request docstring tick semantics, enforced: admit/first-token/done
    ticks from the fused K=1 engine match the per-tick reference exactly,
    including max_new_tokens=1 requests that terminate at prefill."""
    model, params = mp

    def stamps_of(engine_cls, **kw):
        # max_new=(1, 6) forces prefill-terminated requests into the mix
        reqs = poisson_requests(8, seed=11, vocab=512, arrival_rate=0.4,
                                burst_amp=0.5, prompt_bounds=(2, 9),
                                new_bounds=(1, 6))
        eng = engine_cls(model, params, slots=SLOTS, max_len=MAX_LEN, **kw)
        out = run_arrivals(eng, reqs)
        return out, {r.uid: (r.admit_tick, r.first_token_tick, r.done_tick)
                     for r in reqs}

    out_ref, ref = stamps_of(EngineReference)
    out_fused, fused = stamps_of(Engine, ticks_per_sync=1,
                                 record_traffic=False)
    assert out_fused == out_ref
    assert fused == ref, "tick stamps diverged between engines"
    assert any(len(o) == 1 for o in out_ref.values()), \
        "workload must exercise a prefill-terminated (max_new=1) request"
    for uid, (admit, first, done) in ref.items():
        assert first == admit, "t0 is emitted at the admission tick"
        assert done == admit + len(out_ref[uid]) - 2 if len(out_ref[uid]) > 1 \
            else done == admit


def test_bursty_arrivals_outputs_schedule_independent(mp):
    """Greedy outputs under bursty Poisson admission == all-at-once batch:
    the slot-isolation invariant extended to the real traffic generator."""
    model, params = mp
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=3, record_traffic=False)
    reqs = poisson_requests(9, seed=3, vocab=512, arrival_rate=0.3,
                            burst_amp=0.8, burst_period=24.0,
                            prompt_bounds=(2, 9), new_bounds=(1, 7))
    out_bursty = run_arrivals(eng, reqs)
    assert len(out_bursty) == 9
    eng.reset()
    out_batch = run_staggered(eng, [list(poisson_requests(
        9, seed=3, vocab=512, arrival_rate=0.3, burst_amp=0.8,
        burst_period=24.0, prompt_bounds=(2, 9), new_bounds=(1, 7)))])
    assert out_bursty == out_batch


def test_run_budget_is_k_granular_and_reports_unfinished(mp):
    """run(max_ticks) must never overshoot the budget mid-window (the
    window scan length is static) and must report what's left."""
    model, params = mp
    eng = Engine(model, params, slots=2, max_len=MAX_LEN,
                 ticks_per_sync=4, record_traffic=False)
    for uid in range(3):
        eng.submit(Request(uid=uid, prompt=[2 + uid, 3], max_new_tokens=20))
    left = eng.run(max_ticks=6)      # one K=4 window fits, a second doesn't
    assert eng.ticks == 4, "a partial window must not run (no overshoot)"
    assert left == 3                 # 2 mid-decode in slots + 1 queued
    assert eng.run() == 0            # unlimited-by-default finishes the rest
    assert all(r is None for r in eng.slot_req)


def test_run_arrivals_strict_raises_on_budget_exhaustion(mp):
    model, params = mp
    eng = Engine(model, params, slots=1, max_len=MAX_LEN,
                 ticks_per_sync=2, record_traffic=False)
    reqs = poisson_requests(4, seed=0, vocab=512, arrival_rate=2.0,
                            prompt_bounds=(2, 4), new_bounds=(6, 10))
    with pytest.raises(RuntimeError, match="did not finish"):
        run_arrivals(eng, reqs, max_ticks=4)
    eng.reset()
    partial = run_arrivals(eng, poisson_requests(
        4, seed=0, vocab=512, arrival_rate=2.0, prompt_bounds=(2, 4),
        new_bounds=(6, 10)), max_ticks=4, strict=False)
    assert len(partial) < 4


def test_engine_latency_stamps_and_tracer(mp):
    """After an arrival-driven run every finished request carries the full
    stamp set, latency_summary has non-empty percentiles in both domains,
    and the tracer saw prefill / decode-window / drain spans that export
    to a valid chrome trace."""
    model, params = mp
    tracer = Tracer(name="test")
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=2, record_traffic=False, tracer=tracer)
    reqs = poisson_requests(6, seed=4, vocab=512, arrival_rate=0.5,
                            prompt_bounds=(2, 8), new_bounds=(2, 6))
    run_arrivals(eng, reqs)
    for r in reqs:
        assert r.done and r.submit_time is not None
        assert r.admit_time is not None and r.done_time is not None
        assert r.submit_tick <= r.admit_tick == r.first_token_tick
        assert r.submit_time <= r.admit_time <= r.done_time
    s = latency_summary(reqs)
    assert s["completed"] == s["n"] == 6
    for domain in ("wall", "ticks"):
        assert {"p50", "p95", "p99"} <= set(s[domain]["e2e_s" if domain ==
                                            "wall" else "e2e"])
    trace = tracer.to_chrome_trace()
    validate_chrome_trace(trace)
    cats = {e.get("cat") for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"prefill", "decode", "host"} <= cats


# --- request validation -----------------------------------------------------


def test_submit_never_raises_marks_failed_and_keeps_serving(mp):
    """Regression (DESIGN.md §16): a malformed request must NOT raise out
    of submit and wedge the caller's loop — it finalizes as FAILED with
    the validation message, and the engine keeps serving healthy work."""
    model, params = mp
    eng = Engine(model, params, slots=1, max_len=8, ticks_per_sync=1,
                 record_traffic=False)
    bad = [Request(uid=0, prompt=[], max_new_tokens=1),
           Request(uid=1, prompt=list(range(9)), max_new_tokens=1),
           Request(uid=2, prompt=[1], max_new_tokens=0)]
    for b in bad:
        assert eng.submit(b) is False
    assert [b.state for b in bad] == ["FAILED"] * 3
    assert "empty prompt" in bad[0].reason
    assert "exceeds" in bad[1].reason
    assert "max_new_tokens" in bad[2].reason
    assert len(eng._queue) == 0
    good = Request(uid=3, prompt=[1, 2, 3], max_new_tokens=3)
    assert eng.submit(good) is True
    assert eng.run() == 0 and good.done and good.state == "DONE"
    assert eng.resilience_stats()["failed"] == 3


# --- serve-mode NVM records -------------------------------------------------


def test_serve_records_and_nvm_verdicts(mp):
    model, params = mp
    eng = Engine(model, params, slots=2, max_len=16, ticks_per_sync=2,
                 record_traffic=True)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
    eng.submit(Request(uid=1, prompt=[4, 5], max_new_tokens=3))
    eng.run()
    recs = eng.serve_records()
    kinds = {r["kind"] for r in recs}
    assert "decode" in kinds and "prefill" in kinds
    for r in recs:
        assert r["roofline"]["bytes_per_device"] > 0
        assert r["roofline"]["memory_s"] > 0
    decode = next(r for r in recs if r["kind"] == "decode")
    assert decode["ticks"] == eng._counts["decode_ticks"] > 0
    verdicts = eng.nvm_verdicts()
    assert len(verdicts) == len(recs)
    for v in verdicts:
        assert set(v.energy_ratio) == {"STT", "SOT"}
        assert v.edp_ratio["SOT"] > 0


def test_analyze_serve_rejects_termless_records(mp):
    from repro.core.crosslayer import analyze_serve
    with pytest.raises(ValueError, match="roofline terms"):
        analyze_serve([{"arch": "x", "shape": "serve_decode", "mesh": "1dev",
                        "roofline": {"bytes_per_device": 1.0}}])
    assert analyze_serve([]) == []


def test_record_traffic_off_yields_no_records(mp):
    model, params = mp
    eng = Engine(model, params, slots=2, max_len=16, ticks_per_sync=2,
                 record_traffic=False)
    eng.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=3))
    eng.run()
    assert eng.serve_records() == []
    assert eng.nvm_verdicts() == []
