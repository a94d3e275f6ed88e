"""Family-generic slot-state banks (ISSUE 10): every registry config
serves batched with greedy parity vs ``EngineReference``.

The three slot-bank families (mamba2 ssm, recurrentgemma hybrid, whisper
encdec) get the full staggered / uneven-length / eos matrix at K=1 and
K=4 — the acceptance oracle for the StateBank refactor.  The stacked-KV
archs get a lighter parity smoke (their deep matrix already lives in
test_serve_engine.py on llama3-8b).  Bank metadata itself is pinned for
ALL archs: ``state_banks()`` must key exactly like the decode cache.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config, list_archs, reduced
from repro.models import build_model
from repro.models.api import StateBank
from repro.serve import (Engine, EngineReference, Request, mixed_requests,
                         run_staggered, staggered_groups)

MAX_LEN = 40
SLOTS = 3
BANK_ARCHS = ("mamba2-1.3b", "recurrentgemma-2b", "whisper-tiny")
KV_ARCHS = tuple(a for a in list_archs() if a not in BANK_ARCHS)


@functools.lru_cache(maxsize=None)
def _mp(arch):
    cfg = reduced(get_config(arch), dtype="float32")
    model = build_model(cfg, max_seq=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


def _workload(seed=5, n=6):
    return mixed_requests(n, seed=seed, vocab=512, prompt_lens=(2, 9),
                          max_new=(2, 8))


# --- bank metadata (all archs) ----------------------------------------------


@pytest.mark.parametrize("arch", list_archs())
def test_state_banks_key_exactly_like_the_cache(arch):
    model, _ = _mp(arch)
    banks = model.state_banks()
    defs = model.cache_defs(SLOTS, 16)
    assert set(banks) == set(defs), \
        "state_banks() must name every cache entry and nothing else"
    for n, b in banks.items():
        assert isinstance(b, StateBank) and b.name == n
        shape = defs[n].shape
        assert b.batch_axis < len(shape)
        assert shape[b.batch_axis] == SLOTS, \
            f"bank {n}: batch_axis {b.batch_axis} is not the slot axis"
        if b.kind in ("kv", "ring"):
            assert b.seq_axis is not None and shape[b.seq_axis] <= 16


def test_statebank_contract_validation():
    with pytest.raises(ValueError, match="kind"):
        StateBank("x", "paged", batch_axis=0)
    with pytest.raises(ValueError, match="batch_axis"):
        StateBank("x", "kv", batch_axis=2, seq_axis=1)


# --- greedy parity: the slot-bank families, full matrix ---------------------


@pytest.mark.parametrize("arch", BANK_ARCHS)
def test_bank_family_parity_staggered_uneven_eos(arch):
    """Staggered arrivals, uneven prompt/output lengths, eos exits: fused
    outputs == reference outputs, token for token, at K=1 and K=4."""
    model, params = _mp(arch)
    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    probe = run_staggered(ref, staggered_groups(_workload(), 2))
    eos = next(t for o in probe.values() for t in o[1:])

    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN,
                          eos_id=eos)
    out_ref = run_staggered(ref, staggered_groups(_workload(), 2))
    assert any(o[-1] == eos and len(o) > 1 for o in out_ref.values()), \
        "workload must exercise an eos exit"
    for K in (1, 4):
        eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                     eos_id=eos, ticks_per_sync=K, record_traffic=False)
        out = run_staggered(eng, staggered_groups(_workload(), 2))
        assert out == out_ref, f"{arch} K={K} diverged from reference"


@pytest.mark.parametrize("arch", BANK_ARCHS)
def test_bank_family_serve_records_and_verdicts(arch):
    """With ``record_traffic`` each slot-bank family's records are
    family-tagged, recurrent banks carry their write-heavier read split,
    and every record gets an NVM verdict."""
    from repro.core.crosslayer import RECURRENT_READ_FRACTION
    model, params = _mp(arch)
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=4, record_traffic=True)
    run_staggered(eng, staggered_groups(_workload(n=4), 2))
    fam = model.cfg.family
    recs = eng.serve_records()
    assert {r["kind"] for r in recs} == {"decode", "prefill"}
    for r in recs:
        assert r["family"] == fam
        assert r["shape"].startswith(f"serve_{fam}_")
        assert r["roofline"]["bytes_per_device"] > 0
        assert r.get("read_fraction") == (
            RECURRENT_READ_FRACTION if fam in ("ssm", "hybrid") else None)
    verdicts = eng.nvm_verdicts()
    assert len(verdicts) == len(recs)
    assert all(set(v.energy_ratio) == {"STT", "SOT"} for v in verdicts)


@pytest.mark.parametrize("arch", BANK_ARCHS)
def test_bank_family_outputs_schedule_independent(arch):
    model, params = _mp(arch)
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=3, record_traffic=False)
    out_a = run_staggered(eng, staggered_groups(_workload(seed=6), 1))
    eng.reset()
    out_b = run_staggered(eng, [list(_workload(seed=6))])
    assert out_a == out_b


# --- greedy parity: stacked-KV archs, light smoke ---------------------------


@pytest.mark.parametrize("arch", KV_ARCHS)
def test_kv_arch_parity_smoke(arch):
    model, params = _mp(arch)
    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    out_ref = run_staggered(ref, staggered_groups(_workload(n=5), 2))
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=4, record_traffic=False)
    out = run_staggered(eng, staggered_groups(_workload(n=5), 2))
    assert out == out_ref, f"{arch} diverged from reference"


# --- bank semantics ---------------------------------------------------------


def test_recurrent_slot_free_resets_banks():
    """After every request drains, all guarded bank rows must sit at
    their reset value — stale recurrent state on slot reuse was the
    failure mode the reset protocol exists for."""
    model, params = _mp("recurrentgemma-2b")
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=2, record_traffic=False)
    for r in _workload(seed=3, n=5):
        eng.submit(r)
    assert eng.run() == 0
    for n in eng._guarded:
        want = np.full_like(np.asarray(eng.cache[n]), eng._bank_reset[n])
        np.testing.assert_array_equal(np.asarray(eng.cache[n]), want,
                                      err_msg=f"bank {n} kept stale state")


def test_encdec_enc_bank_row_isolated():
    """Admitting a whisper request writes ONLY its slot's enc/out row;
    the encoder program runs at the fixed (slots, max_len) shape so both
    engines' rows are bitwise identical."""
    model, params = _mp("whisper-tiny")
    eng = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                 ticks_per_sync=1, record_traffic=False)
    eng.submit(Request(uid=0, prompt=[5, 7, 11], max_new_tokens=4))
    eng._admit()
    enc = np.asarray(eng.cache["enc/out"])
    assert np.abs(enc[0]).sum() > 0, "admitted row must hold encoder output"
    np.testing.assert_array_equal(enc[1:], np.zeros_like(enc[1:]))

    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    ref._prefill(0, Request(uid=0, prompt=[5, 7, 11], max_new_tokens=4))
    np.testing.assert_array_equal(
        np.asarray(ref.cache["enc/out"])[0], enc[0],
        err_msg="enc/out rows must be bitwise identical across engines")
