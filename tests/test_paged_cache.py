"""Paged KV cache stack: pool/radix-tree invariants (hypothesis), CoW
isolation, paged kernel vs oracle, fused sampling parity, and bitwise
greedy parity of ``PagedEngine`` against ``EngineReference`` on the
standard workloads (DESIGN.md §15).

The load-bearing invariant is the same one the dense engine rests on:
with correct page isolation a request's greedy output depends only on
its own prompt — so sharing prefix pages, CoW'ing boundaries, evicting
tree leaves, or deferring admission must never change a single token.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops, ref
from repro.models import build_model
from repro.serve import (EngineReference, PagedEngine, PagePool,
                         PagePoolExhausted, RadixTree,
                         Request, mixed_requests, pages_for, run_staggered,
                         shared_prefix_requests, staggered_groups)

MAX_LEN = 48
SLOTS = 3
PS = 8


@pytest.fixture(scope="module")
def mp():
    cfg = reduced(get_config("llama3-8b"), dtype="float32")
    model = build_model(cfg, max_seq=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


def _ref_outputs(mp, reqs, group=SLOTS, eos_id=7):
    model, params = mp
    eng = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN,
                          eos_id=eos_id)
    return run_staggered(eng, staggered_groups(copy.deepcopy(reqs), group))


def _paged(mp, eos_id=7, **kw):
    model, params = mp
    kw.setdefault("record_traffic", False)
    return PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                       page_size=PS, eos_id=eos_id, **kw)


# --- host-side pool + tree properties ---------------------------------------


def test_pages_for():
    assert pages_for(0, 8) == 0
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2


def test_pool_alloc_release_cycle():
    pool = PagePool(4, 8)
    a = pool.alloc(3)
    assert sorted(a) == [0, 1, 2] and pool.free_pages == 1
    with pytest.raises(PagePoolExhausted, match="requested 2.*1 free"):
        pool.alloc(2)                     # short -> raise, nothing claimed
    assert pool.free_pages == 1
    pool.share(a[0])
    pool.release(a[0])
    assert pool.free_pages == 1           # still one ref on page 0
    for p in a:
        pool.release(p)
    assert pool.free_pages == 4 and pool.hwm == 3
    with pytest.raises(ValueError, match="dead page"):
        pool.release(a[0])
    pool.check()


def test_tree_match_insert_cow_boundary_coverage():
    pool = PagePool(16, 4)
    tree = RadixTree(pool)
    pages = pool.alloc(3)                 # covers 10 tokens at ps=4
    tree.insert([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], pages)
    for p in pages:                       # tree refs alone keep them live
        pool.release(p)
    # full match mid-edge: 6 tokens -> ceil(6/4)=2 pages, the second is
    # the partially-covered boundary page the engine must CoW
    m, shared = tree.match([1, 2, 3, 4, 5, 6])
    assert m == 6 and shared == pages[:2]
    # divergence after 4 tokens -> exactly the full page is reusable
    m, shared = tree.match([1, 2, 3, 4, 99, 98])
    assert m == 4 and shared == pages[:1]
    m, shared = tree.match([42])
    assert (m, shared) == (0, [])
    pool.check(tree.held_refs())


# (hypothesis property tests live in tests/test_paged_properties.py,
# following the *_properties.py convention so this file runs without the
# optional dependency)


# --- paged decode kernel vs oracle ------------------------------------------


def _rand_paged(seed, B=3, nb=4, ps=8, K=2, G=2, hd=16, share=True):
    rng = np.random.default_rng(seed)
    P = B * nb + 1                        # + TRASH
    k = jnp.asarray(rng.normal(size=(P, ps, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, ps, K, hd)), jnp.float32)
    pt = np.arange(B * nb).reshape(B, nb).astype(np.int32)
    if share:                             # rows 1+ share row 0's first page
        pt[1:, 0] = pt[0, 0]
    q = jnp.asarray(rng.normal(size=(B, K * G, hd)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, nb * ps, size=B), jnp.int32)
    return q, k, v, jnp.asarray(pt), pos


@pytest.mark.parametrize("window", [0, 11])
def test_paged_kernel_matches_oracle(window):
    q, k, v, pt, pos = _rand_paged(0)
    out = ops.paged_decode_attention(q, k, v, pt, pos, window)
    want = ref.paged_decode_attention_ref(q, k, v, pt, pos, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_fused_scatter_bitwise_and_attends_new_kv():
    q, k, v, pt, pos = _rand_paged(1, share=False)
    rng = np.random.default_rng(2)
    nk = jnp.asarray(rng.normal(size=(3, 2, 16)), jnp.float32)
    nv = jnp.asarray(rng.normal(size=(3, 2, 16)), jnp.float32)
    o, k2, v2 = ops.paged_decode_attention_fused(q, k, v, nk, nv, pt, pos, 0)
    ps = 8
    ek, ev = np.array(k), np.array(v)
    for b in range(3):
        page = int(pt[b, int(pos[b]) // ps])
        ek[page, int(pos[b]) % ps] = np.asarray(nk[b])
        ev[page, int(pos[b]) % ps] = np.asarray(nv[b])
    np.testing.assert_array_equal(np.asarray(k2), ek)
    np.testing.assert_array_equal(np.asarray(v2), ev)
    want = ref.paged_decode_attention_ref(q, jnp.asarray(ek), jnp.asarray(ev),
                                          pt, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_ignores_pages_beyond_pos():
    """DMA clamping: garbage in pages past a row's depth cannot leak."""
    q, k, v, pt, pos = _rand_paged(3, share=False)
    pos = jnp.asarray([2, 9, 17], jnp.int32)     # well inside the table
    base = ops.paged_decode_attention(q, k, v, pt, pos, 0)
    k2 = k.at[np.asarray(pt)[:, 3]].set(1e9)     # poison last mapped pages
    v2 = v.at[np.asarray(pt)[:, 3]].set(1e9)
    poisoned = ops.paged_decode_attention(q, k2, v2, pt, pos, 0)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


# --- fused sampling ----------------------------------------------------------


@pytest.mark.parametrize("B,V,bv", [
    (5, 512, 128),       # vocab tiles exactly
    (9, 1000, 256),      # ragged rows and ragged last vocab block
    (8, 32064, 2048),    # phi3-mini vocab: no 128-aligned divisor
])
def test_fused_sample_greedy_bitwise_argmax_with_cross_block_ties(B, V, bv):
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(B, V)).astype(np.float32)
    logits[1, 100] = logits[1, 300] = 50.0       # tie across blocks
    logits[2, 0] = logits[2, V - 1] = 50.0       # tie at both edges
    logits[3, V - 1] = 60.0                      # max in the ragged block
    lg = jnp.asarray(logits)
    temps = jnp.zeros(B, jnp.float32)
    key = jax.random.PRNGKey(42)
    got = ops.fused_sample(lg, temps, key, bv=bv)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(lg, axis=-1)))


def test_fused_sample_temperature_deterministic_and_in_range():
    rng = np.random.default_rng(1)
    lg = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    temps = jnp.asarray([0.0, 0.7, 1.3, 0.0], jnp.float32)
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(9)
    a = np.asarray(ops.fused_sample(lg, temps, k1))
    b = np.asarray(ops.fused_sample(lg, temps, k1))
    c = np.asarray(ops.fused_sample(lg, temps, k2))
    np.testing.assert_array_equal(a, b)          # same key -> same draw
    assert ((a >= 0) & (a < 256)).all()
    # greedy rows ignore the key entirely
    argm = np.asarray(jnp.argmax(lg, axis=-1))
    assert a[0] == c[0] == argm[0] and a[3] == c[3] == argm[3]


def test_fused_sample_tracks_softmax_distribution():
    """Gumbel-max frequencies approach softmax(logits/T) probabilities."""
    lg = jnp.asarray(np.tile([[2.0, 1.0, 0.0, -1e9]], (256, 1)), jnp.float32)
    temps = jnp.full(256, 1.0, jnp.float32)
    counts = np.zeros(4)
    for s in range(8):
        toks = np.asarray(ops.fused_sample(lg, temps, jax.random.PRNGKey(s)))
        counts += np.bincount(toks, minlength=4)
    freq = counts / counts.sum()
    want = np.asarray(jax.nn.softmax(jnp.asarray([2.0, 1.0, 0.0, -1e9])))
    np.testing.assert_allclose(freq, want, atol=0.05)


# --- engine parity ----------------------------------------------------------


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_paged"])
@pytest.mark.parametrize("k", [1, 4])
def test_paged_engine_bitwise_parity_mixed_staggered_eos(mp, attn_impl, k):
    reqs = mixed_requests(8, seed=11, vocab=512, prompt_lens=(2, 12),
                          max_new=(2, 9))
    want = _ref_outputs(mp, reqs, group=2)
    eng = _paged(mp, ticks_per_sync=k, attn_impl=attn_impl)
    got = run_staggered(eng, staggered_groups(copy.deepcopy(reqs), 2))
    assert got == want
    eng.pool.check(eng.tree.held_refs())   # all slots free -> tree-only refs


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_paged"])
def test_paged_engine_bitwise_parity_shared_prefix_cow(mp, attn_impl):
    # template_len 26 % 8 = 2 -> every reuse CoWs a boundary page
    reqs = shared_prefix_requests(9, seed=4, vocab=512, num_templates=2,
                                  template_len=26, suffix_lens=(2, 6),
                                  max_new=(2, 8))
    want = _ref_outputs(mp, reqs, group=SLOTS)
    eng = _paged(mp, ticks_per_sync=4, attn_impl=attn_impl)
    got = run_staggered(eng, staggered_groups(copy.deepcopy(reqs), SLOTS))
    assert got == want
    st = eng.paged_stats()
    assert st["cow_copies"] > 0 and st["prefix_tokens"] > 0
    eng.pool.check(eng.tree.held_refs())


def test_cow_isolation_owner_keeps_decoding_into_boundary_page(mp):
    """A long-running owner writes decode KV into its boundary page AFTER
    the tree registered it; a sharer CoWs that page.  Both outputs must
    equal their solo runs bit-for-bit."""
    template = list(range(100, 126))              # 26 tokens, 26 % 8 != 0
    a = Request(uid=0, prompt=template + [7, 9], max_new_tokens=14)
    b = Request(uid=1, prompt=template + [3, 5], max_new_tokens=6)
    solo = {}
    for r in (a, b):
        solo.update(_ref_outputs(mp, [r], group=1))
    eng = _paged(mp, ticks_per_sync=2)
    eng.submit(copy.deepcopy(a))
    eng.step()                                    # owner decoding already
    got = run_staggered(eng, [[copy.deepcopy(b)]])
    assert got[1] == solo[1]
    assert eng.paged_stats()["cow_copies"] >= 1


def test_tight_pool_defers_and_stays_bitwise(mp):
    reqs = shared_prefix_requests(8, seed=5, vocab=512, num_templates=2,
                                  template_len=26, suffix_lens=(2, 6),
                                  max_new=(2, 8))
    want = _ref_outputs(mp, reqs)
    nb = MAX_LEN // PS
    eng = _paged(mp, ticks_per_sync=2, num_pages=2 * nb + 2)
    got = run_staggered(eng, staggered_groups(copy.deepcopy(reqs), SLOTS))
    assert got == want
    st = eng.paged_stats()
    assert st["deferred"] > 0                     # pressure actually hit
    assert st["pages_hwm"] <= 2 * nb + 2
    eng.pool.check(eng.tree.held_refs())


def test_eviction_under_pressure_recycles_tree_pages(mp):
    """Distinct prompts with no sharing: once the pool fills with dead
    requests' tree-pinned pages, admission must LRU-evict leaves instead
    of deferring forever."""
    reqs = mixed_requests(10, seed=2, vocab=512, prompt_lens=(9, 14),
                          max_new=(2, 4))
    want = _ref_outputs(mp, reqs, group=1)
    nb = MAX_LEN // PS
    eng = _paged(mp, ticks_per_sync=2, num_pages=2 * nb)
    got = run_staggered(eng, staggered_groups(copy.deepcopy(reqs), 1))
    assert got == want
    assert eng.paged_stats()["evicted_pages"] > 0
    eng.pool.check(eng.tree.held_refs())


def test_paged_engine_fused_sampling_greedy_parity(mp):
    reqs = mixed_requests(6, seed=9, vocab=512, prompt_lens=(2, 10),
                          max_new=(2, 7))
    want = _ref_outputs(mp, reqs)
    eng = _paged(mp, ticks_per_sync=4, attn_impl="pallas_paged",
                 sample_impl="pallas")
    got = run_staggered(eng, staggered_groups(copy.deepcopy(reqs), SLOTS))
    assert got == want


def test_charge_prefill_ticks_rewards_prefix_sharing(mp):
    """With prefill charged to the tick clock, the paged engine's mean
    TTFT on a shared-prefix workload beats the dense engine's by the
    margin prefix sharing buys (the bench asserts >= 1.5x; here we pin
    the direction and that outputs stay bitwise-identical)."""
    from repro.serve import Engine, latency_summary
    model, params = mp
    reqs = shared_prefix_requests(9, seed=6, vocab=512, num_templates=2,
                                  template_len=26, suffix_lens=(2, 6),
                                  max_new=(3, 8))
    want = _ref_outputs(mp, reqs)
    dense = Engine(model, params, slots=SLOTS, max_len=MAX_LEN, eos_id=7,
                   ticks_per_sync=2, record_traffic=False,
                   charge_prefill_ticks=True)
    rd = copy.deepcopy(reqs)
    assert run_staggered(dense, staggered_groups(rd, SLOTS)) == want
    paged = _paged(mp, ticks_per_sync=2, charge_prefill_ticks=True)
    rp = copy.deepcopy(reqs)
    assert run_staggered(paged, staggered_groups(rp, SLOTS)) == want
    ttft_d = latency_summary(rd)["ticks"]["ttft"]["mean"]
    ttft_p = latency_summary(rp)["ticks"]["ttft"]["mean"]
    assert ttft_p < ttft_d


# --- serve-mode NVM verdict plumbing ----------------------------------------


def _decode_rec(**extra):
    roof = {"flops_per_device": 1e9, "bytes_per_device": 1e8,
            "collective_bytes": 0.0, "compute_s": 1e-4, "memory_s": 8e-4,
            "collective_s": 0.0}
    return {"arch": "a", "mesh": "1dev", "kind": "decode",
            "shape": "serve_decode_b3_l48", "ticks": 10,
            "roofline": roof, **extra}


def test_unique_page_fraction_scales_verdict_traffic():
    from repro.core.crosslayer import analyze_serve
    full = analyze_serve([_decode_rec()])[0]
    half = analyze_serve([_decode_rec(unique_page_fraction=0.5)])[0]
    assert half.reads == pytest.approx(full.reads * 0.5)
    assert half.writes == pytest.approx(full.writes * 0.5)
    assert half.step_s < full.step_s      # memory-bound window shrinks
    with pytest.raises(ValueError, match="unique_page_fraction"):
        analyze_serve([_decode_rec(unique_page_fraction=0.0)])


def test_paged_serve_records_carry_measured_fraction(mp):
    model, params = mp
    eng = PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                      page_size=PS, eos_id=7, ticks_per_sync=2)
    reqs = shared_prefix_requests(6, seed=3, vocab=512, num_templates=1,
                                  template_len=26, suffix_lens=(2, 5),
                                  max_new=(3, 6))
    run_staggered(eng, staggered_groups(reqs, SLOTS))
    recs = eng.serve_records()
    dec = [r for r in recs if r["kind"] == "decode"]
    assert dec and 0.0 < dec[0]["unique_page_fraction"] < 1.0
    verdicts = eng.nvm_verdicts()
    assert verdicts and all(v.reads > 0 for v in verdicts)


# --- constructor validation --------------------------------------------------


def test_paged_engine_validation(mp):
    model, params = mp
    with pytest.raises(ValueError, match="multiple of page_size"):
        PagedEngine(model, params, slots=2, max_len=50, page_size=8)
    with pytest.raises(ValueError, match="full-length"):
        PagedEngine(model, params, slots=2, max_len=48, page_size=8,
                    num_pages=3)
    with pytest.raises(ValueError, match="attn_impl"):
        PagedEngine(model, params, slots=2, max_len=48, page_size=8,
                    attn_impl="pallas_decode")
    with pytest.raises(ValueError, match="sample_impl"):
        PagedEngine(model, params, slots=2, max_len=48, page_size=8,
                    sample_impl="bogus")
