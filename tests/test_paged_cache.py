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
from repro.kernels import ops, paged_attention, ref
from repro.models import build_model
from repro.serve import (EngineReference, PagedEngine, PagePool,
                         PagePoolExhausted, RadixTree,
                         Request, Tracer, mixed_requests, pages_for,
                         run_staggered, shared_prefix_requests,
                         staggered_groups)

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


def _rand_paged(seed, B=3, nb=4, ps=8, K=2, G=2, hd=16, share=True,
                pos=None, idle=()):
    """Random pool (P = B*nb + 1 pages, the last TRASH), private page-table
    rows (rows 1+ share row 0's first page when ``share``), queries and
    positions.  Rows in ``idle`` are freed slots: a table row of TRASH
    only and a stale position."""
    rng = np.random.default_rng(seed)
    P = B * nb + 1                        # + TRASH
    k = jnp.asarray(rng.normal(size=(P, ps, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, ps, K, hd)), jnp.float32)
    pt = np.arange(B * nb).reshape(B, nb).astype(np.int32)
    if share:
        pt[1:, 0] = pt[0, 0]
    pt[list(idle)] = P - 1
    q = jnp.asarray(rng.normal(size=(B, K * G, hd)), jnp.float32)
    if pos is None:
        pos = rng.integers(0, nb * ps, size=B)
    return q, k, v, jnp.asarray(pt), jnp.asarray(pos, jnp.int32)


# Pages per DMA block follow from a page's bytes; these small pages would
# all fit one block, so cases that need several blocks set ``ppb``.
# Rows (nb = 7 pages of 8): pos 0; the last row of block 0 and the first
# of block 1 at ppb 3; mid-block; the last position of the last page; an
# idle row with a stale position.
EDGE_POS = [0, 23, 24, 37, 55, 50]
EDGE = dict(B=6, nb=7, pos=EDGE_POS, idle=(5,))
ACTIVE = slice(0, 5)


@pytest.fixture
def ppb(request, monkeypatch):
    if request.param is not None:
        monkeypatch.setattr(paged_attention, "pages_per_block",
                            lambda *a: request.param)
    return request.param


def _paged_call(fused, *a, **kw):
    """The kernel itself, traced afresh (the jitted ``ops`` wrappers would
    keep the first block size they traced)."""
    fn = (paged_attention.paged_decode_attention_fused if fused
          else paged_attention.paged_decode_attention)
    return fn(*a, interpret=True, **kw)


@pytest.mark.parametrize("ppb,window,rows", [
    (None, 0, "random"), (None, 11, "random"), (3, 0, "edges"),
    (3, 11, "edges"), (2, 19, "edges"), (4, 0, "edges"),
], ids=["0", "11", "ppb3", "ppb3_window",
        "ppb2_window_spans_blocks", "ppb4"], indirect=["ppb"])
def test_paged_kernel_matches_oracle(ppb, window, rows):
    """Every active row attends exactly its live keys, whatever the block
    split; an idle row (all TRASH) walks nothing and reads zeros."""
    if rows == "random":
        q, k, v, pt, pos = _rand_paged(0)
        out = ops.paged_decode_attention(q, k, v, pt, pos, window)
        act = slice(None)
    else:
        q, k, v, pt, pos = _rand_paged(0, **EDGE)
        out = _paged_call(False, q, k, v, pt, pos, window)
        act = ACTIVE
        np.testing.assert_array_equal(np.asarray(out[5]), 0.0)
    want = ref.paged_decode_attention_ref(q, k, v, pt, pos, window)
    np.testing.assert_allclose(np.asarray(out[act]), np.asarray(want[act]),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layers,layer,ppb,rows", [
    (1, 0, None, "random"), (3, 0, None, "random"), (3, 2, None, "random"),
    (3, 1, 3, "edges"), (1, 0, 4, "edges"),
], ids=["one_layer", "stack3_layer0", "stack3_layer2", "stack3_ppb3_edges",
        "one_layer_ppb4_edges"], indirect=["ppb"])
def test_paged_fused_scatter_bitwise_and_attends_new_kv(layers, layer, ppb,
                                                        rows):
    """The fused kernel writes only layer ``layer``'s boundary rows of the
    layer-stacked pools (an idle row's lands in TRASH); every other bit
    of both stacks, other layers included, keeps its value."""
    kw = dict(share=False) if rows == "random" else dict(EDGE, share=False)
    q, k, v, pt, pos = _rand_paged(1, **kw)
    B, P, ps = q.shape[0], k.shape[0], 8
    rng = np.random.default_rng(2)
    nk = jnp.asarray(rng.normal(size=(B, 2, 16)), jnp.float32)
    nv = jnp.asarray(rng.normal(size=(B, 2, 16)), jnp.float32)
    # other layers: distinct random pools, so a write to the wrong layer
    # or a stale block shows
    others = rng.normal(size=(2, layers) + k.shape).astype(np.float32)
    ks, vs = others[0], others[1]
    ks[layer], vs[layer] = np.asarray(k), np.asarray(v)
    args = (q, jnp.asarray(ks), jnp.asarray(vs), nk, nv, pt, pos, 0, layer)
    if rows == "random":
        o, k2, v2 = ops.paged_decode_attention_fused(*args)
    else:
        o, k2, v2 = _paged_call(True, *args)
    ek, ev = ks.copy(), vs.copy()
    for b in range(B):
        page = int(pt[b, int(pos[b]) // ps])
        ek[layer, page, int(pos[b]) % ps] = np.asarray(nk[b])
        ev[layer, page, int(pos[b]) % ps] = np.asarray(nv[b])
    np.testing.assert_array_equal(np.asarray(k2), ek)
    np.testing.assert_array_equal(np.asarray(v2), ev)
    want = ref.paged_decode_attention_ref(
        q, jnp.asarray(ek[layer]), jnp.asarray(ev[layer]), pt, pos)
    act = slice(None) if rows == "random" else ACTIVE
    np.testing.assert_allclose(np.asarray(o[act]), np.asarray(want[act]),
                               atol=2e-5, rtol=2e-5)
    assert P - 1 not in np.asarray(pt[act])


@pytest.mark.parametrize("fused,ppb", [(False, None), (False, 2),
                                       (True, None), (True, 2)],
                         ids=["plain", "plain_ppb2", "fused", "fused_ppb2"],
                         indirect=["ppb"])
def test_paged_kernel_ignores_pages_beyond_pos(fused, ppb):
    """Pages past a row's depth and the TRASH page are never attended:
    poisoning them (NaN) leaves every active row's output bit-identical,
    also where the last block holds pages it does not copy."""
    q, k, v, pt, pos = _rand_paged(3, B=4, share=False, idle=(3,))
    pos = jnp.asarray([2, 9, 17, 30], jnp.int32)  # well inside the table
    rng = np.random.default_rng(4)
    nk = jnp.asarray(rng.normal(size=(4, 2, 16)), jnp.float32)
    nv = jnp.asarray(rng.normal(size=(4, 2, 16)), jnp.float32)

    def run(k, v):
        if fused:
            return _paged_call(True, q, k[None], v[None], nk, nv, pt, pos,
                               0, 0)[0]
        return _paged_call(False, q, k, v, pt, pos, 0)

    base = run(k, v)
    dead = np.append(np.asarray(pt)[:3, 3], k.shape[0] - 1)
    poisoned = run(k.at[dead].set(jnp.nan), v.at[dead].set(jnp.nan))
    np.testing.assert_array_equal(np.asarray(base[:3]),
                                  np.asarray(poisoned[:3]))
    assert np.isfinite(np.asarray(poisoned)).all()


@pytest.mark.parametrize("nb,kv_heads,head_dim,want", [
    (160, 4, 128, 16), (64, 32, 96, 2), (8, 4, 128, 8), (64, 64, 256, 1),
], ids=["qwen2h", "phi3", "capped_by_table", "page_over_budget"])
def test_pages_per_block_follows_page_bytes(nb, kv_heads, head_dim, want):
    """The DMA block is as many 16-row bf16 pages as fit the K+V budget,
    at least one and at most the table's width."""
    assert paged_attention.pages_per_block(
        nb, 16, kv_heads, head_dim, jnp.bfloat16) == want


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


# --- last-position head of the suffix prefill -------------------------------


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_paged"])
def test_last_position_head_is_the_row_of_every_position(mp, attn_impl):
    """With ``last_index`` only each row's last suffix position reaches the
    final norm and the head: its logits, the pools it leaves and the t0
    sampled from it are bitwise those of unembedding every position and
    taking the row.  ``xla`` runs the S > 1 suffix prefill the engine
    runs; ``pallas_paged`` the one-token tick, whose only position is
    the last."""
    model, params = mp
    B, nb = 3, MAX_LEN // PS
    S = 8 if attn_impl == "xla" else 1
    rng = np.random.default_rng(5)
    pool = model.init_paged_cache(B * nb + 1, PS)
    cache = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype)
             for k, v in pool.items()}
    pt = jnp.asarray(np.arange(B * nb).reshape(B, nb), jnp.int32)
    starts = jnp.asarray([0, 5, 13], jnp.int32)
    lens = np.minimum([S, 5, 3], S)
    tokens = jnp.asarray(rng.integers(0, 512, (B, S)), jnp.int32)
    kw = dict(attn_impl=attn_impl, page_table=pt,
              kv_write_mask=jnp.asarray(np.arange(S) < lens[:, None]))
    full, want_cache = model.decode_step(
        params, cache, {"tokens": tokens}, starts, **kw)
    head, got_cache = model.decode_step(
        params, cache, {"tokens": tokens}, starts,
        last_index=jnp.asarray(lens - 1, jnp.int32), **kw)
    want = np.take_along_axis(np.asarray(full), (lens - 1)[:, None, None],
                              axis=1)
    assert head.shape == (B, 1, full.shape[-1])
    np.testing.assert_array_equal(np.asarray(head), want)
    for k in want_cache:
        np.testing.assert_array_equal(np.asarray(got_cache[k]),
                                      np.asarray(want_cache[k]))
    greedy = jnp.zeros(B, jnp.float32)
    key = jax.random.PRNGKey(3)
    np.testing.assert_array_equal(
        np.asarray(ops.fused_sample(head[:, 0], greedy, key)),
        np.asarray(ops.fused_sample(jnp.asarray(want[:, 0]), greedy, key)))


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


def test_twice_the_slots_at_equal_kv_memory(mp):
    """Capacity: a pool holding exactly the dense engine's KV rows
    (SLOTS x MAX_LEN, the trash page included) serves twice the slots on
    a shared-prefix workload to completion, with more than SLOTS rows in
    flight at once, bitwise equal to the reference."""
    model, params = mp
    nb = MAX_LEN // PS
    num_pages = SLOTS * nb - 1
    assert (num_pages + 1) * PS == SLOTS * MAX_LEN
    # template_len 30 % 8 = 6 -> every reuse CoWs a boundary page
    reqs = shared_prefix_requests(12, seed=3, vocab=512, num_templates=2,
                                  template_len=30, suffix_lens=(2, 6),
                                  max_new=(3, 8))
    want = _ref_outputs(mp, reqs)
    tracer = Tracer()
    eng = PagedEngine(model, params, slots=2 * SLOTS, max_len=MAX_LEN,
                      page_size=PS, num_pages=num_pages, eos_id=7,
                      ticks_per_sync=4, record_traffic=False,
                      attn_impl="pallas_paged", tracer=tracer)
    # run_staggered raises unless every request finishes
    got = run_staggered(
        eng, staggered_groups(copy.deepcopy(reqs), 2 * SLOTS))
    assert got == want
    active = [c["values"]["active"] for c in tracer._counters
              if c["name"] == "active_slots"]
    assert max(active) > SLOTS
    st = eng.paged_stats()
    assert st["prefix_tokens"] > 0 and st["cow_copies"] > 0
    eng.pool.check(eng.tree.held_refs())


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
