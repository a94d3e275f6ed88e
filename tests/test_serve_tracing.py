"""Engine tracing: the leaf spans that tile ``PagedEngine.step``, the
``admit`` span over the admission leaves, the per-request queue stamp
that every engine takes, and what the hot path pays with no tracer
(DESIGN.md §14).

The benchmark's readers lean on these spans by category: ``prefill``,
``decode`` and ``host`` must keep holding exactly the prefill, decode
window and host drain spans, and ``admit`` spans carry the ``decoding``
and ``uids`` args that the admission metrics read.
"""
import types

import jax
import pytest

import repro.serve.engine as engine_mod
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serve import (Engine, EngineReference, PagedEngine, Request,
                         Tracer, mixed_requests, run_staggered,
                         shared_prefix_requests, staggered_groups,
                         validate_chrome_trace)

MAX_LEN = 48
SLOTS = 3
PS = 8
ADMIT_LEAVES = ["plan", "cow", "prefill_inputs", "prefill", "tree"]
WINDOW_LEAVES = ["pre_window", "decode", "host"]


@pytest.fixture(scope="module")
def mp():
    cfg = reduced(get_config("llama3-8b"), dtype="float32")
    model = build_model(cfg, max_seq=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


def _shared_reqs():
    # template_len 26 % 8 = 2: every reuse copies a boundary page
    return shared_prefix_requests(9, seed=4, vocab=512, num_templates=2,
                                  template_len=26, suffix_lens=(2, 6),
                                  max_new=(2, 8))


@pytest.fixture(scope="module")
def traced(mp):
    """One traced paged run over shared prefixes, arriving in groups."""
    model, params = mp
    tracer = Tracer(name="t")
    eng = PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                      page_size=PS, ticks_per_sync=2, record_traffic=False,
                      tracer=tracer)
    reqs = _shared_reqs()
    run_staggered(eng, staggered_groups(reqs, SLOTS))
    assert all(r.done for r in reqs)
    return eng, tracer, reqs


def _steps(tracer):
    """The leaf spans in time order, cut into steps after each drain."""
    leaves = sorted((s for s in tracer._spans if s["cat"] != "admit"),
                    key=lambda s: s["t0"])
    steps, cur = [], []
    for s in leaves:
        cur.append(s)
        if s["cat"] == "host":
            steps.append(cur)
            cur = []
    assert not cur, "every step of this run ends in a host drain"
    return steps


def test_paged_step_leaves_abut_and_admit_spans_the_admission(traced):
    eng, tracer, reqs = traced
    steps = _steps(tracer)
    admits = sorted((s for s in tracer._spans if s["cat"] == "admit"),
                    key=lambda s: s["t0"])
    assert steps and admits
    n_admitting = 0
    for leaves in steps:
        for a, b in zip(leaves, leaves[1:]):
            assert a["t1"] == b["t0"], (a["name"], b["name"])
        cats = [s["cat"] for s in leaves]
        assert cats[-3:] == WINDOW_LEAVES
        if cats[0] == "pre_window":
            continue                       # nothing admitted this step
        n_admitting += 1
        want = [c for c in ADMIT_LEAVES if c != "cow" or "cow" in cats]
        assert cats[:-3] == want
        admit = next(a for a in admits if a["t0"] == leaves[0]["t0"])
        assert admit["t1"] == leaves[len(want) - 1]["t1"]
        # the admitted are the requests dequeued where ``plan`` ends
        dequeued = {r.uid for r in reqs
                    if r.dequeue_time == leaves[0]["t1"]}
        assert set(admit["args"]["uids"]) == dequeued
        assert admit["args"]["admitted"] == len(dequeued)
        assert leaves[len(want) - 2]["args"]["uids"] == \
            admit["args"]["uids"]
        assert 0 <= admit["args"]["decoding"] <= SLOTS - len(dequeued)
    assert n_admitting == len(admits)
    cows = [s for s in tracer._spans if s["cat"] == "cow"]
    assert cows and all(s["args"]["copies"] >= 1 for s in cows)
    assert sum(s["args"]["copies"] for s in cows) == \
        eng.paged_stats()["cow_copies"]
    pre = [s for s in tracer._spans if s["cat"] == "pre_window"]
    assert all(0 < s["args"]["unique_page_fraction"] <= 1
               and s["args"]["unique"] <= s["args"]["mapped"]
               <= s["args"]["grid_pages"] == eng.slots * eng.nb
               for s in pre)
    # these small pages all fit one DMA block: one block a live row
    assert eng._kernel_ppb == eng.nb
    windows = [s for s in tracer._spans if s["cat"] == "decode"]
    assert [s["args"]["kernel_blocks"] for s in sorted(
        pre, key=lambda s: s["t0"])] == [
        s["args"]["active"] for s in sorted(windows, key=lambda s: s["t0"])]
    trace = tracer.to_chrome_trace()
    validate_chrome_trace(trace)
    assert {e["ph"] for e in trace["traceEvents"]} == {"X", "C", "M"}


def test_paged_span_categories_read_by_the_benchmark(traced):
    _, tracer, _ = traced
    by_cat = {}
    for s in tracer._spans:
        by_cat.setdefault(s["cat"], set()).add(s["name"].split(" ")[0])
    assert by_cat["prefill"] == {"prefill_chunk"}
    assert by_cat["decode"] == {"decode_window"}
    assert by_cat["host"] == {"host_drain"}
    assert set(by_cat) == {"admit", "plan", "cow", "prefill_inputs",
                           "prefill", "tree", "pre_window", "decode",
                           "host"}


def _engine(kind, mp, **kw):
    model, params = mp
    if kind == "reference":
        return EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    kw.setdefault("ticks_per_sync", 2)
    if kind == "paged":
        return PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                           page_size=PS, record_traffic=False, **kw)
    return Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                  record_traffic=False, **kw)


@pytest.mark.parametrize("kind", ["dense", "paged", "reference"])
def test_queue_stamp_orders_every_request_lifecycle(mp, kind):
    eng = _engine(kind, mp)
    reqs = mixed_requests(8, seed=3, vocab=512, prompt_lens=(2, 9),
                          max_new=(2, 7))
    run_staggered(eng, staggered_groups(reqs, 4))
    for r in reqs:
        assert r.done
        assert (r.submit_time <= r.dequeue_time <= r.first_token_time
                <= r.done_time)


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_preempted_request_keeps_its_first_dequeue(mp, kind):
    eng = _engine(kind, mp)
    r = Request(uid=0, prompt=[5, 6, 7, 8], max_new_tokens=12)
    eng.submit(r)
    eng.step()
    first = r.dequeue_time
    assert first is not None and r.output
    eng.preempt_slot(eng.slot_req.index(r))
    eng.run()
    assert r.done and r.preemptions == 1
    assert r.dequeue_time == first


def test_untraced_step_takes_no_extra_clock_stamps(mp, monkeypatch):
    """With no tracer, an admitting paged step stamps the clock six
    times (queue expiry scan, dequeue, prefill launch and result, window
    launch and result) and a step that admits nothing twice."""
    eng = _engine("paged", mp)
    warm = Request(uid=9, prompt=[5, 6, 7], max_new_tokens=6)
    eng.submit(warm)
    eng.run()                               # compile what the steps run
    calls = []
    real = engine_mod.time.perf_counter
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: calls.append(1) or real()))
    eng.submit(Request(uid=1, prompt=[8, 9, 10], max_new_tokens=6))
    del calls[:]
    eng.step()
    assert len(calls) == 6
    del calls[:]
    eng.step()
    assert len(calls) == 2


def test_traced_stall_step_is_one_plan_leaf(mp):
    """A step whose scan admits nothing and runs no window (the pool
    cannot hold the request yet) is still covered, by a ``plan`` leaf."""
    model, params = mp
    tracer = Tracer()
    eng = PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                      page_size=PS, ticks_per_sync=2, record_traffic=False,
                      tracer=tracer)
    hold = eng.pool.alloc(eng.pool.free_pages)     # nothing left to plan
    r = Request(uid=0, prompt=[5, 6, 7], max_new_tokens=3)
    eng.submit(r)
    assert eng.step() == 0
    assert [(s["name"], s["args"]["queued"]) for s in tracer._spans] == \
        [("plan", 1)]
    for p in hold:
        eng.pool.release(p)
    eng.run()
    assert r.done and eng.paged_stats()["deferred"] == 1
