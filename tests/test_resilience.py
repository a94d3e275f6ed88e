"""Resilience layer (serve/resilience.py + engine integration): watchdog
retry/fallback semantics, queue backpressure, deadlines (queued and
mid-decode), tight-pool defer/shed behavior, preemption resume, health-
check retry budgets, and crash-rebuild resume — all with the bitwise
contract: whatever survives chaos must produce exactly the tokens an
undisturbed run would have (greedy decoding is schedule-independent and
requeued work resumes from ``prompt + output``).
"""
import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serve import (DONE, FAILED, SHED, TIMED_OUT, Engine,
                         EngineReference, Fault, FaultPlan, PagedEngine,
                         Request, ShedPolicy, WatchdogError,
                         WindowWatchdog, mixed_requests)

MAX_LEN = 48
SLOTS = 3


@pytest.fixture(scope="module")
def mp():
    cfg = reduced(get_config("llama3-8b"), dtype="float32")
    model = build_model(cfg, max_seq=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def dense(mp):
    model, params = mp
    return Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                  ticks_per_sync=2, record_traffic=False)


@pytest.fixture(scope="module")
def paged(mp):
    model, params = mp
    return PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                      page_size=4, ticks_per_sync=2, record_traffic=False)


@pytest.fixture(scope="module")
def tight(mp):
    """Page pool that fits ONE in-flight request: 8 pages of 4 tokens
    on 2 slots, so a 20-token reservation starves the second slot."""
    model, params = mp
    return PagedEngine(model, params, slots=2, max_len=32, page_size=4,
                      num_pages=8, ticks_per_sync=2, record_traffic=False)


@pytest.fixture(scope="module")
def ref(mp):
    model, params = mp
    return EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)


def _fresh(eng, *, plan=None, policy=None, watchdog=None):
    eng.reset()
    eng.fault_plan = plan
    eng.shed_policy = policy if policy is not None else ShedPolicy()
    eng.watchdog = (watchdog if watchdog is not None
                    else WindowWatchdog(backoff_s=0.001))
    return eng


def _alone(ref, prompt, max_new):
    """Clean single-request reference output."""
    ref.reset()
    r = Request(uid=0, prompt=list(prompt), max_new_tokens=max_new)
    ref.submit(r)
    assert ref.run() == 0
    return list(r.output)


def _conserved(eng):
    from collections import Counter
    slot_refs = Counter()
    for s, r in enumerate(eng.slot_req):
        if r is not None:
            slot_refs.update(eng._slot_pages[s])
    eng.pool.check(eng.tree.held_refs() + slot_refs)


# --- WindowWatchdog units ---------------------------------------------------


def test_watchdog_retries_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    retries = []
    wd = WindowWatchdog(max_attempts=3, backoff_s=0.0)
    assert wd.call(flaky, on_retry=lambda a, e: retries.append(a)) == "ok"
    assert len(calls) == 3 and len(retries) == 2


def test_watchdog_exhaustion_uses_fallback_then_raises_without():
    def broken():
        raise RuntimeError("permanent")

    wd = WindowWatchdog(max_attempts=2, backoff_s=0.0)
    assert wd.call(broken, fallback=lambda: "degraded") == "degraded"
    with pytest.raises(WatchdogError) as ei:
        wd.call(broken, label="win")
    assert "permanent" in str(ei.value.__cause__)


def test_watchdog_timeout_abandons_hung_attempt():
    import time as _t

    def hung():
        _t.sleep(5.0)
        return "never"

    wd = WindowWatchdog(max_attempts=1, backoff_s=0.0, timeout_s=0.05)
    t0 = _t.perf_counter()
    assert wd.call(hung, fallback=lambda: "degraded") == "degraded"
    assert _t.perf_counter() - t0 < 2.0


def test_fault_validation_rejects_unknown_kinds():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("meteor_strike")
    with pytest.raises(ValueError, match="at >= 0"):
        Fault("nan_logits", at=-1)


# --- shed policy: backpressure + deadlines ----------------------------------


def test_queue_depth_backpressure_sheds(dense):
    _fresh(dense, policy=ShedPolicy(max_queue_depth=2))
    reqs = [Request(uid=i, prompt=[3 + i, 5], max_new_tokens=3)
            for i in range(4)]
    accepted = [dense.submit(r) for r in reqs]
    assert accepted == [True, True, False, False]
    assert reqs[2].state == SHED and "queue depth" in reqs[2].reason
    dense.run()
    assert reqs[0].state == DONE and reqs[1].state == DONE
    assert dense.resilience_stats()["shed"] == 2


@pytest.mark.parametrize("engine_fixture", ["dense", "paged"])
def test_expired_queued_deadline_times_out(engine_fixture, request):
    eng = _fresh(request.getfixturevalue(engine_fixture))
    r = Request(uid=0, prompt=[5, 7], max_new_tokens=4, deadline=-1.0)
    assert eng.submit(r)          # queued fine; expiry is checked at admit
    eng.run()
    assert r.state == TIMED_OUT and r.output == []
    assert "expired in queue" in r.reason


def test_mid_decode_deadline_keeps_prefix(dense, ref):
    alone = _alone(ref, [5, 7, 11, 13], 20)
    _fresh(dense)
    r = Request(uid=0, prompt=[5, 7, 11, 13], max_new_tokens=20,
                deadline=5.0)
    dense.submit(r)
    dense.run()
    assert r.state == TIMED_OUT and "mid-decode" in r.reason
    assert 0 < len(r.output) < len(alone)
    assert r.output == alone[:len(r.output)]


# --- tight pool: defer, deadline, max_defers shed ---------------------------


def test_tight_pool_defers_then_deadline_resolves(tight, ref):
    """One request's reservation starves the pool; the second defers
    (no head-of-line deadlock) and its deadline resolves it while the
    first finishes untouched, bitwise."""
    alone = _alone(ref, list(range(2, 12)), 10)
    _fresh(tight)
    a = Request(uid=0, prompt=list(range(2, 12)), max_new_tokens=10)
    b = Request(uid=1, prompt=list(range(3, 13)), max_new_tokens=10,
                deadline=4.0)
    tight.submit(a)
    tight.submit(b)
    tight.run()
    assert a.state == DONE and list(a.output) == alone
    assert b.state in (TIMED_OUT, SHED)
    assert tight.paged_stats()["deferred"] > 0
    _conserved(tight)


def test_tight_pool_max_defers_sheds_with_shortfall_reason(tight):
    _fresh(tight, policy=ShedPolicy(max_defers=2))
    a = Request(uid=0, prompt=list(range(2, 12)), max_new_tokens=10)
    b = Request(uid=1, prompt=list(range(3, 13)), max_new_tokens=10)
    tight.submit(a)
    tight.submit(b)
    tight.run()
    assert a.state == DONE
    assert b.state == SHED
    assert "page pool exhausted" in b.reason and "pages" in b.reason
    _conserved(tight)


# --- preemption: resume is bitwise ------------------------------------------


@pytest.mark.parametrize("engine_fixture", ["dense", "paged"])
def test_preempt_mid_decode_resumes_bitwise(engine_fixture, request, ref):
    eng = _fresh(request.getfixturevalue(engine_fixture))
    alone = _alone(ref, [5, 7, 11, 13], 16)
    r = Request(uid=0, prompt=[5, 7, 11, 13], max_new_tokens=16)
    eng.submit(r)
    eng.step()
    slot = next(s for s, q in enumerate(eng.slot_req) if q is r)
    assert 0 < len(r.output) < 16     # genuinely mid-decode
    eng.preempt_slot(slot)
    assert eng.slot_req[slot] is None and r.preemptions == 1
    eng.run()
    assert r.state == DONE and list(r.output) == alone
    if hasattr(eng, "pool"):
        # the stashed prefix must be re-matched, not re-prefilled
        assert eng.paged_stats()["prefix_tokens"] > 0
        _conserved(eng)


def test_preempt_empty_slot_raises(dense):
    _fresh(dense)
    with pytest.raises(ValueError, match="not occupied"):
        dense.preempt_slot(0)


# --- health-check retry budget ----------------------------------------------


def test_quarantine_retry_budget_exhaustion_fails(dense):
    """Every window poisons slot 0: with max_retries=1 the request is
    quarantined, retried once, quarantined again, and FAILED — never an
    infinite requeue loop."""
    plan = FaultPlan([Fault("nan_logits", at=0, count=8, slot=0)], seed=0)
    _fresh(dense, plan=plan, policy=ShedPolicy(max_retries=1))
    r = Request(uid=0, prompt=[5, 7, 11], max_new_tokens=8)
    dense.submit(r)
    dense.run()
    assert r.state == FAILED
    assert "health check" in r.reason and "retry budget" in r.reason
    rs = dense.resilience_stats()
    assert rs["quarantined"] == 2 and rs["retried"] == 1
    assert rs["failed"] == 1


# --- crash + rebuild --------------------------------------------------------


def test_crash_rebuild_resumes_bitwise(dense, ref):
    """Mid-run crash: device state is lost (reset == rebuilt engine),
    every non-terminal request — including mid-slot ones with partial
    output — is resubmitted and finishes with reference parity.  This is
    the dense engine's ``engine.crash`` chaos case
    (tests/test_chaos_scenarios.py runs it on the other engines)."""
    reqs = mixed_requests(5, seed=4, vocab=512, prompt_lens=(2, 9),
                          max_new=(6, 12))
    want = {}
    for r in reqs:
        want[r.uid] = _alone(ref, r.prompt, r.max_new_tokens)
    _fresh(dense)
    for r in reqs:
        dense.submit(r)
    dense.step()
    dense.step()
    survivors = [r for r in reqs if not r.terminal]
    assert survivors                  # the crash interrupted real work
    assert any(r.output for r in survivors)      # some mid-slot
    _fresh(dense)                     # the crash: everything device-side gone
    for r in survivors:
        dense.submit(r)
    assert dense.run() == 0
    for r in reqs:
        assert r.state == DONE and list(r.output) == want[r.uid]
