"""Chaos scenarios: fault injection over the serve engines (DESIGN.md §16).

Each case builds a seeded ``FaultPlan`` (serve/chaos.py) or injects a
driver-level fault (malformed submits, a mid-run crash + rebuild),
drives one engine through it, and asserts the invariants:

  * termination  — ``run()`` returns and every submitted request reaches
    exactly one terminal state (DONE / SHED / TIMED_OUT / FAILED); the
    engines' stall guard advances the tick clock when chaos starves the
    pool, so there is no schedule that deadlocks the loop
  * parity       — requests the faults did not kill finish with greedy
    tokens BITWISE equal to a clean ``EngineReference`` run of the same
    workload (quarantine/preempt/crash resume from the already-emitted
    prefix, and greedy decoding is scheduling-independent); TIMED_OUT
    and SHED partial outputs must be prefixes of the reference answer
  * conservation — after ``plan.release_held()`` the paged pool's
    refcounts equal tree-held + slot-held references EXACTLY
    (``PagePool.check``), even though chaos stole pages mid-run
  * bounded shed — under an overloaded Poisson/burst arrival schedule
    with deadlines and a queue-depth cap, the engine sheds SOME work
    (admission control is real) but completes at least a floor fraction

Engines: the dense ``Engine`` and the ``PagedEngine`` over reduced
llama3-8b, each on >= ``MIN_FAULT_SITES`` distinct fault sites, and a
recurrentgemma ``Engine`` (hybrid slot banks), where quarantine and crash
recovery must keep parity when the faulted state is positionless bank
rows rather than positioned KV.  The dense engine's crash-and-rebuild
case is ``tests/test_resilience.py::test_crash_rebuild_resumes_bitwise``.
"""
from collections import Counter

import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serve import (DONE, FAILED, SHED, TIMED_OUT, Engine,
                         EngineReference, Fault, FaultPlan, PagedEngine,
                         Request, ShedPolicy, WindowWatchdog,
                         mixed_requests, poisson_requests, run_arrivals)

ARCH = "llama3-8b"
RECURRENT_ARCH = "recurrentgemma-2b"
SLOTS = 3
MAX_LEN = 48
K = 4                        # ticks_per_sync: small so faults land mid-flight
PAGE_SIZE = 4
VOCAB = 512
MAX_TICKS = 6000
N_REQUESTS = 6
N_TRAFFIC = 24
MIN_FAULT_SITES = 6          # distinct sites per engine (dense and paged)

# bounded-shed scenario: deliberate overload (arrivals far outpace the
# 3 slots) with deadlines + a queue cap — admission control must shed
# SOME work but still complete at least DONE_FLOOR of the offered load
BURST_RATE = 1.5
BURST_AMP = 0.6
BURST_DEADLINE = 80.0
BURST_QUEUE_DEPTH = 4
SHED_BOUND = 0.8             # <= 80% of requests may be shed/timed out
DONE_FLOOR = 0.2             # >= 20% must finish DONE under overload


def _stall_watchdog():
    return WindowWatchdog(max_attempts=3, backoff_s=0.001)


# FaultPlan scenarios: faults, watchdog factory, counter floors
# (resilience_stats, then paged_stats) the run must reach
FAULT_PLANS = {
    "nan_logits": ([Fault("nan_logits", at=1)], None,
                   {"quarantined": 1, "retried": 1}),
    "kv_corrupt": ([Fault("kv_corrupt", at=1)], None, {"quarantined": 1}),
    "window_stall": ([Fault("window_stall", at=1, count=2)],
                     _stall_watchdog, {"window_retries": 2}),
    # same kind, other exit: every attempt stalls -> sticky degrade to
    # the eager window; parity must STILL hold on the fallback path
    "window_stall_degrade": ([Fault("window_stall", at=1, count=3)],
                             _stall_watchdog,
                             {"window_fallbacks": 1, "degraded": 1}),
    "pool_exhaust": ([Fault("pool_exhaust", at=0, count=2, hold=2)], None,
                     {"deferred": 1}),
    "cow_storm": ([Fault("cow_storm", at=1, count=2, pages=2)], None,
                  {"cow_copies": 2}),
}
# a corrupted page may be tree-shared: the paged engine flushes the tree
PAGED_FLOORS = {"kv_corrupt": {"tree_flushes": 1}}

SITES = {   # scenario -> the fault sites it exercises
    "submit_malformed": ("submit.malformed", "submit.oversized"),
    "nan_logits": ("nan_logits",),
    "kv_corrupt": ("kv_corrupt",),
    "window_stall": ("window_stall",),
    "window_stall_degrade": ("window_stall",),
    "pool_exhaust": ("pool_exhaust",),
    "cow_storm": ("cow_storm",),
    "engine.crash": ("engine.crash",),
    "burst_shed": (),
}
_DENSE = ("submit_malformed", "nan_logits", "kv_corrupt", "window_stall",
          "window_stall_degrade")
SCENARIOS = {
    "dense": _DENSE + ("burst_shed",),
    "paged": _DENSE + ("pool_exhaust", "cow_storm", "engine.crash",
                       "burst_shed"),
    "recurrent": ("nan_logits", "kv_corrupt", "engine.crash"),
}
# scenarios whose case lives in tests/test_resilience.py
ELSEWHERE = {"dense": ("engine.crash",)}
CASES = [(e, s) for e, scns in SCENARIOS.items() for s in scns]


def _workload(seed=0, max_new=(3, 8)):
    return mixed_requests(N_REQUESTS, seed=seed, vocab=VOCAB,
                          prompt_lens=(2, 12), max_new=max_new)


def _crash_workload():
    return _workload(seed=5, max_new=(6, 12))


def _reference_outputs(ref, reqs) -> dict:
    """Clean greedy outputs for a workload, keyed by uid."""
    ref.reset()
    for r in reqs:
        ref.submit(r)
    assert ref.run(max_ticks=MAX_TICKS) == 0, "reference run did not finish"
    return {r.uid: list(r.output) for r in reqs}


def _model_and_refs(arch):
    cfg = reduced(get_config(arch), dtype="float32")
    model = build_model(cfg, max_seq=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0))
    ref = EngineReference(model, params, slots=SLOTS, max_len=MAX_LEN)
    refs = {"mixed": _reference_outputs(ref, _workload()),
            "crash": _reference_outputs(ref, _crash_workload())}
    return model, params, refs


@pytest.fixture(scope="module")
def llama():
    return _model_and_refs(ARCH)


@pytest.fixture(scope="module")
def dense(llama):
    model, params, refs = llama
    return Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                  ticks_per_sync=K, record_traffic=False), refs


@pytest.fixture(scope="module")
def paged(llama):
    model, params, refs = llama
    return PagedEngine(model, params, slots=SLOTS, max_len=MAX_LEN,
                       page_size=PAGE_SIZE, ticks_per_sync=K,
                       record_traffic=False), refs


@pytest.fixture(scope="module")
def recurrent():
    model, params, refs = _model_and_refs(RECURRENT_ARCH)
    return Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                  ticks_per_sync=K, record_traffic=False), refs


def _fresh(eng, *, plan=None, policy=None, watchdog=None):
    """Reset + rebind the per-scenario resilience knobs (reset() keeps
    shed_policy/watchdog/fault_plan, so scenarios restore defaults)."""
    eng.reset()
    eng.fault_plan = plan
    eng.shed_policy = policy if policy is not None else ShedPolicy()
    eng.watchdog = (watchdog if watchdog is not None
                    else WindowWatchdog(backoff_s=0.001))
    return eng


def _drive(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=MAX_TICKS)
    return reqs


def _states(reqs) -> dict:
    return dict(Counter(r.state for r in reqs))


def _check_terminal(reqs) -> None:
    stuck = sorted(r.uid for r in reqs if not r.terminal)
    assert not stuck, (f"requests {stuck} never reached a terminal state "
                       f"({_states(reqs)})")


def _check_parity(reqs, ref_out) -> None:
    """DONE outputs must be bitwise equal to the clean reference run;
    TIMED_OUT/SHED partials must be prefixes of it."""
    for r in reqs:
        want, got = ref_out[r.uid], list(r.output)
        if r.state == DONE:
            assert got == want, f"uid {r.uid} DONE output diverges"
        elif r.state in (TIMED_OUT, SHED):
            assert got == want[:len(got)], \
                f"uid {r.uid} {r.state} partial output is not a prefix"


def _check_conservation(eng, plan=None) -> None:
    """Exact page-refcount conservation: pool refs == tree + slots (+
    nothing, once the plan returns its stolen pages)."""
    if not hasattr(eng, "pool"):
        return                   # dense engine has no page pool
    if plan is not None:
        plan.release_held()
    slot_refs: Counter = Counter()
    for s, r in enumerate(eng.slot_req):
        if r is not None:
            slot_refs.update(eng._slot_pages[s])
    eng.pool.check(eng.tree.held_refs() + slot_refs)


# ---- scenarios ----------------------------------------------------------


def _submit_malformed(eng, refs, scenario):
    """Driver-level faults: malformed and oversized submits must soft-
    fail as FAILED (with a reason) while the engine keeps serving."""
    _fresh(eng)
    bad = [Request(uid=900, prompt=[], max_new_tokens=3),
           Request(uid=901, prompt=[1] * (MAX_LEN + 8), max_new_tokens=3),
           Request(uid=902, prompt=[1, 2], max_new_tokens=0)]
    accepted = [eng.submit(b) for b in bad]
    reqs = _drive(eng, _workload())
    assert not any(accepted), "a malformed request was accepted"
    for b in bad:
        assert b.state == FAILED and b.reason, (b.uid, b.state, b.reason)
    assert eng.resilience_stats()["failed"] >= len(bad)
    _check_terminal(reqs)
    _check_parity(reqs, refs["mixed"])
    _check_conservation(eng)


def _fault_plan(eng, refs, scenario):
    """Run a FaultPlan scenario: the plan must fire, the run must reach
    its counter floors, and the invariants must hold."""
    faults, watchdog, floors = FAULT_PLANS[scenario]
    plan = FaultPlan(faults, seed=11)
    _fresh(eng, plan=plan, watchdog=watchdog() if watchdog else None)
    reqs = _drive(eng, _workload())
    assert plan.injected, f"plan fired no faults (visits {dict(plan.visits)})"
    stats = dict(eng.resilience_stats())
    if hasattr(eng, "pool"):
        stats = {**eng.paged_stats(), **stats}
        floors = {**floors, **PAGED_FLOORS.get(scenario, {})}
    for key, floor in floors.items():
        assert int(stats.get(key, 0)) >= floor, (key, stats)
    _check_terminal(reqs)
    _check_parity(reqs, refs["mixed"])
    _check_conservation(eng, plan)


def _crash_rebuild(eng, refs, scenario):
    """Mid-run crash: run two windows, drop the device state on the
    floor (reset == rebuilt engine: fresh cache/state, empty queue),
    resubmit every non-terminal request — mid-slot ones resume from
    their emitted prefix — and finish with bitwise parity."""
    reqs = _crash_workload()
    _fresh(eng)
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()                   # crash point: some requests mid-decode
    survivors = [r for r in reqs if not r.terminal]
    assert survivors, "nothing survived the crash point"
    _fresh(eng)                  # the rebuilt engine
    for r in survivors:
        eng.submit(r)
    eng.run(max_ticks=MAX_TICKS)
    _check_terminal(reqs)
    _check_parity(reqs, refs["crash"])
    _check_conservation(eng)


def _burst_shed(eng, refs, scenario):
    """Overloaded Poisson/burst arrivals + deadlines + queue cap: every
    request ends terminal, SOME load is shed, the shed+timeout rate stays
    under SHED_BOUND, and at least DONE_FLOOR finishes."""
    _fresh(eng, policy=ShedPolicy(max_queue_depth=BURST_QUEUE_DEPTH))
    reqs = poisson_requests(N_TRAFFIC, seed=7, vocab=VOCAB,
                            arrival_rate=BURST_RATE, burst_amp=BURST_AMP,
                            prompt_bounds=(2, 10), new_bounds=(2, 8),
                            deadline_ticks=BURST_DEADLINE)
    run_arrivals(eng, reqs, max_ticks=MAX_TICKS)   # strict: raises on hang
    states = _states(reqs)
    shed = states.get(SHED, 0) + states.get(TIMED_OUT, 0)
    assert shed > 0, f"overload shed nothing ({states})"
    assert shed / len(reqs) <= SHED_BOUND, states
    assert states.get(DONE, 0) / len(reqs) >= DONE_FLOOR, states
    _check_terminal(reqs)
    _check_conservation(eng)


RUN = {"submit_malformed": _submit_malformed,
       "engine.crash": _crash_rebuild, "burst_shed": _burst_shed,
       **{scenario: _fault_plan for scenario in FAULT_PLANS}}


@pytest.mark.parametrize("engine,scenario", CASES,
                         ids=[f"{e}-{s}" for e, s in CASES])
def test_chaos_scenario(engine, scenario, request):
    eng, refs = request.getfixturevalue(engine)
    RUN[scenario](eng, refs, scenario)


def test_fault_site_floor():
    for engine in ("dense", "paged"):
        scns = SCENARIOS[engine] + ELSEWHERE.get(engine, ())
        sites = {site for s in scns for site in SITES[s]}
        assert len(sites) >= MIN_FAULT_SITES, (engine, sorted(sites))
