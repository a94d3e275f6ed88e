"""Percentile math, latency summaries, and the Chrome-trace exporter.

Model-free: requests are hand-stamped so every expected TTFT/TPOT/E2E
value is computable by hand (the engine-integration side lives in
tests/test_serve_engine.py).
"""
import json

import numpy as np
import pytest

from repro.serve import Request
from repro.serve.telemetry import (Tracer, latency_summary, percentile,
                                   request_latency, summarize,
                                   validate_chrome_trace)


# --- percentile math --------------------------------------------------------


def test_percentile_hand_computed():
    # linear interpolation on [1, 2, 3, 4]: p50 sits halfway between the
    # 2nd and 3rd order statistics
    assert percentile([4, 1, 3, 2], 50) == 2.5
    assert percentile([4, 1, 3, 2], 0) == 1.0
    assert percentile([4, 1, 3, 2], 100) == 4.0
    # p25 of [0, 10]: rank 0.25 -> 2.5
    assert percentile([10, 0], 25) == 2.5
    # 1..100: rank 99 * 0.99 = 98.01 -> 99 + 0.01 * (100 - 99)
    assert percentile(list(range(1, 101)), 99) == pytest.approx(99.01)
    assert percentile([7.0], 99) == 7.0


def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(0.0, 1.0, size=137)
    for q in (0, 10, 50, 95, 99, 100):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_validation():
    with pytest.raises(ValueError, match="empty"):
        percentile([], 50)
    with pytest.raises(ValueError, match="outside"):
        percentile([1.0], 101)


def test_summarize_keys():
    s = summarize([1.0, 2.0, 3.0])
    assert set(s) == {"p50", "p95", "p99", "mean", "max"}
    assert s["p50"] == 2.0 and s["mean"] == 2.0 and s["max"] == 3.0
    assert summarize([]) == {}


# --- per-request latency ----------------------------------------------------


def _stamped(uid=0, *, arrival=None, submit_tick=0, submit_time=10.0,
             dequeue_time=10.2, admit_tick=2, admit_time=10.5, done_tick=6,
             done_time=11.3, n_tokens=5):
    r = Request(uid=uid, prompt=[1, 2], max_new_tokens=n_tokens,
                output=list(range(n_tokens)), arrival=arrival)
    r.submit_tick, r.submit_time = submit_tick, submit_time
    r._mark_admitted(admit_tick, admit_time, dequeue_time)
    r._mark_done(done_tick, done_time)
    return r


def test_request_latency_hand_computed():
    lat = request_latency(_stamped())
    assert lat["wall"]["ttft_s"] == pytest.approx(0.5)
    assert lat["wall"]["e2e_s"] == pytest.approx(1.3)
    # 5 tokens, done - first_token = 0.8 s over 4 decode tokens
    assert lat["wall"]["tpot_s"] == pytest.approx(0.2)
    assert lat["ticks"]["ttft"] == 2
    assert lat["ticks"]["e2e"] == 6
    assert lat["ticks"]["tpot"] == pytest.approx(1.0)


def test_request_latency_uses_arrival_when_set():
    lat = request_latency(_stamped(arrival=1.5))
    # tick-domain latencies charge the admission delay from arrival
    assert lat["ticks"]["ttft"] == pytest.approx(0.5)
    assert lat["ticks"]["e2e"] == pytest.approx(4.5)
    # wall-clock still measures from the submit stamp
    assert lat["wall"]["ttft_s"] == pytest.approx(0.5)


def test_request_latency_single_token_has_no_tpot():
    lat = request_latency(_stamped(n_tokens=1, done_tick=2, done_time=10.5))
    assert "tpot_s" not in lat["wall"] and "tpot" not in lat["ticks"]
    assert lat["ticks"]["e2e"] == 2


def test_request_latency_none_for_unfinished():
    r = Request(uid=0, prompt=[1], max_new_tokens=2)
    assert request_latency(r) is None


def test_latency_summary_counts_and_percentiles():
    reqs = [_stamped(uid=i, done_time=11.0 + i) for i in range(4)]
    reqs.append(Request(uid=9, prompt=[1], max_new_tokens=2))  # unfinished
    s = latency_summary(reqs)
    assert s["n"] == 5 and s["completed"] == 4
    assert s["tokens"] == 20
    # e2e wall times are 1, 2, 3, 4 s
    assert s["wall"]["e2e_s"]["p50"] == pytest.approx(2.5)
    assert s["wall"]["e2e_s"]["max"] == pytest.approx(4.0)
    assert s["ticks"]["ttft"]["p50"] == 2


def test_latency_summary_reports_queue_wait():
    # queue waits (dequeue - submit) of 0.1, 0.2, 0.3 and 0.4 s
    reqs = [_stamped(uid=i, dequeue_time=10.1 + 0.1 * i) for i in range(4)]
    assert request_latency(reqs[1])["wall"]["queue_s"] == pytest.approx(0.2)
    q = latency_summary(reqs)["wall"]["queue_s"]
    assert set(q) == {"p50", "p95", "p99", "mean", "max"}
    assert q["p50"] == pytest.approx(0.25)
    assert q["p95"] == pytest.approx(0.385)
    assert q["max"] == pytest.approx(0.4)


def test_requeued_request_keeps_its_first_queue_stamp():
    r = Request(uid=0, prompt=[1, 2], max_new_tokens=4)
    r._mark_admitted(2, 10.5, 10.2)
    r._mark_admitted(5, 12.0, 11.9)     # a retry's second admission
    assert (r.dequeue_time, r.admit_time) == (10.2, 10.5)


def test_latency_summary_empty():
    s = latency_summary([])
    assert s["n"] == 0 and s["completed"] == 0
    assert s["wall"] == {} and s["ticks"] == {}


# --- chrome trace export ----------------------------------------------------


def test_tracer_exports_valid_chrome_trace(tmp_path):
    tr = Tracer(name="t")
    tr.span("prefill P=8", "prefill", 100.0, 100.5, args={"tick": 0})
    tr.span("decode_window", "decode", 100.5, 101.0, args={"K": 4})
    tr.counter("active_slots", {"active": 3}, 100.5)
    trace = tr.to_chrome_trace()
    validate_chrome_trace(trace)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["prefill P=8", "decode_window"]
    # rebased to the first event, microseconds
    assert xs[0]["ts"] == 0.0
    assert xs[0]["dur"] == pytest.approx(0.5e6)
    assert xs[1]["ts"] == pytest.approx(0.5e6)
    cs = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert cs[0]["args"] == {"active": 3}
    # save() round-trips through json and re-validates
    path = tr.save(tmp_path / "trace.json")
    validate_chrome_trace(json.loads(path.read_text()))


def test_tracer_rejects_negative_span():
    tr = Tracer()
    with pytest.raises(ValueError, match="end"):
        tr.span("x", "c", 2.0, 1.0)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"events": []})
    for ph in ("Z", "B", "E", "i"):       # the exporter emits X/C/M only
        with pytest.raises(ValueError, match="phase"):
            validate_chrome_trace({"traceEvents": [{"ph": ph}]})
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "a"}]})
    with pytest.raises(ValueError, match="non-negative"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "a", "ts": -1.0, "dur": 1.0,
             "pid": 0, "tid": 0}]})
