"""SLO-grade serve telemetry: per-request latency percentiles + tracing.

Two independent pieces (DESIGN.md §14):

* **Latency accounting.**  ``Request`` (serve/engine.py) carries
  submit/admit/first-token/done stamps in BOTH time domains — engine
  ticks (deterministic, schedule-comparable across engines) and wall
  clock (``time.perf_counter``, what a client actually waits) — plus a
  wall-clock ``dequeue_time``, when admission took it off the queue.
  This module turns a finished request set into the three serving
  metrics a production SLO is written against, and the queue wait
  (``queue_s``, dequeue − submit) that splits TTFT:

    TTFT  time-to-first-token: first_token − reference point (the
          request's intended ``arrival`` when a traffic generator set
          one, else its submit stamp — so tick-domain TTFT includes the
          up-to-K admission delay of the sync cadence);
    TPOT  time-per-output-token: (done − first_token) / (tokens − 1),
          defined only for multi-token outputs;
    E2E   end-to-end: done − reference point.

  ``latency_summary`` reports p50/p95/p99 (+ mean/max) of each metric in
  each domain.  The percentile math is the standard linear-interpolation
  estimator (numpy's default) implemented here so a hand-computed trace
  can pin it in tests.

* **Chrome-trace export.**  ``Tracer`` collects engine spans — the
  leaves that tile each engine step (admission phases, batched prefill,
  fused decode window, host drain) and the ``admit`` span around the
  admission leaves — plus an active-slots counter track, and serializes
  them as Trace Event JSON (``chrome://tracing`` / Perfetto "X"/"C"/"M"
  events, microsecond timestamps; X spans nest by time).  The engine
  calls ``span``/``counter`` only when a tracer is attached, so the hot
  path pays nothing by default.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence

from repro.serve.engine import Request

PERCENTILES = (50.0, 95.0, 99.0)


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default method): for
    sorted x of length n, rank ``(n-1) * q/100`` interpolated between the
    two neighbouring order statistics."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    xs = sorted(float(x) for x in xs)
    if not xs:
        raise ValueError("percentile of an empty sequence")
    rank = (len(xs) - 1) * (q / 100.0)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return xs[lo] + frac * (xs[hi] - xs[lo])


def summarize(xs: Sequence[float],
              qs: Sequence[float] = PERCENTILES) -> Dict[str, float]:
    """{"p50": ..., "p95": ..., "p99": ..., "mean": ..., "max": ...}."""
    xs = [float(x) for x in xs]
    if not xs:
        return {}
    out = {f"p{q:g}": percentile(xs, q) for q in qs}
    out["mean"] = sum(xs) / len(xs)
    out["max"] = max(xs)
    return out


def request_latency(req: Request) -> Optional[Dict[str, Dict[str, float]]]:
    """Per-request {wall: {ttft_s, queue_s, tpot_s?, e2e_s}, ticks: {...}}
    or None if the request has not finished (or predates the stamping
    engine)."""
    if not (req.done and req.done_time is not None
            and req.first_token_time is not None
            and req.submit_time is not None):
        return None
    n = len(req.output)
    wall = {"ttft_s": req.first_token_time - req.submit_time,
            "queue_s": req.dequeue_time - req.submit_time,
            "e2e_s": req.done_time - req.submit_time}
    # tick-domain latencies measure from the intended arrival when the
    # traffic generator set one (charging the sync-cadence admission
    # delay), else from the submit tick
    ref = req.arrival if req.arrival is not None else req.submit_tick
    ticks = {"ttft": req.first_token_tick - ref,
             "e2e": req.done_tick - ref}
    if n > 1:
        wall["tpot_s"] = (req.done_time - req.first_token_time) / (n - 1)
        ticks["tpot"] = (req.done_tick - req.first_token_tick) / (n - 1)
    return {"wall": wall, "ticks": ticks}


def latency_summary(reqs: Iterable[Request],
                    qs: Sequence[float] = PERCENTILES) -> dict:
    """Aggregate TTFT/TPOT/E2E percentiles over finished requests.

    Returns {"n", "completed", "tokens", "wall": {ttft_s/queue_s/tpot_s/
    e2e_s -> summarize()}, "ticks": {ttft/tpot/e2e -> summarize()}};
    requests that never finished count in ``n`` but not in the
    percentiles.
    """
    reqs = list(reqs)
    per = [(r, request_latency(r)) for r in reqs]
    finished = [(r, lat) for r, lat in per if lat is not None]
    states: Dict[str, int] = {}
    for r in reqs:
        states[r.state] = states.get(r.state, 0) + 1
    out = {"n": len(reqs), "completed": len(finished),
           "tokens": sum(len(r.output) for r, _ in finished),
           # terminal-state histogram + degraded-traffic counters
           # (DESIGN.md §16): shed/timed-out/failed requests count in
           # ``n`` and ``states`` but never in the percentiles
           "states": states,
           "shed": states.get("SHED", 0),
           "timed_out": states.get("TIMED_OUT", 0),
           "failed": states.get("FAILED", 0),
           "retries": sum(r.retries for r in reqs),
           "preemptions": sum(r.preemptions for r in reqs),
           "wall": {}, "ticks": {}}
    for domain in ("wall", "ticks"):
        keys = sorted({k for _, lat in finished for k in lat[domain]})
        out[domain] = {
            k: summarize([lat[domain][k] for _, lat in finished
                          if k in lat[domain]], qs)
            for k in keys}
    return out


# ---- chrome://tracing export ------------------------------------------------

_REQUIRED_BY_PHASE = {"X": ("name", "ts", "dur", "pid", "tid"),
                      "C": ("name", "ts", "pid"),
                      "M": ("name", "pid")}


class Tracer:
    """Collects engine spans/counters; exports Trace Event Format JSON.

    Wall-clock inputs are ``time.perf_counter`` seconds; the exporter
    rebases them onto the first recorded event and converts to the
    microsecond ``ts``/``dur`` the trace viewers expect.
    """

    def __init__(self, name: str = "serve-engine"):
        self.name = name
        self._spans: List[dict] = []      # (name, cat, t0, t1, tid, args)
        self._counters: List[dict] = []   # (name, values, t, tid)

    def span(self, name: str, cat: str, start_s: float, end_s: float,
             tid: int = 0, args: Optional[dict] = None) -> None:
        if end_s < start_s:
            raise ValueError(f"span {name!r}: end {end_s} < start {start_s}")
        self._spans.append({"name": name, "cat": cat, "t0": start_s,
                            "t1": end_s, "tid": tid, "args": args or {}})

    def counter(self, name: str, values: Dict[str, float], when_s: float,
                tid: int = 0) -> None:
        self._counters.append({"name": name, "values": dict(values),
                               "t": when_s, "tid": tid})

    def _origin(self) -> float:
        times = ([s["t0"] for s in self._spans]
                 + [c["t"] for c in self._counters])
        return min(times) if times else 0.0

    def to_chrome_trace(self) -> dict:
        origin = self._origin()
        us = lambda t: (t - origin) * 1e6   # noqa: E731
        events: List[dict] = [
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": self.name}},
            {"ph": "M", "name": "thread_name", "pid": 0, "tid": 0,
             "args": {"name": "engine"}},
        ]
        for s in self._spans:
            events.append({"ph": "X", "name": s["name"], "cat": s["cat"],
                           "ts": us(s["t0"]), "dur": us(s["t1"]) - us(s["t0"]),
                           "pid": 0, "tid": s["tid"], "args": s["args"]})
        for c in self._counters:
            events.append({"ph": "C", "name": c["name"], "ts": us(c["t"]),
                           "pid": 0, "tid": c["tid"], "args": c["values"]})
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"producer": "repro.serve.telemetry"}}

    def save(self, path) -> Path:
        path = Path(path)
        trace = self.to_chrome_trace()
        validate_chrome_trace(trace)
        path.write_text(json.dumps(trace, indent=1) + "\n")
        return path


def validate_chrome_trace(obj: dict) -> None:
    """Raise ValueError unless ``obj`` is structurally valid Trace Event
    JSON (the subset this exporter emits): a ``traceEvents`` list whose
    events carry a known ``ph``, the per-phase required keys and
    non-negative numeric ``ts``/``dur``."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be a dict with a 'traceEvents' list")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph not in _REQUIRED_BY_PHASE:
            raise ValueError(f"event {i}: unknown phase {ph!r}")
        missing = [k for k in _REQUIRED_BY_PHASE[ph] if k not in ev]
        if missing:
            raise ValueError(f"event {i} (ph={ph}): missing keys {missing}")
        for k in ("ts", "dur"):
            if k in ev and (not isinstance(ev[k], (int, float))
                            or ev[k] < 0):
                raise ValueError(f"event {i}: {k}={ev[k]!r} must be a "
                                 "non-negative number")
