"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy time (the union of the intervals in which an operation ran
on a device), idle gaps labelled by the host span around them, and device
time by operation name.

Device operations are the events of the ``XLA Ops`` line of each
``/device:*`` plane.  A trace recorded on the CPU has no device plane;
there the operations are the host events that carry an ``hlo_op`` stat,
which is what the test of this module reads.

Host spans come in two kinds, both given to ``reduce`` as
``(name, start_ns, end_ns)`` on the trace's clock: the harness's own
``jax.profiler.TraceAnnotation`` spans, found in the trace by name
(``host_spans``), and the program's spans, which are on
``time.perf_counter`` and are moved onto the trace's clock by the offset
that ``clock_offset_ns`` reads from one annotation taken at a known
``perf_counter`` time.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
Span = Tuple[str, float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:       # an event without readable stats
        return {}


def op_name(text: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``); keep the name before ``=``."""
    return text.split(" = ", 1)[0]


def is_control(name: str) -> bool:
    """Control-flow ops (a scan's while loop, a cond) enclose the ops of
    their body on the same line; they count in busy time, which is a union,
    but not as ops of their own."""
    return re.match(r"%?(while|conditional|call)\b", name) is not None


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """device plane name -> [(op name, start_ns, end_ns)]."""
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out[plane.name] = [(op_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events]
    if out:
        return out
    ops = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if "hlo_op" in _stats(e):
                    ops.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {"/host:CPU": sorted(ops, key=lambda o: o[1])} if ops else {}


def host_spans(pd, names: Iterable[str]) -> List[Span]:
    """Host events whose name is in ``names`` (the harness's annotations)."""
    names = set(names)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return sorted(out, key=lambda s: s[1])


def clock_offset_ns(pd, marker: str, perf_s: float) -> float:
    """Trace time minus ``perf_counter`` time, in ns, from the annotation
    ``marker`` that was opened at ``perf_s``."""
    hits = host_spans(pd, [marker])
    if not hits:
        raise ValueError(f"marker {marker!r} not in the trace")
    return hits[0][1] - perf_s * 1e9


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_ns(merged: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(b - a for a, b in clip(merged, lo, hi))


def idle_gaps(merged: Sequence[Interval], lo: float,
              hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for a, b in clip(merged, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def label(gap: Interval, spans: Sequence[Span]) -> str:
    """The innermost (shortest) span that holds the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [s for s in spans if s[1] <= mid <= s[2]]
    if not inside:
        return "none"
    return min(inside, key=lambda s: s[2] - s[1])[0]


def op_totals(ops, lo: float, hi: float) -> Dict[str, List[float]]:
    """op name -> [device seconds, calls] inside [lo, hi]."""
    acc: Dict[str, List[float]] = {}
    for name, a, b in ops:
        if b > lo and a < hi:
            t = acc.setdefault(name, [0.0, 0])
            t[0] += (min(b, hi) - max(a, lo)) * 1e-9
            t[1] += 1
    return acc


def reduce(pd, lo: float, hi: float, spans: Sequence[Span] = (),
           n_top: int = 10) -> dict:
    """Device numbers of the window [lo, hi] (trace ns), averaged over the
    device planes: busy and window seconds, device seconds and calls of
    every op name, the top ops, and the longest idle gaps with the host
    span each fell in."""
    planes = device_ops(pd)
    if not planes:
        raise ValueError("the trace holds no device operation")
    busy, gaps_all, totals = 0.0, [], {}
    for ops in planes.values():
        merged = union((a, b) for _, a, b in ops)
        busy += busy_ns(merged, lo, hi)
        gaps_all.extend(idle_gaps(merged, lo, hi))
        for name, (t, c) in op_totals(ops, lo, hi).items():
            if is_control(name):
                continue
            acc = totals.setdefault(name, [0.0, 0])
            acc[0] += t
            acc[1] += c
    n = len(planes)
    ops_s = {k: [t / n, c / n] for k, (t, c) in totals.items()}
    top = sorted(ops_s.items(), key=lambda kv: -kv[1][0])[:n_top]
    longest = sorted(gaps_all, key=lambda g: g[0] - g[1])[:n_top]
    return {
        "devices": n,
        "op_calls": sum(c for _, c in ops_s.values()),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "ops": ops_s,
        "device_ops": [[k, v[0]] for k, v in top],
        "idle_gaps": [[label(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in longest],
    }


def matching(ops_s: Dict[str, List[float]], pattern: str) -> Tuple[float, float]:
    """(device seconds, calls) summed over the op names that match
    ``pattern``, a regular expression."""
    rx = re.compile(pattern)
    t = c = 0.0
    for name, (s, n) in ops_s.items():
        if rx.search(name):
            t += s
            c += n
    return t, c
