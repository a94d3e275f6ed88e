"""The trace reduction on a profiler trace recorded on the CPU: a jitted
step (dot, tanh, two reductions) run three times, each in a ``step``
annotation and followed by 20 ms in a ``wait`` annotation.  The expected
numbers are the event times of that file, added by hand."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"
LO, HI = 429857.0, 41987452.0          # first op start, last op end (ns)
BUSY_NS = 145082 + 149963 + 179493      # the three steps' op durations
DOT_NS = 119349 + 118462 + 142558       # dot_general.1, three calls


@pytest.fixture(scope="module")
def pd():
    return tr.load(str(TRACE))


def test_device_ops_and_busy_union(pd):
    planes = tr.device_ops(pd)
    assert list(planes) == ["/host:CPU"]
    ops = planes["/host:CPU"]
    assert len(ops) == 12
    merged = tr.union((a, b) for _, a, b in ops)
    assert tr.busy_ns(merged, LO, HI) == BUSY_NS
    # clipping: half of the first dot lies before the window
    assert tr.busy_ns(merged, 489531.5, HI) == BUSY_NS - 59674.5


def test_reduce_idle_gaps_and_kernel_time(pd):
    spans = tr.host_spans(pd, ("step", "wait"))
    assert [s[0] for s in spans] == ["step", "wait"] * 3
    out = tr.reduce(pd, LO, HI, spans=spans)
    assert out["busy_s"] == pytest.approx(BUSY_NS * 1e-9, rel=1e-12)
    assert out["window_s"] == pytest.approx((HI - LO) * 1e-9, rel=1e-12)
    assert out["ops"]["dot_general.1"] == [pytest.approx(DOT_NS * 1e-9), 3]
    assert tr.matching(out["ops"], r"^dot_general") == (
        pytest.approx(DOT_NS * 1e-9), 3)
    assert tr.matching(out["ops"], r"no_such_kernel") == (0.0, 0.0)
    # the two long gaps are the sleeps, longest first
    (l1, g1), (l2, g2) = out["idle_gaps"][:2]
    assert (l1, l2) == ("wait", "wait")
    assert g1 == pytest.approx((41804642 - 21183521) * 1e-9)
    assert g2 == pytest.approx((21031244 - 576338) * 1e-9)
    # a gap between two ops of one step is labelled by that step
    assert tr.label((549206.0, 549493.0), spans) == "step"
    assert out["device_ops"][0][0] == "dot_general.1"
    idle = sum(g for _, g in out["idle_gaps"])
    assert idle <= out["window_s"] - out["busy_s"] + 1e-12


def test_clock_offset_from_the_marker(pd):
    # the marker was opened at perf_counter() == 1976.848854274 s
    off = tr.clock_offset_ns(pd, "bench_clock", 1976.848854274)
    assert off == pytest.approx(143351.0 - 1976.848854274e9)


def test_union_and_gaps_of_overlapping_intervals():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert tr.idle_gaps(merged, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert tr.busy_ns(merged, 2, 6) == 2
