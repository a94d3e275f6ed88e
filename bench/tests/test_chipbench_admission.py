"""Whole runs of a tiny cell on the CPU: the admission path's metrics
read the engine's queue stamps and admission spans when traced, and a
program without them gives no reading."""
import math
import types

import jax
import pytest

from bench import costs, harness
from bench.tests import tiny

ADMISSION = ("queue_wait_p95_ms", "admit_stall_share", "admit_host_ms")


@pytest.fixture(autouse=True)
def _restore_cache_config():
    """A run turns JAX's persistent cache on for the whole process; put
    the settings back so later tests in this process see what they saw."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def _run(tmp_path, trace):
    root = tiny.make(tmp_path, tiny.chat_limit())
    spec = harness.load_spec(tiny.CELL, root)
    return harness.run(spec, 2 ** 32 + 17, 1.5, trace, require_tpu=False,
                       peaks=costs.peaks("TPU v5 lite"),
                       log=lambda *a, **k: None)


def test_traced_run_reports_the_admission_path(tmp_path):
    m = _run(tmp_path, trace=True)["metrics"]
    # 2 slots at 8 sessions/s: requests wait in the queue
    assert 0 < m["queue_wait_p95_ms"]["value"] < math.inf
    assert 0 <= m["admit_stall_share"]["value"] <= 100
    assert m["admit_host_ms"]["value"] >= 0
    assert {m[k]["unit"] for k in ADMISSION} == {"ms", "%"}


def test_untraced_run_reports_no_admission_metric(tmp_path):
    m = _run(tmp_path, trace=False)["metrics"]
    assert m["ttft_p95_ms"]["value"] > 0
    assert not set(ADMISSION) & set(m)


def test_admission_readers_find_nothing_in_a_program_without_them():
    """A program whose requests carry no queue stamp and whose engine
    records no ``admit`` span gives no reading, and does not raise."""
    req = types.SimpleNamespace(submit_time=1.0, state="DONE")
    rec = types.SimpleNamespace(
        due=[(types.SimpleNamespace(req=req), 1.0)],
        spans=[{"name": "prefill_chunk S=8", "cat": "prefill", "t0": 1.0,
                "t1": 1.1, "args": {}},
               {"name": "decode_window", "cat": "decode", "t0": 1.1,
                "t1": 1.2, "args": {"active": 2}}])
    for name in ADMISSION:
        assert harness.metric_reader(name)(rec) is None
