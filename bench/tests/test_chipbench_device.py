"""The device stamp: peaks come only from the table, and a run without
the chip the cell needs prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import costs

ROOT = Path(__file__).resolve().parents[2]


def test_peaks_by_device_kind():
    pk = costs.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(costs.UnknownDeviceError, match="TPU v9"):
        costs.peaks("TPU v9")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3.chat-shared",
         "--seed", "4294967301", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_backend_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
