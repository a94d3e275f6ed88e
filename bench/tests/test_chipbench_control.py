"""The check's control at a size a test run can hold: the reference
computed with float8 operands puts first, on three seeds, tokens whose
float32 gap passes the chat cell's limit, so a program that computed in
float8 would come out not correct.  (On the chip the control is read at
the cell's own size; PERF.md gives those readings.)"""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import check

CELL = Path(__file__).resolve().parents[1] / "workloads" / "phi3.chat-shared.json"
MODEL = {"num_layers": 8, "d_model": 256, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 64, "d_ff": 1024, "vocab_size": 2048, "qkv_bias": False,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-05}
CONF = {"name": "small-phi3", "reference": "dense", "model": MODEL}


class Served:
    def __init__(self, prompt, output):
        self.prompt, self.output, self.uid = prompt, output, 0


def _requests(seed):
    rng = np.random.default_rng(seed)
    return [Served(rng.integers(0, 2048, 20).tolist(),
                   rng.integers(0, 2048, 100).tolist()) for _ in range(4)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_limit(seed):
    limit = json.loads(CELL.read_text())["check"]["max_widest_gap"]
    reqs = _requests(seed)
    control = check.control_gaps(CONF, seed, reqs, 4, 128)
    assert control.max() > limit
    # the reference's own first choices read 0
    from bench.reference import dense as ref
    toks, rows, _ = check._batch(reqs, 4, 128)
    h = ref.final_residual(MODEL, seed, toks)
    _, _, top = ref.head_rows(MODEL, seed, check._rows(h, rows),
                              np.zeros(len(rows), np.int32))
    best, at, _ = ref.head_rows(MODEL, seed, check._rows(h, rows), top)
    np.testing.assert_array_equal(best - at, 0.0)
