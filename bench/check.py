"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the timed path finished (drawn from the seed, with the
longest among them) goes through the float32 reference once: each prompt
with the tokens the program served after it.  At every served position
the number compared is the gap by which the served token's reference
logit lies below the reference's best logit there; a served token that
the reference would also pick has gap 0.  The widest gap over the sample
must stay under the cell's limit (``check.max_widest_gap``), which was
set between the program's readings over many seeds and the
lower-precision control's (see PERF.md).

``control_gaps`` gives the control's reading: the gap, under the float32
reference, of the token that the same reference computed in float8 puts
first at each of the same positions.
"""
from __future__ import annotations

import importlib
import sys
from typing import Dict, List, Sequence

import numpy as np


def pick(finished: Sequence, seed: int, n: int) -> List:
    """The longest finished request and n-1 others drawn from the seed."""
    if not finished:
        return []
    total = lambda r: len(r.prompt) + len(r.output)
    order = sorted(finished, key=lambda r: (-total(r), r.uid))
    rest = list(order[1:])
    rng = np.random.default_rng(int(seed))
    idx = rng.permutation(len(rest))[:max(0, n - 1)]
    return [order[0]] + [rest[i] for i in sorted(idx)]


def _batch(reqs, n: int, T: int):
    toks = np.zeros((n, T), np.int32)
    rows, served = [], []
    for b, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.output)
        toks[b, :len(seq)] = seq[:T]
        L = len(r.prompt)
        rows.extend((b, L - 1 + j) for j in range(len(r.output)))
        served.extend(r.output)
    return toks, np.array(rows, np.int32).reshape(-1, 2), np.array(served)


def _rows(h, rows):
    import jax.numpy as jnp
    return h[jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1])]


def served_gaps(conf: dict, seed: int, reqs, n: int, T: int) -> np.ndarray:
    """Reference best logit minus the served token's, at every served
    position of ``reqs`` (padded to ``n`` sequences of ``T``)."""
    ref = importlib.import_module(f"bench.reference.{conf['reference']}")
    m = conf["model"]
    toks, rows, served = _batch(reqs, n, T)
    h = ref.final_residual(m, seed, toks)
    best, at, _ = ref.head_rows(m, seed, _rows(h, rows), served)
    return best - at


def control_gaps(conf: dict, seed: int, reqs, n: int, T: int,
                 quant: str = "fp8") -> np.ndarray:
    """The same gaps for the token that the reference computed with
    ``quant`` operands (the float8 control) puts first."""
    ref = importlib.import_module(f"bench.reference.{conf['reference']}")
    m = conf["model"]
    toks, rows, served = _batch(reqs, n, T)
    hc = ref.final_residual(m, seed, toks, quant=quant)
    _, _, pick8 = ref.head_rows(m, seed, _rows(hc, rows), served, quant=quant)
    del hc
    h = ref.final_residual(m, seed, toks)
    best, at, _ = ref.head_rows(m, seed, _rows(h, rows), pick8)
    return best - at


def run(spec, seed: int, finished, log=print) -> Dict:
    c = spec.cell["check"]
    e = spec.cell["engine"]
    sample = pick(finished, seed, c["sample_requests"])
    limit = float(c["max_widest_gap"])
    min_tokens = int(c["sample_tokens"])
    if not sample:
        gaps = np.zeros(0)
    else:
        gaps = served_gaps(spec.conf, seed, sample, c["sample_requests"],
                           e["max_len"])
    widest = float(gaps.max()) if gaps.size else float("inf")
    numbers = {"widest_logit_gap": {"value": widest, "limit": limit},
               "tokens_compared": {"value": int(gaps.size),
                                   "limit": min_tokens}}
    correct = bool(gaps.size >= min_tokens and widest <= limit)
    log(f"check: {len(sample)} requests, {gaps.size} served tokens, "
        f"{int((gaps > 0).sum())} off the reference's argmax",
        file=sys.stderr)
    return {"correct": correct, "numbers": numbers, "gaps": gaps,
            "sample": sample}
