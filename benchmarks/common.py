"""Shared benchmark utilities: timing and CSV row emission.

Timing discipline (DESIGN.md §14): JAX dispatch is asynchronous, so a
timing loop that reads ``perf_counter`` without blocking on the outputs
measures launch overhead, not the computation.  ``timed`` blocks on each
iteration's outputs.  The rows are CPU timings of the paper-figure
scripts, printed beside their paper-target comparisons; they are not
speed evidence (the chip benchmark, ``bench/``, is).
"""
from __future__ import annotations

import time
from typing import Callable

import jax


def timed(name: str, fn: Callable, *, repeats: int = 3):
    """Run fn warm ``repeats`` times; return (last output, us per call).

    Blocks on each call's outputs (``jax.block_until_ready``) before
    reading the clock — without this, async dispatch returns as soon as
    the work is enqueued and warm timings collapse toward launch
    overhead (regression-tested in tests/test_bench_timed.py).
    """
    jax.block_until_ready(fn())  # warmup / build caches
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = jax.block_until_ready(fn())
    us = (time.perf_counter() - t0) / repeats * 1e6
    return out, us


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def run_and_emit(name: str, fn: Callable, derive: Callable[[object], str],
                 repeats: int = 1):
    out, us = timed(name, fn, repeats=repeats)
    emit(name, us, derive(out))
    return out
