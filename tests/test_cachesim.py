"""Oracle-parity and property tests for the trace-driven cache simulator.

The contract (DESIGN.md §3): the Pallas kernels (interpret mode, so CI
runs them without a TPU) are bit-exact against two independent LRU
oracles — the array-state numpy oracle and the OrderedDict python one —
and the batched ladder engine is bit-exact against the retained
per-point path over the default iso-area capacity ladder.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cachesim import (capacity_lines, largest_divisor_tile,
                                 simulate_ladder, simulate_reference,
                                 synthetic_traces, trace_dram_scale)
from repro.core.dram import dram_scale
from repro.core.sweep import capacity_ladder
from repro.kernels import ops, ref


def _zipf_trace(n, footprint, seed=0, theta=1.3):
    rng = np.random.RandomState(seed)
    return (rng.zipf(theta, n) % footprint).astype(np.int64)


# --- per-point kernel vs oracles (incl. num_sets=1 / ways=1 edges) ----------


@pytest.mark.parametrize("nsets,ways,tile,n", [
    (1, 1, 1, 400),       # single direct-mapped line
    (1, 16, 1, 400),      # one set, full associativity
    (8, 1, 8, 600),       # direct-mapped, several sets
    (32, 4, 8, 800),
    (64, 8, 64, 800),
    (81, 16, 27, 600),    # odd set count, non-power-of-two tile
])
def test_cache_sim_matches_both_oracles(nsets, ways, tile, n):
    sid = _zipf_trace(n, 10 * nsets, seed=nsets + ways) % nsets
    tags = _zipf_trace(n, 700, seed=nsets)
    h1, m1 = ops.cache_sim(jnp.asarray(sid), jnp.asarray(tags),
                           num_sets=nsets, ways=ways, sets_tile=tile)
    h2, m2 = ref.cache_sim_numpy(sid, tags, num_sets=nsets, ways=ways)
    h3, m3 = ref.cache_sim_python(sid, tags, num_sets=nsets, ways=ways)
    assert (int(h1), int(m1)) == (h2, m2) == (h3, m3)
    assert int(h1) + int(m1) == n


@pytest.mark.parametrize("ways,num_sets,tile", [
    (4, (1, 3, 7, 20, 33), 8),    # partial tiles, odd rungs
    (1, (1, 2, 5), 4),            # ways=1 ladder
    (16, (1,), 1),                # single fully-associative rung
])
def test_ladder_kernel_matches_numpy_oracle(ways, num_sets, tile):
    traces = np.stack([_zipf_trace(600, 500, seed=s) for s in (0, 1)])
    got = ops.cache_sim_ladder(jnp.asarray(traces, jnp.int32),
                               num_sets=num_sets, ways=ways, sets_tile=tile)
    want = ref.cache_sim_ladder_numpy(traces, num_sets, ways=ways)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (np.asarray(got).sum(axis=2) == traces.shape[1]).all()


@pytest.mark.parametrize("chunk", [128, 250, 600])
def test_ladder_kernel_chunked_trace_matches_numpy_oracle(chunk):
    """LRU state and counters carry across SMEM trace chunks, including
    a ragged last chunk whose padding must never be simulated."""
    from repro.kernels import cache_sim
    traces = np.stack([_zipf_trace(600, 500, seed=s) for s in (2, 3)])
    got = cache_sim.cache_sim_ladder(jnp.asarray(traces, jnp.int32),
                                     (3, 20, 33), ways=4, sets_tile=8,
                                     chunk=chunk, interpret=True)
    want = ref.cache_sim_ladder_numpy(traces, (3, 20, 33), ways=4)
    np.testing.assert_array_equal(np.asarray(got), want)


# --- batched engine vs the retained per-point path --------------------------


def test_simulate_ladder_bit_exact_vs_reference_on_default_ladder():
    ladder = capacity_ladder()            # the iso-area search ladder
    traces = synthetic_traces(500, 4096, seeds=(0, 1))
    engine = simulate_ladder(traces, ladder, scale=256, ways=16)
    per_point = np.stack([
        np.stack([np.asarray(simulate_reference(
            tr, capacity_lines(c, scale=256), ways=16)) for c in ladder])
        for tr in traces])
    np.testing.assert_array_equal(engine, per_point)
    oracle = simulate_ladder(traces, ladder, scale=256, ways=16,
                             use_kernel=False)
    np.testing.assert_array_equal(engine, oracle)


def test_capacity_ladder_include_splices_sorted():
    ladder = capacity_ladder(include=(3.0,))
    assert 3.0 in ladder
    assert list(ladder) == sorted(ladder)
    assert len(set(ladder)) == len(ladder)
    # idempotent for capacities already on the ladder
    assert capacity_ladder(include=(0.5,)) == capacity_ladder()


# --- tile-selection regression ----------------------------------------------


def test_largest_divisor_tile_not_degenerate():
    # seed halving loop gave tile=1 for 81 and tile=4 for 100
    assert largest_divisor_tile(81, 64) == 27
    assert largest_divisor_tile(100, 64) == 50
    assert largest_divisor_tile(61, 64) == 61   # prime but <= cap
    assert largest_divisor_tile(4096, 64) == 64
    assert largest_divisor_tile(1, 64) == 1
    assert largest_divisor_tile(30, 7) == 6


def test_simulate_ladder_rejects_line_ids_wider_than_int32():
    # int32 wrap would alias tag -1 with the kernel's EMPTY sentinel and
    # count phantom hits on cold ways — must refuse, not silently cast
    trace = np.array([2 ** 32 - 2, 123, 456, 789], np.int64)
    with pytest.raises(ValueError, match="int32"):
        simulate_ladder(trace, (3.0,), scale=4096)
    with pytest.raises(ValueError, match="int32"):
        simulate_ladder(np.array([-1, 5]), (3.0,), scale=4096)


def test_simulate_reference_odd_set_count_matches_oracle():
    ways = 4
    num_sets = 81
    trace = _zipf_trace(700, 3000, seed=9)
    got = simulate_reference(trace, num_sets * ways, ways=ways)
    want = ref.cache_sim_numpy(trace % num_sets, trace // num_sets,
                               num_sets=num_sets, ways=ways)
    assert got == want


# --- cross-validation against the analytic miss model -----------------------


def test_trace_dram_scale_matches_analytic_model():
    scales = trace_dram_scale([6.0, 12.0], trace_len=30_000,
                              use_kernel=False)
    for c in (6.0, 12.0):
        assert abs(scales[c] - dram_scale(c)) < 0.05


def test_iso_area_trace_mode_close_to_analytic():
    from repro.core.iso import iso_area
    from repro.core.profiles import paper_profiles
    profiles = paper_profiles()[:2]
    kw = dict(trace_len=20_000, use_kernel=False)
    analytic = iso_area(profiles)
    traced = iso_area(profiles, dram_model="trace", trace_kwargs=kw)
    for ra, rt in zip(analytic, traced):
        for m in ("STT", "SOT"):
            a = ra.metrics[m]["edp_with_dram"]
            t = rt.metrics[m]["edp_with_dram"]
            assert abs(a - t) / a < 0.25
    with pytest.raises(ValueError):
        iso_area(profiles, dram_model="bogus")


# Property-based suites live in tests/test_cachesim_properties.py behind
# the repo's standard `pytest.importorskip("hypothesis")` guard, so this
# oracle-parity module always runs even without the dev extras.
