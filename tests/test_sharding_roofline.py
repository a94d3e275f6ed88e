"""Sharding rule engine + HLO analyzer + roofline + crosslayer + cachesim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.launch.hlo_analysis import analyze_hlo, parse_hlo
from repro.launch.roofline import MODELED_KIND, PEAKS, Roofline
from repro.sharding.rules import default_rules, spec_for

MESH16 = {"data": 16, "model": 16}
MESH512 = {"pod": 2, "data": 16, "model": 16}


def test_spec_divisibility_basic():
    rules = default_rules()
    spec = spec_for(("batch", "seq", None), (256, 4096, 512), MESH16, rules)
    assert spec[0] == "data"
    # kv_heads=8 can't take model(16); kv_seq picks it up
    spec = spec_for(("batch", "kv_seq", "kv_heads", "head_dim"),
                    (128, 32768, 8, 128), MESH16, rules)
    assert spec[1] == "model" and (len(spec) < 3 or spec[2] is None)
    # kv_heads=16 wins over kv_seq (higher priority)
    spec = spec_for(("batch", "kv_seq", "kv_heads", "head_dim"),
                    (128, 32768, 16, 128), MESH16, rules)
    assert spec[2] == "model" and spec[1] is None


def test_spec_multipod_batch():
    rules = default_rules(multi_pod=True)
    spec = spec_for(("batch", "seq"), (256, 4096), MESH512, rules)
    assert spec[0] == ("pod", "data")


def test_spec_experts_fallback():
    rules = default_rules()
    # 40 experts don't divide 16 -> expert_ffn gets model
    spec = spec_for(("experts", "ffn_in", "expert_ffn"), (40, 1536, 512),
                    MESH16, rules)
    assert spec[0] is None and spec[2] == "model"
    # 64 experts divide -> EP
    spec = spec_for(("experts", "ffn_in", "expert_ffn"), (64, 2048, 1408),
                    MESH16, rules)
    assert spec[0] == "model"


@given(dims=st.lists(st.sampled_from([1, 2, 3, 8, 16, 40, 64, 128, 256]),
                     min_size=1, max_size=4),
       names=st.lists(st.sampled_from(["batch", "heads", "ffn", "vocab",
                                       "kv_seq", "experts", None]),
                      min_size=1, max_size=4))
@settings(max_examples=100, deadline=None)
def test_spec_never_violates_divisibility(dims, names):
    n = min(len(dims), len(names))
    dims, names = tuple(dims[:n]), tuple(names[:n])
    rules = default_rules()
    spec = spec_for(names, dims, MESH16, rules)
    used = []
    for dim, ax in zip(dims, tuple(spec) + (None,) * (n - len(spec))):
        if ax is None:
            continue
        axes = (ax,) if isinstance(ax, str) else ax
        size = int(np.prod([MESH16[a] for a in axes]))
        assert dim % size == 0
        used += list(axes)
    assert len(used) == len(set(used))  # each mesh axis used at most once


# --- HLO analyzer --------------------------------------------------------------


_FAKE_HLO = """
%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  %t = (s32[], f32[8,16]) tuple(%i, %ar)
  ROOT %r = (s32[], f32[8,16]) tuple(%i, %ar)
}
%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  ROOT %c = pred[] constant(true)
}
ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %t0 = (s32[], f32[8,16]) tuple(%a, %a)
  %w0 = (s32[], f32[8,16]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w0), index=1
}
"""


def test_hlo_while_multiplier_flops_and_collectives():
    stats = analyze_hlo(_FAKE_HLO)
    # dot: 2 * 8*16 * 16 = 4096 flops, x10 trips
    assert stats.flops == pytest.approx(4096 * 10)
    # all-reduce payload 8*16*4 bytes, ring 2(n-1)/n with n=4, x10
    want = 8 * 16 * 4 * 2 * 3 / 4 * 10
    assert stats.collective_link_bytes == pytest.approx(want)
    assert stats.collective_counts["all-reduce"] == 10


def test_hlo_analyzer_on_real_compiled_scan():
    L, M = 7, 32

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    stats = analyze_hlo(compiled.as_text())
    want = 2 * M * M * M * L
    assert abs(stats.flops / want - 1) < 0.01


def test_roofline_terms():
    pk = PEAKS[MODELED_KIND]
    r = Roofline(flops_per_device=pk.flops, bytes_per_device=pk.hbm_bw,
                 collective_bytes=2 * pk.ici_bw, collectives={},
                 collective_counts={}, temp_bytes=0, arg_bytes=0,
                 device_kind=MODELED_KIND)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(2.0)
    assert r.dominant == "collective"
    assert r.model_flops_util(pk.flops) == pytest.approx(0.5)


def test_roofline_prices_by_device_kind():
    """CPU compiles are priced as the modeled v5e; a TPU kind missing
    from the table raises instead of borrowing another chip's peaks."""
    from repro.launch.roofline import (UnknownChipError, analyze,
                                       priced_kind)
    cpu = jax.devices("cpu")[0]
    assert priced_kind(cpu) == MODELED_KIND
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    assert analyze(compiled).device_kind == MODELED_KIND

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v99"
    r = Roofline(flops_per_device=1.0, bytes_per_device=1.0,
                 collective_bytes=0.0, collectives={}, collective_counts={},
                 temp_bytes=0, arg_bytes=0,
                 device_kind=priced_kind(FakeTpu()))
    with pytest.raises(UnknownChipError, match="TPU v99"):
        r.compute_s


# --- crosslayer -----------------------------------------------------------------


def test_crosslayer_verdict():
    from repro.core.crosslayer import analyze_record
    rec = {"arch": "x", "shape": "train_4k", "mesh": "16x16",
           "roofline": {"bytes_per_device": 1e12, "compute_s": 1.0,
                        "memory_s": 1.2, "collective_s": 0.3}}
    v = analyze_record(rec)
    assert v.reads > v.writes > 0
    for m in ("STT", "SOT"):
        assert 0 < v.energy_ratio[m] < 10
        assert 0 < v.edp_ratio[m] < 10


# --- cache simulator vs analytic miss model --------------------------------------


def test_simulated_miss_curve_matches_analytic():
    from repro.core.cachesim import dram_reduction_curve
    from repro.core.dram import dram_reduction_pct
    sim = dram_reduction_curve((3, 7, 10), trace_len=150_000, seed=3)
    assert abs(sim[7] - dram_reduction_pct(7)) < 6.0
    assert abs(sim[10] - dram_reduction_pct(10)) < 7.0
    assert sim[7] < sim[10]
