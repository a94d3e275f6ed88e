"""Whole runs of a tiny cell on the CPU: a cell, configuration, traffic
mix and metric added as new files run without an edit to the harness,
and the check rejects a run whose timed path is broken underneath."""
import jax
import pytest

from bench import costs, harness
from bench.tests import tiny


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


def _run(tmp_path, trace=False, extra_metric=None, seed=2 ** 32 + 17,
         traffic=None):
    root = tiny.make(tmp_path, tiny.chat_limit(), extra_metric, traffic)
    spec = harness.load_spec(tiny.CELL, root)
    return harness.run(spec, seed, 1.5, trace, require_tpu=False,
                       peaks=costs.peaks("TPU v5 lite"),
                       log=lambda *a, **k: None)


def test_new_cell_config_mix_and_metric_run_from_files(tmp_path):
    out = _run(tmp_path, trace=True, extra_metric="decoded_rows.tiny")
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["decoded_rows.tiny"]["value"] > 0
    assert m["prefix_hit_rate"]["value"] > 0          # shared system prompts
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert list(out)[-1] == "check"
    assert out["check"]["widest_logit_gap"]["value"] \
        <= out["check"]["widest_logit_gap"]["limit"]


def test_an_unshared_mix_added_as_a_file_runs(tmp_path):
    traffic = dict(tiny.TRAFFIC, system={"count": 0, "length": 10,
                                         "zipf_s": 1.1},
                   turns={"min": 1, "max": 1})
    out = _run(tmp_path, traffic=traffic)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["ttft_p95_ms"]["value"] > 0


def _alter_row0(orig):
    def f(logits, temps, key, **kw):
        tok = orig(logits, temps, key, **kw)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])
    return f


def _drop_kv_writes(orig):
    def f(q, k, v, new_k, new_v, *a, **kw):
        o, _, _ = orig(q, k, v, new_k, new_v, *a, **kw)
        return o, k, v                      # the pools come back unchanged
    return f


def _half_batch(orig):
    def f(q, *a, **kw):
        o, ck, cv = orig(q, *a, **kw)
        half = q.shape[0] // 2
        return o.at[:half].set(0), ck, cv   # the first half left out
    return f


@pytest.mark.parametrize("name,fault", [
    ("fused_sample", _alter_row0),
    ("paged_decode_attention_fused", _drop_kv_writes),
    ("paged_decode_attention_fused", _half_batch),
], ids=["token_altered", "state_unchanged", "half_batch_left_out"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, name,
                                            fault):
    from repro.kernels import ops
    monkeypatch.setattr(ops, name, fault(getattr(ops, name)))
    out = _run(tmp_path)
    assert out["correct"] is False
    gap = out["check"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]
