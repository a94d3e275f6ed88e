"""The paged suffix prefill computes only the admitted rows, at most
``PREFILL_ROWS`` a step, and only their last-position logits.

On the CPU, at a reduced size with GQA group 7 (14 query heads over 2 kv
heads) and more slots than prefill rows: the program's logits against
the benchmark's float32 reference through a capped admission, a prefix
hit and paged decode; a whole traced run of a tiny closed-loop cell,
added as files, whose prefill spans feed ``prefill_fill``; and the batch
cell's check limit against the float8 control at a small GQA size.
"""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, costs, harness
from bench import weights as W
from bench.adapters import dense as adapter
from bench.reference import dense as ref
from bench.tests import tiny

TINY = {"num_layers": 2, "d_model": 64, "num_heads": 14, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 512, "qkv_bias": True,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-6}
CONF = {"name": "tiny-qwen2-g7", "arch": "qwen2-7b",
        "overrides": {k: v for k, v in TINY.items() if k != "rms_norm_eps"},
        "model": TINY}
MAX_LEN, PAGE, SLOTS = 64, 8, 10
# bfloat16 weights and activations against float32, as in
# test_chipbench_reference.py: logits of unit spread agree to about 2**-8
# per op, and 0.08 holds what two layers and the head accumulate, while
# a row's KV or t0 taken from another row, or a wrong position, moves
# logits by O(1)
LOGIT_TOL = 0.08


@pytest.fixture(autouse=True)
def _restore_cache_config():
    """A harness run turns JAX's persistent cache on for the whole
    process; put the settings back for later tests in this process."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def _recording_engine(seed, tracer):
    """A paged engine that keeps, per request, the logits row behind each
    token it served: prefill rows map to the slots the admission filled
    (in slot order), and decode row s of tick k to slot s's request."""
    from repro.serve import PagedEngine

    class Recording(PagedEngine):
        def __init__(self, *a, **kw):
            self.seen, self.logits = [], {}
            super().__init__(*a, **kw)

        def _sample_batch(self, lg, temps, sub):
            jax.debug.callback(self.seen.append, lg, ordered=True)
            return super()._sample_batch(lg, temps, sub)

        def _admit(self):
            before = list(self.slot_req)
            n0 = len(self.seen)
            took = super()._admit()
            jax.effects_barrier()
            new = [s for s, r in enumerate(self.slot_req)
                   if r is not None and r is not before[s]]
            for lg in self.seen[n0:]:
                assert lg.shape[0] == self.rows
                for i, s in enumerate(new):
                    r = self.slot_req[s]
                    self.logits.setdefault(r.uid, {})[0] = lg[i]
            self._window_slots = [(r, len(r.output)) if r else None
                                  for r in self.slot_req]
            self._n_window = len(self.seen)
            return took

        def step(self):
            n = super().step()
            jax.effects_barrier()
            for k, lg in enumerate(self.seen[self._n_window:]):
                assert lg.shape[0] == self.slots
                for s, held in enumerate(self._window_slots):
                    if held is None:
                        continue
                    r, j0 = held
                    if j0 + k < len(r.output):
                        self.logits[r.uid][j0 + k] = lg[s]
            return n

    model = adapter.build_model(CONF, MAX_LEN)
    params = adapter.draw_params(CONF, model, seed)
    return Recording(model, params, slots=SLOTS, max_len=MAX_LEN,
                     page_size=PAGE, eos_id=None, ticks_per_sync=4,
                     record_traffic=False, attn_impl="pallas_paged",
                     sample_impl="pallas", tracer=tracer)


def _ref_logits(seed, req):
    seq = list(req.prompt) + list(req.output)
    toks = np.zeros((1, MAX_LEN), np.int32)
    toks[0, :len(seq)] = seq
    h = ref.final_residual(TINY, seed, toks)
    L, n = len(req.prompt), len(req.output)
    g = jax.jit(lambda r: W.draw_globals(TINY, r))(W.root_key(seed))
    with jax.default_matmul_precision("highest"):
        x = ref.rms_norm(h[0, L - 1:L - 1 + n],
                         g["final_norm"].astype(jnp.float32), 1e-6)
        return np.asarray(x @ g["lm_head"].astype(jnp.float32))


def test_capped_admission_and_prefix_hit_match_the_reference():
    from repro.serve import Request, Tracer
    from repro.serve.engine import PREFILL_ROWS

    seed = 2 ** 31 + 5
    tracer = Tracer()
    eng = _recording_engine(seed, tracer)
    assert eng.rows == PREFILL_ROWS < SLOTS
    rng = np.random.default_rng(1)
    first = rng.integers(0, 512, 21).tolist()
    reqs = [Request(uid=0, prompt=first, max_new_tokens=3)]
    reqs += [Request(uid=u, prompt=rng.integers(0, 512, 5 + u).tolist(),
                     max_new_tokens=6 + u % 5) for u in range(1, 11)]
    # shares 13 tokens with uid 0: a full page and a boundary page that
    # is copied on write
    reqs.append(Request(uid=11, prompt=first[:13]
                        + rng.integers(0, 512, 6).tolist(),
                        max_new_tokens=9))
    for r in reqs:
        eng.submit(r)
    eng.step()
    # 12 queued, 10 slots free: the prefill takes 8, the rest wait
    admit = [s for s in tracer._spans if s["cat"] == "admit"]
    prefill = [s for s in tracer._spans if s["cat"] == "prefill"]
    assert admit[0]["args"]["admitted"] == PREFILL_ROWS
    assert admit[0]["args"]["capped"] == SLOTS - PREFILL_ROWS
    assert prefill[0]["args"]["rows"] == PREFILL_ROWS
    assert len(eng._queue) == len(reqs) - PREFILL_ROWS
    while not all(r.terminal for r in reqs):
        eng.step()
    st = eng.paged_stats()
    assert st["prefix_hits"] >= 1 and st["cow_copies"] >= 1
    assert all(s["args"]["admitted"] <= PREFILL_ROWS for s in admit)
    for r in reqs:
        assert r.state == "DONE" and len(r.output) == r.max_new_tokens
        got = np.stack([eng.logits[r.uid][j] for j in range(len(r.output))])
        want = _ref_logits(seed, r)
        assert np.abs(got - want).max() < LOGIT_TOL, r.uid
        assert np.abs(want).std() > 0.5          # logits of unit spread
        # each served token is the program's own argmax
        np.testing.assert_array_equal(got.argmax(-1), r.output)


CLOSED_LOOP = {"generator": "closed_loop", "shape_seed": 3,
               "outstanding_per_slot": 2, "requests": 3000,
               "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
               "output": {"median": 10, "sigma": 0.5, "min": 6, "max": 16}}


def test_closed_loop_cell_with_more_slots_than_rows_reports_prefill_fill(
        tmp_path, monkeypatch):
    """A tiny closed-loop cell with 10 slots, added as files: the run is
    correct, its traced prefill spans carry ``rows`` and its admit spans
    ``capped``, and ``prefill_fill`` reads them."""
    import repro.serve as serve
    from repro.serve.engine import PREFILL_ROWS

    root = tiny.make(tmp_path, tiny.chat_limit(), traffic=CLOSED_LOOP)
    cell = root / "bench" / "workloads" / f"{tiny.CELL}.json"
    spec_file = json.loads(cell.read_text())
    spec_file["engine"]["slots"] = SLOTS
    cell.write_text(json.dumps(spec_file))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] == "prefill_fill":
            m["workloads"].append(tiny.CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    tracers = []

    class Kept(serve.Tracer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            tracers.append(self)

    monkeypatch.setattr(serve, "Tracer", Kept)
    spec = harness.load_spec(tiny.CELL, root)
    out = harness.run(spec, 2 ** 33 + 7, 1.5, True, require_tpu=False,
                      peaks=costs.peaks("TPU v5 lite"),
                      log=lambda *a, **k: None)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    fill = out["metrics"]["prefill_fill"]
    assert fill["unit"] == "%" and 0 < fill["value"] <= 100
    spans = tracers[0]._spans
    prefill = [s for s in spans if s["cat"] == "prefill"]
    admit = [s for s in spans if s["cat"] == "admit"]
    assert prefill and all(s["args"]["rows"] == PREFILL_ROWS
                           and s["args"]["admitted"] <= PREFILL_ROWS
                           for s in prefill)
    # the traffic before the window submits 20 requests to 10 free slots
    assert admit[0]["args"]["capped"] == SLOTS - PREFILL_ROWS
    assert all(s["args"]["capped"] >= 0 for s in admit)


def test_prefill_fill_finds_nothing_in_a_program_without_rows():
    """A program whose prefill spans carry no ``rows`` (the parent's)
    gives no reading, and does not raise."""
    span = {"name": "prefill_chunk S=8", "cat": "prefill", "t0": 1.0,
            "t1": 1.1, "args": {"admitted": 2, "padded_len": 8,
                                "suffix_tokens": 11}}
    read = harness.metric_reader("prefill_fill")
    assert read(types.SimpleNamespace(spans=[span])) is None
    assert read(types.SimpleNamespace(spans=[])) is None
    with_rows = dict(span, args=dict(span["args"], rows=8))
    assert read(types.SimpleNamespace(spans=[with_rows])) == \
        pytest.approx(100.0 * 11 / 64)


BATCH_CELL = harness.ROOT / "bench" / "workloads" / \
    "qwen2h.batch-reasoning.json"
SMALL = {"num_layers": 8, "d_model": 256, "num_heads": 14, "num_kv_heads": 2,
         "head_dim": 32, "d_ff": 1024, "vocab_size": 2048, "qkv_bias": True,
         "rope_theta": 1000000.0, "rms_norm_eps": 1e-6}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_batch_cell_limit(seed):
    """The reference computed with float8 operands puts first, at GQA
    group 7 with QKV bias, tokens whose float32 gap passes the batch
    cell's limit, so a program that computed in float8 would come out
    not correct.  (PERF.md gives the readings at the cell's own size.)"""
    limit = json.loads(BATCH_CELL.read_text())["check"]["max_widest_gap"]
    rng = np.random.default_rng(seed)
    reqs = [types.SimpleNamespace(prompt=rng.integers(0, 2048, 20).tolist(),
                                  output=rng.integers(0, 2048, 100).tolist(),
                                  uid=0) for _ in range(4)]
    conf = {"name": "small-qwen2", "reference": "dense", "model": SMALL}
    assert check.control_gaps(conf, seed, reqs, 4, 128).max() > limit
