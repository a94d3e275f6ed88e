"""The benchmark's float32 reference against the program's paged serve
path, at a reduced size on the CPU: prefill into the page pool, a prefix
hit with a copy-on-write boundary page, and paged decode through the
Pallas kernel (interpret mode here)."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.adapters import dense as adapter
from bench.reference import dense as ref

TINY = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 512, "qkv_bias": True,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-6}
CONF = {"name": "tiny-qwen2", "arch": "qwen2-7b",
        "overrides": {k: v for k, v in TINY.items() if k != "rms_norm_eps"},
        "model": TINY}
MAX_LEN, PAGE = 64, 8
# bfloat16 weights and activations against float32: logits of unit spread
# agree to about 2**-8 per op; 0.08 allows the error that two layers and
# the head accumulate while a wrong KV row or position moves logits by O(1)
LOGIT_TOL = 0.08


def test_layer_draw_equals_its_slice_of_the_stacked_draw():
    root = W.root_key(2 ** 31 + 12345)
    layers, glob = jax.jit(lambda r: W.draw_stacked(TINY, r))(root)
    one = jax.jit(lambda r, i: W.draw_layer(TINY, r, i))(root, jnp.int32(1))
    for name, a in one.items():
        np.testing.assert_array_equal(np.asarray(layers[name][1]),
                                      np.asarray(a))
    assert glob["lm_head"].dtype == jnp.bfloat16


class _Recorder:
    """Logits of every sampling call of the engine, in call order."""

    def __init__(self):
        self.calls = []

    def __call__(self, lg):
        self.calls.append(np.asarray(lg))


def _engine(seed, recorder):
    from repro.serve import PagedEngine

    class Recording(PagedEngine):
        def _sample_batch(self, lg, temps, sub):
            jax.debug.callback(recorder, lg, ordered=True)
            return super()._sample_batch(lg, temps, sub)

    model = adapter.build_model(CONF, MAX_LEN)
    params = adapter.draw_params(CONF, model, seed)
    return Recording(model, params, slots=2, max_len=MAX_LEN, page_size=PAGE,
                     eos_id=None, ticks_per_sync=4, record_traffic=False,
                     attn_impl="pallas_paged", sample_impl="pallas")


def _serve_alone(eng, rec, req):
    """Serve one request with the engine otherwise empty; return the
    logits row that produced each of its tokens (slot 0)."""
    eng.submit(req)
    start = len(rec.calls)
    while not req.terminal:
        eng.step()
    jax.effects_barrier()
    rows = [c[0] for c in rec.calls[start:]]
    return np.stack(rows[:len(req.output)])


def _ref_logits(seed, req):
    seq = list(req.prompt) + list(req.output)
    T = 64
    toks = np.zeros((1, T), np.int32)
    toks[0, :len(seq)] = seq
    h = ref.final_residual(TINY, seed, toks)
    L, n = len(req.prompt), len(req.output)
    g = jax.jit(lambda r: W.draw_globals(TINY, r))(W.root_key(seed))
    rows = h[0, L - 1:L - 1 + n]
    with jax.default_matmul_precision("highest"):
        x = ref.rms_norm(rows, g["final_norm"].astype(jnp.float32), 1e-6)
        return np.asarray(x @ g["lm_head"].astype(jnp.float32))


def test_paged_serve_matches_reference_with_a_prefix_hit():
    from repro.serve import Request

    seed = 7
    rec = _Recorder()
    eng = _engine(seed, rec)
    rng = np.random.default_rng(0)
    prompt_a = rng.integers(0, 512, 21).tolist()
    a = Request(uid=0, prompt=prompt_a, max_new_tokens=9)
    got_a = _serve_alone(eng, rec, a)
    # b shares a's first 13 tokens: one full page and a boundary page
    # that is copied on write
    b = Request(uid=1, prompt=prompt_a[:13] + rng.integers(0, 512, 6).tolist(),
                max_new_tokens=11)
    got_b = _serve_alone(eng, rec, b)
    st = eng.paged_stats()
    assert st["prefix_hits"] == 1 and st["cow_copies"] == 1
    for req, got in ((a, got_a), (b, got_b)):
        want = _ref_logits(seed, req)
        assert np.abs(got - want).max() < LOGIT_TOL
        assert np.abs(want).std() > 0.5        # logits of unit spread
        # each served token is the program's own argmax
        np.testing.assert_array_equal(got.argmax(-1), req.output)
