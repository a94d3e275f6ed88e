"""Bring-up smoke test on a TPU: serve phi3-mini-3.8b at its published
widths through the serve launcher, then run each main-path Pallas kernel
compiled against its jnp oracle.

    python chip_smoke.py               # one chip, whatever the host exposes
    python chip_smoke.py --four-chips  # the launcher's (data=1, model=4)
                                       # mesh against the same requests
                                       # on one device

With four chips the greedy tokens are compared request by request; a
request may diverge only at a near-tie (``TIE_ULPS``), since bf16 sums
in another order can flip two logits that close.

Everything runs in this one process, which holds the chip.  Each phase
fails the script on any error.  Earlier lines report per phase the
compile seconds, tokens served and device bytes: information, not
claims.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Without a TPU the script exits
non-zero before doing anything else.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ARCH = "phi3-mini-3.8b"
# 8 slots x 1024 positions: 7.6 GB of bf16 weights plus 3.2 GB of KV
SERVE_ARGV = ["--arch", ARCH, "--no-reduced", "--slots", "8",
              "--max-len", "1024", "--requests", "16",
              "--prompt-lens", "64", "512", "--max-new", "16", "64"]
PHASES = {
    "defaults": [],
    "pallas_decode": ["--attn-impl", "pallas_decode", "--sample-impl",
                      "pallas"],
    "pallas_paged": ["--attn-impl", "pallas_paged", "--shared-prefix",
                     "--page-size", "16"],
}
ATTN_TOL = 2e-2          # the bound benchmarks/kernels_micro.py gates
# kernel-phase widths: phi3-mini's heads, 2048 cache positions
KV_HEADS, HEAD_DIM, KV_LEN, PAGE = 32, 96, 2048, 16
# ladder: long enough that simulation, not launch, dominates the kernel,
# short enough that the numpy oracle checks it in well under a minute
LADDER_TRACES, LADDER_LEN, LADDER_SETS, LADDER_WAYS = 4, 1 << 18, \
    (64, 181, 512, 1448), 16
# four chips against one: a greedy divergence is allowed only at a
# near-tie, two logits within this many bfloat16 ulps
TIE_ULPS = 8


class PhaseError(RuntimeError):
    """A phase ran but its result is wrong."""


def tpu_device():
    """The first device, or None when JAX finds no TPU."""
    import jax

    dev = jax.devices()[0]
    return dev if dev.platform == "tpu" else None


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while open."""

    def __enter__(self):
        from jax import monitoring

        self.seconds = 0.0

        def listen(event, duration, **_):
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration

        self._listen = listen
        monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._listen)
        return False


def _mib(n: float) -> str:
    return f"{n / 2 ** 20:.0f} MiB"


def serve_phase(name: str, argv, devices):
    """One launcher run; returns (mesh, engine, requests).  Passes only if every
    request ends DONE, nothing was quarantined, and no decode window was
    retried or degraded to the eager path."""
    from repro.launch import serve as launcher
    from repro.launch.mesh import mesh_context
    from repro.serve import DONE

    args = launcher.parse_args(argv)
    t0 = time.perf_counter()
    with CompileClock() as cc:
        mesh, eng = launcher.build_engine(args, devices=devices)
        reqs = launcher.make_requests(args, eng.model.cfg.vocab_size)
        with mesh_context(mesh):
            outputs, seconds = launcher.serve(eng, args, reqs)
        launcher.report(eng, args, reqs, outputs, seconds, mesh)
    rs = eng.resilience_stats()
    bad = [r.uid for r in reqs if r.state != DONE]
    if bad or rs["quarantined"] or rs["degraded"] or rs["window_retries"] \
            or rs["window_fallbacks"]:
        raise PhaseError(f"{name}: requests not DONE {bad}, resilience {rs}")
    if args.shared_prefix and eng.paged_stats()["prefix_hits"] == 0:
        raise PhaseError(f"{name}: no prefix hits on shared prefixes")
    stats = devices[0].memory_stats() or {}
    print(f"phase {name}: compile {cc.seconds:.1f}s, "
          f"{sum(len(o) for o in outputs.values())} tokens served in "
          f"{seconds:.1f}s, wall {time.perf_counter() - t0:.1f}s, "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"({_mib(stats.get('peak_bytes_in_use', 0))})", flush=True)
    return mesh, eng, reqs


def _max_err(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _check(name: str, ok: bool, detail: str, t0: float) -> None:
    print(f"kernel {name}: {detail} ({time.perf_counter() - t0:.1f}s)",
          flush=True)
    if not ok:
        raise PhaseError(f"kernel {name}: {detail}")


def kernel_phase(seed: int = 0) -> None:
    """Each main-path kernel once, compiled, against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.cachesim import synthetic_traces
    from repro.kernels import ops, ref

    cfg = get_config(ARCH)
    B, H, K, hd, L = 8, cfg.num_heads, KV_HEADS, HEAD_DIM, KV_LEN
    bf = jnp.bfloat16
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, H, hd), bf)
    k = jax.random.normal(ks[1], (B, L, K, hd), bf)
    v = jax.random.normal(ks[2], (B, L, K, hd), bf)
    nk = jax.random.normal(ks[3], (B, K, hd), bf)
    nv = jax.random.normal(ks[4], (B, K, hd), bf)
    pos = jnp.asarray(rng.integers(0, L, B), jnp.int32)
    win = jnp.int32(0)

    t0 = time.perf_counter()
    err = _max_err(ops.decode_attention(q, k, v, pos, win),
                   ref.decode_attention_ref(q, k, v, pos))
    _check("decode", err <= ATTN_TOL, f"max|err| {err:.3g}", t0)

    t0 = time.perf_counter()
    o, ck, cv = ops.decode_attention_fused(q, k, v, nk, nv, pos, win)
    rows = jnp.arange(B)
    k_ref, v_ref = k.at[rows, pos].set(nk), v.at[rows, pos].set(nv)
    err = _max_err(o, ref.decode_attention_ref(q, k_ref, v_ref, pos))
    exact = bool(jnp.array_equal(ck, k_ref) & jnp.array_equal(cv, v_ref))
    _check("decode_fused", err <= ATTN_TOL and exact,
           f"max|err| {err:.3g}, KV scatter bitwise {exact}", t0)
    del k, v, ck, cv, k_ref, v_ref

    nb = L // PAGE
    n_pages = B * nb + 1
    kp = jax.random.normal(ks[5], (n_pages, PAGE, K, hd), bf)
    vp = jax.random.normal(ks[6], (n_pages, PAGE, K, hd), bf)
    pt = jnp.asarray(rng.permutation(n_pages - 1)[:B * nb].reshape(B, nb),
                     jnp.int32)     # private pages: the CoW precondition
    t0 = time.perf_counter()
    err = _max_err(ops.paged_decode_attention(q, kp, vp, pt, pos, win),
                   ref.paged_decode_attention_ref(q, kp, vp, pt, pos))
    _check("paged", err <= ATTN_TOL, f"max|err| {err:.3g}", t0)

    t0 = time.perf_counter()
    o, ckp, cvp = ops.paged_decode_attention_fused(q, kp, vp, nk, nv, pt,
                                                   pos, win)
    page, row = pt[rows, pos // PAGE], pos % PAGE
    kp_ref, vp_ref = kp.at[page, row].set(nk), vp.at[page, row].set(nv)
    err = _max_err(o, ref.paged_decode_attention_ref(q, kp_ref, vp_ref, pt,
                                                     pos))
    exact = bool(jnp.array_equal(ckp, kp_ref) & jnp.array_equal(cvp, vp_ref))
    _check("paged_fused", err <= ATTN_TOL and exact,
           f"max|err| {err:.3g}, KV scatter bitwise {exact}", t0)
    del kp, vp, ckp, cvp, kp_ref, vp_ref

    t0 = time.perf_counter()
    logits = jax.random.normal(ks[7], (B, cfg.vocab_size), jnp.float32)
    greedy = ops.fused_sample(logits, jnp.zeros(B, jnp.float32), ks[0])
    warm = ops.fused_sample(logits, jnp.full(B, 0.8, jnp.float32), ks[0])
    same = bool(jnp.array_equal(greedy, jnp.argmax(logits, axis=-1)))
    in_vocab = bool(((warm >= 0) & (warm < cfg.vocab_size)).all())
    _check("sampling", same and in_vocab,
           f"greedy == argmax {same}, sampled in vocab {in_vocab}", t0)

    t0 = time.perf_counter()
    traces = synthetic_traces(LADDER_LEN, 1 << 16,
                              seeds=tuple(range(LADDER_TRACES)))
    got = np.asarray(ops.cache_sim_ladder(
        jnp.asarray(traces, jnp.int32), num_sets=LADDER_SETS,
        ways=LADDER_WAYS))
    t_kernel = time.perf_counter() - t0
    t1 = time.perf_counter()
    want = ref.cache_sim_ladder_numpy(traces, LADDER_SETS, ways=LADDER_WAYS)
    exact = bool(np.array_equal(got, want))
    _check("cache_sim_ladder", exact,
           f"{LADDER_TRACES}x{LADDER_LEN} accesses x {len(LADDER_SETS)} "
           f"rungs bit-exact {exact}; kernel incl. compile "
           f"{t_kernel:.1f}s, numpy oracle "
           f"{time.perf_counter() - t1:.1f}s", t0)


def one_chip() -> None:
    import jax

    devices = jax.devices()[:1]
    for name, extra in PHASES.items():
        serve_phase(name, SERVE_ARGV + extra, devices)
        # the engine and its weights go before the next phase draws its
        # own: one chip cannot hold two copies of the weights
        gc.collect()
    with CompileClock() as cc:
        kernel_phase()
    print(f"phase kernels: compile {cc.seconds:.1f}s", flush=True)


def _bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 numbers (8 significant bits) around ``x``."""
    return 2.0 ** (math.frexp(abs(x) or 1.0)[1] - 8)


def _next_logits(eng, mesh, seqs):
    """Next-token logits (n, V) f32 after each token sequence, computed
    by one prefill call on the engine's own devices and parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import mesh_context

    width = -(-max(len(s) for s in seqs) // 128) * 128
    tokens = np.zeros((len(seqs), width), np.int32)
    for row, seq in enumerate(seqs):
        tokens[row, :len(seq)] = seq       # causal: padding after is unseen
    last = np.asarray([len(s) - 1 for s in seqs], np.int32)

    def fn(params, tokens, last):
        logits, _ = eng.model.prefill(params, {"tokens": tokens})
        return logits[jnp.arange(len(seqs)), last].astype(jnp.float32)

    with mesh_context(mesh):
        return np.asarray(jax.jit(fn)(eng.params, tokens, last))


def _compare_greedy(mesh, eng, reqs, outs1) -> None:
    """Greedy tokens on four devices against one.  A request may diverge
    only where the two candidate tokens' logits, recomputed on four
    devices for the shared prefix, are within ``TIE_ULPS`` bf16 ulps: a
    near-tie that summation order decides.  Anything else fails."""
    divs = []
    for r in reqs:
        o1, o4 = outs1[r.uid], list(r.output)
        i = next((i for i, (a, b) in enumerate(zip(o1, o4)) if a != b),
                 None)
        if i is None and len(o1) != len(o4):
            raise PhaseError(f"request {r.uid}: one output is a prefix of "
                             f"the other ({len(o1)} vs {len(o4)} tokens)")
        if i is not None:
            divs.append((r, i))
    print(f"greedy tokens agree on {len(reqs) - len(divs)}/{len(reqs)} "
          "requests", flush=True)
    if not divs:
        return
    lgs = _next_logits(eng, mesh, [list(r.prompt) + list(r.output)[:i]
                                   for r, i in divs])
    wide = []
    for (r, i), lg in zip(divs, lgs):
        t1, t4 = outs1[r.uid][i], r.output[i]
        margin = float(lg[t4] - lg[t1])
        tol = TIE_ULPS * _bf16_ulp(max(abs(lg[t1]), abs(lg[t4])))
        print(f"  request {r.uid} first differs at token {i}: one device "
              f"{t1}, four devices {t4}; four-device logits {lg[t1]:.4f} "
              f"vs {lg[t4]:.4f}, margin {margin:.4g} (tie bound {tol:.4g},"
              f" top logit {lg.max():.4f})", flush=True)
        if abs(margin) > tol:
            wide.append(r.uid)
    if wide:
        raise PhaseError(f"requests {wide} diverge where the logits are "
                         "not a near-tie")


def four_chips() -> None:
    """The launcher's own four-device path (xla attention, parameters
    over a (data=1, model=4) mesh) against the same requests on one."""
    import jax

    devices = jax.devices()
    if len(devices) < 4:
        raise PhaseError(f"--four-chips needs 4 devices, found "
                         f"{len(devices)}")
    devices = devices[:4]
    _, eng, reqs = serve_phase("one_device", SERVE_ARGV, devices[:1])
    outs1 = {r.uid: list(r.output) for r in reqs}
    del eng, reqs
    gc.collect()
    mesh, eng, reqs = serve_phase("four_devices", SERVE_ARGV, devices)
    for name in sorted(eng.params)[:3]:
        print(f"  param {name}: {eng.params[name].sharding}")
    print(f"  after serving: cache k {eng.cache['k'].sharding}; slot state "
          f"pos {eng._state['pos'].sharding}")
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
    print("  bytes_in_use per device after serving: "
          + ", ".join(f"{d.id}: {n} ({_mib(n)})"
                      for d, n in zip(devices, used)), flush=True)
    _compare_greedy(mesh, eng, reqs, outs1)
    eng.reset()      # where Engine.reset creates cache and slot state
    print(f"  after reset: cache k {eng.cache['k'].sharding}; slot state "
          f"pos {eng._state['pos'].sharding}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device mesh against one device")
    args = ap.parse_args(argv)
    dev = tpu_device()
    if dev is None:
        import jax
        print(f"chip_smoke: no TPU (JAX found {jax.devices()[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {dev.device_kind} x {len(jax.devices())}", flush=True)
    try:
        four_chips() if args.four_chips else one_chip()
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
