"""Production serving launcher: mesh + sharded params + fused engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
        --no-reduced --ticks-per-sync 16 --temperature 0.7
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
        --arrival-rate 0.5 --burst-amp 0.6 --trace-out /tmp/serve.json

``--reduced`` defaults on (CPU-runnable smoke config) and — unlike the
seed's ``action="store_true", default=True``, which could never be turned
off — is disabled with ``--no-reduced`` for full-size configs.

``--arrival-rate > 0`` switches from fixed staggered groups to the real
traffic generator (DESIGN.md §14): Poisson arrivals in the tick domain
(optionally burst-modulated via ``--burst-amp``/``--burst-period``),
lognormal heavy-tailed prompt/output lengths, admission by arrival time.
After an arrival-driven run the launcher prints TTFT/TPOT/end-to-end
p50/p95/p99 (tick-domain and wall-clock) and FAILS if the percentiles
are empty or any request went unserved — the CI smoke leans on that.
``--trace-out PATH`` attaches a telemetry tracer and writes a
chrome://tracing JSON of the engine's prefill calls, decode windows, and
host drains.  After every run the launcher prints the engine's
serve-mode NVM verdicts: SRAM vs STT/SOT-MRAM energy/EDP on the measured
decode-tick and prefill traffic — family-tagged shapes (DESIGN.md §17),
with ssm/hybrid recurrent-bank traffic scored under its write-heavier
read split.  ``--list-configs`` prints every registry arch with its
family and which engines (dense/paged) can serve it, then exits.

Resilience plumbing (DESIGN.md §16): ``--deadline-ticks`` gives every
arrival-driven request an absolute deadline and ``--max-queue-depth``
caps the admission queue (excess submissions shed).  Every run prints a
terminal-state histogram next to the paged-stats line, and ``--strict``
(default on) exits non-zero if any request ended FAILED or never
reached a terminal state, or if the watchdog degraded a failing
compiled decode window to the eager path — the CI smokes lean on that
exit code.

The parameters are sharded over a (data, model) mesh of every device the
backend exposes (on a four-chip host, model=4).  ``parse_args``,
``build_engine``, ``make_requests``, ``serve`` and ``report`` are the
steps of ``main``; ``chip_smoke.py`` calls them in-process.
"""
import argparse
import collections
import time

import jax

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_context
from repro.models import build_model
from repro.serve import (FAILED, Engine, PagedEngine, ShedPolicy, Tracer,
                         latency_summary, mixed_requests, poisson_requests,
                         run_arrivals, run_staggered,
                         shared_prefix_requests, staggered_groups)
from repro.sharding import default_rules, tree_shardings
from repro.train.elastic import remesh


def _print_latency(summary: dict) -> None:
    print(f"latency over {summary['completed']}/{summary['n']} requests "
          f"({summary['tokens']} tokens):")
    for domain, unit, scale in (("ticks", "t", 1.0), ("wall", "ms", 1e3)):
        for metric, stats in sorted(summary[domain].items()):
            line = " ".join(f"{k} {v * scale:.2f}{unit}"
                            for k, v in stats.items() if k != "max")
            print(f"  {domain:5s} {metric:7s} {line}")


def _terminal_report(eng, reqs, strict: bool) -> None:
    """Terminal-state histogram + strict-mode exit code: FAILED or
    non-terminal requests, and a decode window the watchdog degraded to
    the eager path, are a launcher failure; shed/timed-out are legitimate
    admission-control outcomes (reported, not fatal)."""
    hist = collections.Counter(r.state for r in reqs)
    rs = eng.resilience_stats()
    extras = {k: v for k, v in rs.items()
              if v and k not in ("shed", "timed_out", "failed")}
    print(f"terminal states: "
          + " ".join(f"{k}={v}" for k, v in sorted(hist.items()))
          + (f"  resilience: {extras}" if extras else ""))
    stuck = [r.uid for r in reqs if not r.terminal]
    failed = [r.uid for r in reqs if r.state == FAILED]
    if strict and (stuck or failed):
        raise SystemExit(
            f"strict mode: {len(stuck)} non-terminal {stuck[:8]} / "
            f"{len(failed)} FAILED {failed[:8]} requests "
            f"(states: {dict(hist)})")
    if strict and (rs["degraded"] or rs["window_fallbacks"]):
        raise SystemExit(
            f"strict mode: the compiled decode window failed and "
            f"{rs['window_fallbacks']} window(s) ran on the eager path "
            f"after {rs['window_retries']} retries")


def _list_configs() -> None:
    """Registry listing with per-engine serve capability (serve_modes):
    which engines — Engine/EngineReference ("dense") and/or PagedEngine
    ("paged") — accept each config."""
    from repro.configs import all_configs
    from repro.models.api import _FAMILY_SERVE_MODES
    print(f"{'arch':<22} {'family':<8} engines")
    for arch, cfg in all_configs().items():
        modes = _FAMILY_SERVE_MODES[cfg.family]
        engines = ["Engine", "EngineReference"] if "dense" in modes else []
        if "paged" in modes:
            engines.append("PagedEngine")
        print(f"{arch:<22} {cfg.family:<8} {', '.join(engines)}")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--list-configs", action="store_true",
                    help="print every registry config with its family and "
                         "the serve engines that accept it, then exit")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", type=int, nargs=2, default=None,
                    metavar=("LO", "HI"),
                    help="prompt length range of the mixed and arrival "
                         "workloads (default 2 .. max_len/4)")
    ap.add_argument("--max-new", type=int, nargs=2, default=None,
                    metavar=("LO", "HI"),
                    help="new-token budget range of every workload "
                         "(default 2 .. max_len/8; arrivals 1 .. max_len/8)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-sized config (--no-reduced for full size)")
    ap.add_argument("--ticks-per-sync", type=int, default=8,
                    help="fused decode ticks per host drain (K)")
    ap.add_argument("--attn-impl",
                    choices=("xla", "pallas_decode", "paged",
                             "pallas_paged"),
                    default="xla",
                    help="decode-tick attention: jnp full-cache path (the "
                         "parity oracle), the Pallas blocked kernel with "
                         "fused KV scatter, the paged-KV jnp gather path, "
                         "or the Pallas paged kernel with scalar-prefetch "
                         "page tables (interpret mode on CPU); 'paged'/"
                         "'pallas_paged' run the PagedEngine with "
                         "radix-tree prefix sharing (DESIGN.md §15)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (paged engine only)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical page-pool size (paged engine only; "
                         "default slots * max_len / page_size)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="serve the shared-prefix template workload and "
                         "FAIL unless the paged engine actually shares "
                         "prefix pages (zero prefix hits = regression)")
    ap.add_argument("--sample-impl", choices=("xla", "pallas"),
                    default="xla",
                    help="token sampling: two-step XLA path or the fused "
                         "one-launch Pallas kernel")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every 2nd request "
                         "(0 = all greedy)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean Poisson arrivals per decode tick; > 0 "
                         "switches to arrival-driven traffic with "
                         "heavy-tailed lengths and SLO latency output")
    ap.add_argument("--burst-amp", type=float, default=0.0,
                    help="sinusoidal burst modulation amplitude in [0, 1] "
                         "for the arrival rate")
    ap.add_argument("--burst-period", type=float, default=64.0,
                    help="burst modulation period in ticks")
    ap.add_argument("--trace-out", default=None,
                    help="write a chrome://tracing JSON of engine windows "
                         "(prefill / decode / host drain) to this path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verdicts", action=argparse.BooleanOptionalAction,
                    default=True, help="print serve-mode NVM verdicts")
    ap.add_argument("--deadline-ticks", type=float, default=None,
                    help="per-request deadline in ticks past arrival "
                         "(arrival-driven runs only); overdue work is "
                         "shed or timed out instead of served late")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission queue cap: submissions beyond it are "
                         "shed (backpressure instead of unbounded queue)")
    ap.add_argument("--strict", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="exit non-zero if any request ends FAILED or "
                         "non-terminal, or a decode window degraded to "
                         "the eager path (--no-strict to just report)")
    return ap.parse_args(argv)


def build_engine(args, devices=None):
    """(mesh, engine) the arguments describe.  Parameters are drawn
    directly into their shardings over a (data, model) mesh of
    ``devices`` (default: every device of the backend)."""
    devices = list(jax.devices() if devices is None else devices)
    mesh = remesh(len(devices), devices)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg, max_seq=args.max_len)
    rules = default_rules(fsdp=False)  # serving: params over model axis only

    tracer = Tracer(name=f"serve-{args.arch}") if args.trace_out else None
    with mesh_context(mesh):
        p_sh = tree_shardings(model.param_axes(), model.abstract_params(),
                              mesh, rules)
        params = model.init(jax.random.PRNGKey(0), p_sh)
        paged = args.attn_impl in ("paged", "pallas_paged")
        policy = ShedPolicy(max_queue_depth=args.max_queue_depth)
        if paged:
            eng = PagedEngine(
                model, params, slots=args.slots, max_len=args.max_len,
                page_size=args.page_size, num_pages=args.num_pages,
                seed=args.seed, ticks_per_sync=args.ticks_per_sync,
                record_traffic=args.verdicts, sample_impl=args.sample_impl,
                attn_impl=("pallas_paged" if args.attn_impl == "pallas_paged"
                           else "xla"), tracer=tracer, shed_policy=policy)
        elif args.shared_prefix:
            raise SystemExit("--shared-prefix requires a paged engine "
                             "(--attn-impl paged or pallas_paged)")
        else:
            eng = Engine(model, params, slots=args.slots,
                         max_len=args.max_len, seed=args.seed,
                         ticks_per_sync=args.ticks_per_sync,
                         record_traffic=args.verdicts,
                         sample_impl=args.sample_impl,
                         attn_impl=args.attn_impl, tracer=tracer,
                         shed_policy=policy)
    return mesh, eng


def make_requests(args, vocab: int) -> list:
    """The workload the arguments select: shared-prefix templates,
    Poisson arrivals, or staggered mixed-length requests."""
    temp_every = 2 if args.temperature > 0 else 0
    hi_new = max(2, args.max_len // 8)
    if args.shared_prefix:
        # template length deliberately off the page grid so boundary
        # CoW copies exercise on every admission wave
        tlen = max(args.page_size + args.page_size // 2,
                   args.max_len // 2 - args.page_size // 2)
        return shared_prefix_requests(
            args.requests, seed=args.seed, vocab=vocab,
            template_len=min(tlen, args.max_len - 10),
            suffix_lens=(2, 8), max_new=tuple(args.max_new or (2, hi_new)),
            temperature=args.temperature, temperature_every=temp_every)
    prompt_lens = tuple(args.prompt_lens or (2, max(2, args.max_len // 4)))
    if args.arrival_rate > 0:
        return poisson_requests(
            args.requests, seed=args.seed, vocab=vocab,
            arrival_rate=args.arrival_rate, burst_amp=args.burst_amp,
            burst_period=args.burst_period, prompt_bounds=prompt_lens,
            new_bounds=tuple(args.max_new or (1, hi_new)),
            temperature=args.temperature, temperature_every=temp_every,
            deadline_ticks=args.deadline_ticks)
    return mixed_requests(
        args.requests, seed=args.seed, vocab=vocab, prompt_lens=prompt_lens,
        max_new=tuple(args.max_new or (2, hi_new)),
        temperature=args.temperature, temperature_every=temp_every)


def serve(eng, args, reqs):
    """Run ``reqs`` to completion; returns (outputs by uid, seconds on
    the blocking clock)."""
    t0 = time.time()
    if args.arrival_rate > 0 and not args.shared_prefix:
        outputs = run_arrivals(eng, reqs)
    else:
        outputs = run_staggered(eng, staggered_groups(reqs, args.slots))
    jax.block_until_ready(eng.cache)   # timings are blocking-clock
    return outputs, time.time() - t0


def report(eng, args, reqs, outputs, seconds, mesh) -> None:
    """Print the run's summary lines; raises SystemExit where the
    workload's own checks fail (strict mode, prefix sharing, latency)."""
    ntok = sum(len(o) for o in outputs.values())
    print(f"served {args.requests} requests / {ntok} tokens in "
          f"{eng.ticks} ticks (K={args.ticks_per_sync}, "
          f"attn={args.attn_impl}) = {ntok / seconds:.0f} tok/s on "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if isinstance(eng, PagedEngine):
        st = eng.paged_stats()
        print(f"paged KV: pages-in-use high-water {st['pages_hwm']}"
              f"/{eng.num_pages} (page_size={eng.page_size}), "
              f"prefix-hit rate {st['prefix_hit_rate']:.2f} "
              f"({st['prefix_tokens']}/{st['prompt_tokens']} prompt "
              f"tokens), CoW copies {st['cow_copies']}, "
              f"radix nodes {st['radix_nodes']}, "
              f"deferred {st['deferred']}, evicted {st['evicted_pages']}")
        if args.shared_prefix and st["prefix_tokens"] == 0:
            raise SystemExit(
                "shared-prefix workload produced ZERO prefix hits — "
                "radix-tree sharing is broken")
    _terminal_report(eng, reqs, args.strict)
    if args.arrival_rate > 0 and not args.shared_prefix:
        summary = latency_summary(reqs)
        _print_latency(summary)
        # with admission control engaged (deadlines or a queue cap),
        # shed / timed-out outcomes are legitimate — all-terminal is
        # enforced by _terminal_report; without it, anything short of
        # full completion is a regression
        shedding = (args.deadline_ticks is not None
                    or args.max_queue_depth is not None)
        complete = (summary["completed"] == args.requests
                    or (shedding and summary["completed"] > 0))
        if not complete or not summary["wall"] or not summary["ticks"]:
            raise SystemExit(
                f"latency percentiles empty or incomplete: "
                f"{summary['completed']}/{args.requests} requests finished")
    if eng.tracer is not None:
        path = eng.tracer.save(args.trace_out)
        print(f"chrome trace "
              f"({len(eng.tracer.to_chrome_trace()['traceEvents'])}"
              f" events) -> {path}")
    if args.verdicts:
        for v in eng.nvm_verdicts():
            print(f"  {v.shape}: energy vs SRAM "
                  f"STT {v.energy_ratio['STT']:.3f} / "
                  f"SOT {v.energy_ratio['SOT']:.3f}   EDP "
                  f"STT {v.edp_ratio['STT']:.3f} / "
                  f"SOT {v.edp_ratio['SOT']:.3f}")


def main(argv=None):
    args = parse_args(argv)
    if args.list_configs:
        _list_configs()
        return
    enable_compile_cache()
    mesh, eng = build_engine(args)
    reqs = make_requests(args, eng.model.cfg.vocab_size)
    with mesh_context(mesh):
        outputs, seconds = serve(eng, args, reqs)
    report(eng, args, reqs, outputs, seconds, mesh)


if __name__ == "__main__":
    main()
