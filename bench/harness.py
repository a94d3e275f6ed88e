"""One run of one benchmark cell, driven by data.

``load_spec`` resolves a cell by its name in ``BENCHMARK.json``: the
cell's file ``bench/workloads/<cell>.json`` (engine options, check), its
configuration's file (named in ``BENCHMARK.json``), its traffic mix
``bench/traffic/<traffic>.json`` with the generator the mix names
(``bench/traffic/<generator>.py``), and the metrics that apply to it,
each read by ``bench/metrics/<name>.py`` (or, for a dotted name such as
``decode_tick_ms.chat``, by ``<name>.py`` else ``decode_tick_ms.py``).
Adding a cell, configuration, mix, generator or metric adds files and
entries; nothing here names one.

``run`` builds the program's paged engine with seeded weights, warms
every shape the cell's traffic can ask for, drives the traffic for
``seconds`` with the clock open (traced, with the profiler on for the
whole window), lets the generator finish what the window answers for,
reads the device's peak memory, frees the program's state and checks a
sample of the served tokens against the float32 reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, List, Optional

ROOT = Path(__file__).resolve().parents[1]
CLOCK_MARKER = "bench_clock"
IDLE_SLEEP_S = 0.002


class NoChipError(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Spec:
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    cell: dict           # bench/workloads/<cell>.json
    conf: dict           # the configuration's file
    mix: dict            # bench/traffic/<traffic>.json
    traffic: type        # the Generator class the mix names
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def load_spec(cell: str, root: Path = ROOT) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if cell not in entries:
        raise KeyError(f"no cell {cell!r} in BENCHMARK.json "
                       f"(cells: {sorted(entries)})")
    entry = entries[cell]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[entry["config"]]["file"]).read_text())
    cellfile = json.loads(
        (root / "bench" / "workloads" / f"{cell}.json").read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    from bench.generator import load
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, cell, names)]
    return Spec(cell, entry, cellfile, conf, mix, load(mix, root), e2e,
                per_layer, root)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    d = root / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = d / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in {d}")


# ---------------------------------------------------------------------------
# the record a run leaves for the metric readers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    tokens: int = 0          # tokens that reached the host in this step
    decode_rows: int = 0     # (row, tick) pairs that decoded a token
    ctx_sum: int = 0         # keys attended, summed over those pairs
    pages_sum: int = 0       # live KV pages read, summed over those pairs


@dataclasses.dataclass
class Record:
    """Everything a metric reader may read; one per run."""
    spec: Spec
    model: dict                          # the configuration's sizes
    page_size: int
    ticks_per_sync: int
    peaks: dict
    t_open: float
    t_close: float
    setup_s: float
    compile_setup_s: float
    compile_window_s: float
    due: List[tuple]                     # (Pending, due_abs) counted
    steps: List[StepRecord]
    spans: List[dict]                    # the program's spans, window only
    stats_open: dict
    stats_close: dict
    memory_peak_bytes: Optional[int]
    trace: Optional[dict] = None         # trace_reduce.reduce(...) output

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def percentile(xs, q: float) -> float:
    """Linear-interpolation percentile (numpy's default method); an
    infinite entry (a request that never finished) sorts last."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        raise ValueError("percentile of an empty sequence")
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) and rank > lo:
        return math.inf
    return xs[lo] + (rank - lo) * (xs[hi] - xs[lo]) if xs[lo] != xs[hi] \
        else xs[lo]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds of JAX compile events (trace, lower, compile)."""

    def __init__(self):
        self.seconds = 0.0
        from jax import monitoring

        def listen(event, duration, **_):
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration

        self._listen = listen
        monitoring.register_event_duration_secs_listener(listen)

    def close(self):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listen)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and (d0.platform != "tpu" or len(devs) < chips):
        raise NoChipError(
            f"the cell needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{d0.platform} device(s)")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _annotation(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Feeder:
    """Feeds the generator's requests to the engine and keeps the books."""

    def __init__(self, eng, gen, t0: float):
        self.eng, self.gen, self.t0 = eng, gen, t0
        self.inflight: list = []
        self.done: list = []          # (Pending, seen_abs)
        self.submitted: list = []     # (Pending, due_abs)
        self.steps: List[StepRecord] = []
        self._finished_new: list = []

    def has_work(self) -> bool:
        return bool(self.eng._queue) or any(
            r is not None for r in self.eng.slot_req)

    def poll(self):
        now = time.perf_counter()
        fin = [(p, t - self.t0) for p, t in self._finished_new]
        self._finished_new = []
        new = self.gen.poll(now - self.t0, fin)
        if new:
            with _annotation("submit"):
                for p in new:
                    self.eng.submit(p.req)
                    t = p.submitted = time.perf_counter()
                    self.submitted.append((p, self.t0 + p.due))
                    self.inflight.append(p)
                    if p.req.terminal:          # refused at submit
                        self._finish(p, t)

    def _finish(self, p, seen):
        p.done_seen = seen
        self.done.append((p, seen))
        self._finished_new.append((p, seen))

    def step(self):
        eng = self.eng
        before = {id(p): len(p.req.output) for p in self.inflight}
        t0 = time.perf_counter()
        with _annotation("step"):
            eng.step()
        t1 = time.perf_counter()
        rec = StepRecord(t0, t1)
        ps = eng.page_size
        still = []
        for p in self.inflight:
            r = p.req
            j0, j1 = before.get(id(p), 0), len(r.output)
            rec.tokens += max(0, j1 - j0)
            if j0 == 0 and j1 > 0:
                p.first_seen = t1
            # decode ticks produced outputs j in [max(j0,1), j1); output j
            # is decoded at position L + j - 1, attending L + j keys
            L = len(r.prompt)
            for j in range(max(j0, 1), j1):
                ctx = L + j
                rec.decode_rows += 1
                rec.ctx_sum += ctx
                rec.pages_sum += -(-ctx // ps)
            if r.terminal:
                self._finish(p, t1)
            else:
                still.append(p)
        self.inflight = still
        self.steps.append(rec)

    def idle(self):
        nxt = self.gen.next_due()
        now = time.perf_counter() - self.t0
        wait = IDLE_SLEEP_S if nxt is None else min(max(nxt - now, 0.0),
                                                     0.05)
        if wait > 0:
            with _annotation("wait"):
                time.sleep(wait)

    def turn(self):
        self.poll()
        if self.has_work():
            self.step()
        else:
            self.idle()


def _engine(spec: Spec, model, params, seed: int, tracer=None):
    from repro.serve import PagedEngine
    e = spec.cell["engine"]
    return PagedEngine(
        model, params, slots=e["slots"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"], eos_id=None,
        seed=seed & 0x7FFFFFFF, ticks_per_sync=e["ticks_per_sync"],
        record_traffic=False, attn_impl=e["attn_impl"],
        sample_impl=e["sample_impl"], tracer=tracer)


def warm(eng, gen, spec: Spec) -> None:
    """Compile every program the cell's traffic can run: each power-of-two
    prefill width, the decode window, and the copy-on-write of 1..slots
    boundary pages; then empty the engine."""
    import jax.numpy as jnp
    from bench.generator import prefill_buckets
    from repro.serve import Request
    e = spec.cell["engine"]
    max_len, ps = e["max_len"], e["page_size"]
    for S in prefill_buckets(*gen.prefill_bounds(), max_len):
        n = min(S, max_len - 2)
        r = Request(uid=-S, prompt=gen.tokens(n), max_new_tokens=2)
        eng.submit(r)
        while not r.terminal:
            eng.step()
    for n in range(1, e["slots"] + 1):
        # as the engine calls it: index tuples made device arrays
        idx = (eng.trash,) * n
        eng.cache = eng._cow_jit(eng.cache, jnp.asarray(idx, jnp.int32),
                                 jnp.asarray(idx, jnp.int32))
    eng.cache = None          # free the pool before reset draws a new one
    eng.reset()
    if max_len % ps:
        raise ValueError("max_len must be a multiple of page_size")


def run(spec: Spec, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, peaks: Optional[dict] = None, log=print,
        t_process: float = None) -> dict:
    """One run; returns the result line as a dict.  ``peaks`` defaults to
    the table's entry for the device's kind; the tests pass their own and
    ``require_tpu=False`` to run a tiny cell on the CPU."""
    t_process = time.perf_counter() if t_process is None else t_process
    import jax
    from bench import check as checker
    from bench import costs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import Request, Tracer

    dev = device_info(spec.entry["chips"], require_tpu)
    pk = costs.peaks(dev["kind"]) if peaks is None else peaks
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock()
    e = spec.cell["engine"]
    adapter = importlib.import_module(
        f"bench.adapters.{spec.conf['adapter']}")
    model = adapter.build_model(spec.conf, e["max_len"])
    params = adapter.draw_params(spec.conf, model, seed)
    eng = _engine(spec, model, params, seed)
    mix = spec.mix
    gen = spec.traffic(mix, seed=seed, vocab=model.cfg.vocab_size,
                       max_len=e["max_len"], slots=e["slots"],
                       horizon_s=float(mix.get("preroll_s", 0.0)) + seconds
                       + float(mix.get("drain_s", 0.0)),
                       request_cls=Request)
    warm(eng, gen, spec)
    tracer = Tracer(name=spec.name) if trace else None
    eng.tracer = tracer
    drv = Feeder(eng, gen, time.perf_counter())
    gen.before_window(drv)
    compile_setup = clock.seconds

    # the window; traced, the profiler covers all of it
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
        with _annotation(CLOCK_MARKER):
            t_sync = time.perf_counter()
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    stats_open = dict(eng.paged_stats())
    n_steps0 = len(drv.steps)
    while time.perf_counter() - t_open < seconds:
        drv.turn()
    t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compile_window = clock.seconds - compile_setup
    stats_close = dict(eng.paged_stats())
    window_steps = drv.steps[n_steps0:]

    # what the window answers for finishes; late is late, not wrong
    gen.after_window(drv, t_open, t_close)
    due = gen.counted(drv, t_open, t_close)
    spans = tracer_spans(tracer, t_open, t_close) if tracer else []
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = mem.get("peak_bytes_in_use")
    if trace:
        log_memory_analysis(eng, log)
    resilience = eng.resilience_stats()
    clock.close()

    rec = Record(spec=spec, model=spec.conf["model"],
                 page_size=e["page_size"], ticks_per_sync=e["ticks_per_sync"],
                 peaks=pk, t_open=t_open, t_close=t_close, setup_s=setup_s,
                 compile_setup_s=compile_setup,
                 compile_window_s=compile_window, due=due,
                 steps=window_steps, spans=spans, stats_open=stats_open,
                 stats_close=stats_close, memory_peak_bytes=memory_peak)
    if trace:
        rec.trace = reduce_trace(trace_dir, t_sync, t_open, t_close,
                                 spans_all(tracer))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # attempted: what the window answers for; failed: not served to the
    # end (shed, timed out, failed, or unfinished after the drain)
    finished = [p.req for p, _ in due if p.req.state == "DONE"]
    attempted, failed = len(due), len(due) - len(finished)

    # free the program's state before the reference runs
    eng.cache = None
    eng.params = None
    del params
    gc.collect()
    check = checker.run(spec, seed, finished, log=log)

    metrics = {}
    for m in spec.per_layer if trace else spec.end_to_end:
        v = metric_reader(m["name"], spec.root)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=memory_peak)
    out = {"correct": check["correct"], "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    if compile_window:
        log(f"warning: {compile_window:.3f} s of compilation inside the "
            "window", file=sys.stderr)
    log(f"run: setup {setup_s:.3f} s (compile {compile_setup:.3f} s), window "
        f"{rec.window_s:.3f} s, {len(window_steps)} steps, attempted "
        f"{attempted}, failed {failed}, resilience {resilience}",
        file=sys.stderr)
    for k, v in check["numbers"].items():
        log(f"check: {k} {v['value']!r} limit {v['limit']!r}",
            file=sys.stderr)
    out["check"] = check["numbers"]
    return out


def tracer_spans(tracer, lo, hi) -> List[dict]:
    return [s for s in spans_all(tracer) if lo <= s["t0"] and s["t1"] <= hi]


def spans_all(tracer) -> List[dict]:
    return [dict(name=s["name"], cat=s["cat"], t0=s["t0"], t1=s["t1"],
                 args=s.get("args") or {}) for s in tracer._spans]


def log_memory_analysis(eng, log) -> None:
    """The compiler's memory plan of the decode window, beside the
    device's own peak (compiled again from the persistent cache)."""
    import jax.numpy as jnp
    try:
        args = (eng.params, eng.cache, eng._state, eng.key,
                jnp.zeros(eng.slots, bool), *eng._extra_window_args())
        ma = eng._window_jit.lower(*args).compile().memory_analysis()
        log(f"memory_analysis decode window: arguments "
            f"{ma.argument_size_in_bytes} temporaries "
            f"{ma.temp_size_in_bytes} outputs {ma.output_size_in_bytes} "
            f"aliased {ma.alias_size_in_bytes}", file=sys.stderr)
    except Exception as err:      # the analysis is information only
        log(f"memory_analysis unavailable: {err!r}", file=sys.stderr)


def reduce_trace(trace_dir, t_sync, t_lo, t_hi, spans) -> dict:
    """The trace's device numbers over [t_lo, t_hi] (perf_counter s),
    with idle gaps labelled by the harness's and the program's spans."""
    from bench import trace_reduce as tr
    pd = tr.load(tr.find_xplane(trace_dir))
    off = tr.clock_offset_ns(pd, CLOCK_MARKER, t_sync)
    host = tr.host_spans(pd, ("step", "submit", "wait"))
    names = {"prefill": "prefill", "decode": "decode_window",
             "host": "host_drain"}
    prog = [(names.get(s["cat"], s["cat"]), s["t0"] * 1e9 + off,
             s["t1"] * 1e9 + off) for s in spans]
    out = tr.reduce(pd, t_lo * 1e9 + off, t_hi * 1e9 + off,
                    spans=host + prog)
    out["offset_ns"] = off
    return out
