"""Readings that set a cell's check limit and its knee, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 12 [--control fp8,bf16] [--qk-scale 2]
    python3 bench/calibrate.py --workload <cell> --seeds 7 --seconds 30 --rates 0.3,0.4,0.5

Each seed (or rate) is one whole run of the cell, as ``bench/run.py``
makes it (a new engine, its programs from the compile cache), with the
window shortened to ``--seconds``; this tool only watches the run from
outside.  Without ``--rates`` it prints, per seed, the program's widest
logit gap and, with ``--control``, the gap of the tokens that the
reference computed with those operands (``fp8``: the control; ``bf16``:
a witness) puts first at the same positions.  ``--qk-scale`` multiplies
the spread of the query and key weights, in the program and the
reference alike.  With ``--rates`` it overrides the open-loop mix's
session rate and prints the end-to-end metrics and the backlog of each:
the knee is the highest rate whose backlog does not grow over the
window.  Benchmark runs never run this file.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import argparse  # noqa: E402


def _scale_qk(weights, factor):
    orig = weights.layer_specs

    def layer_specs(m):
        s = dict(orig(m))
        for k in ("wq", "wk"):
            shape, kind, std = s[k]
            s[k] = (shape, kind, std * factor)
        return s

    weights.layer_specs = layer_specs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--qk-scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    from bench import check, harness, weights
    if args.qk_scale != 1.0:
        _scale_qk(weights, args.qk_scale)
    spec = harness.load_spec(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    controls = [q for q in args.control.split(",") if q]
    runs = [(s, None) for s in seeds] if not rates else \
        [(seeds[0] + i, r) for i, r in enumerate(rates)]

    seen = {}
    run_check = check.run

    def watched_check(*a, **kw):
        seen["check"] = run_check(*a, **kw)
        return seen["check"]

    class Watched(harness.Feeder):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.backlog = []
            seen["feeder"] = self

        def step(self):
            super().step()
            self.backlog.append((time.perf_counter(), len(self.eng._queue)))

    check.run = watched_check
    harness.Feeder = Watched
    quiet = lambda *a, **k: None
    e = spec.cell["engine"]
    for seed, rate in runs:
        if rate is not None:
            spec.mix["sessions_per_s"] = rate
        t0 = time.perf_counter()
        out = harness.run(spec, seed, args.seconds, False, log=quiet)
        row = {"seed": seed, "rate": rate, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               "gap": out["check"]["widest_logit_gap"]["value"],
               "tokens_compared": out["check"]["tokens_compared"]["value"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "peak": out["device"]["memory_peak_bytes"]}
        sample = seen["check"]["sample"]
        for q in controls if sample and rate is None else ():
            g = check.control_gaps(spec.conf, seed, sample,
                                   spec.cell["check"]["sample_requests"],
                                   e["max_len"], quant=q)
            row[f"{q}_gap"] = float(g.max())
            row[f"{q}_off_argmax"] = float((g > 0).mean())
        row["off_argmax"] = float((seen["check"]["gaps"] > 0).mean())
        if rate is not None:
            drv = seen["feeder"]
            t_open = drv.t0 + float(spec.mix.get("preroll_s", 0.0))
            q = [n for t, n in drv.backlog
                 if t_open <= t <= t_open + args.seconds]
            third = max(1, len(q) // 3)
            row["backlog_first_third"] = max(q[:third] or [0])
            row["backlog_last_third"] = max(q[-third:] or [0])
            row["backlog_end"] = q[-1] if q else 0
        row["wall_s"] = time.perf_counter() - t0
        print("CALIB " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
