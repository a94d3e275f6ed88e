"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files under ``bench/`` (see harness.py).  The
last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), its last key ``check`` holding each number compared
beside its limit; the same numbers end standard error.  With no TPU, or
fewer chips than the cell needs, it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

NO_CHIP = 3


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a, **kw):
    print(*a, **kw, flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        from bench import harness
    except ImportError as err:
        print(f"cannot load the benchmark or the program: {err}",
              file=sys.stderr)
        return 2
    spec = harness.load_spec(args.workload)
    try:
        out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                          log=log, t_process=T_START)
    except harness.NoChipError as err:
        print(f"no chip: {err}", file=sys.stderr)
        return NO_CHIP
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
