"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module for the
paper-target comparison packed into the derived column).
Run: PYTHONPATH=src python -m benchmarks.run [--only fig7]
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = [
    "table1_bitcell",
    "table2_cache",
    "fig3_rw_ratio",
    "fig4_5_isocap",
    "fig6_batch",
    "fig7_dram",
    "fig8_9_isoarea",
    "fig10_ppa",
    "fig11_13_scalability",
    "crosslayer_tpu",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            mod.run()
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
