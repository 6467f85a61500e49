"""Benchmark entrypoint: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement).

    PYTHONPATH=src python -m benchmarks.run [--only SECTION]
"""
from __future__ import annotations

import argparse
import sys
import traceback


SECTIONS = [
    "table1_overheads",      # paper Table 1
    "fig4b_kappa_privacy",   # paper Fig. 4(b)
    "security_table",        # paper §4.2
    "augconv_equivalence",   # paper §4.4 experiment (CPU-scaled)
    "kernel_bench",          # Pallas kernel structure/μbench
    "engine_throughput",     # delivery engine: batched multi-tenant serving
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=SECTIONS)
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    failures = 0
    for sec in SECTIONS if args.only is None else [args.only]:
        try:
            mod = __import__(f"benchmarks.{sec}", fromlist=["run"])
            mod.run()
        except Exception:
            failures += 1
            print(f"{sec},0.0,FAILED")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
