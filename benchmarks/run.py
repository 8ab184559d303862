"""Benchmark driver: one section per paper table/figure + the beyond-paper
studies.  ``python -m benchmarks.run`` (add --quick for a fast smoke pass,
--full for the paper-exact 100-case MIP runs at 80 GPUs).

Sections:
  [1] Fig 9  initial deployment    (placement_bench)
  [2] Fig 10 compaction            (placement_bench)
  [3] Fig 11 reconfiguration       (placement_bench)
  [4] solver scaling               (beyond paper)
  [5] kernel micro-bench           (serving substrate)
  [6] roofline table               (from dry-run artifacts)
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache

from . import kernel_bench, roofline, solver_scaling
from .placement_bench import print_table, run_case


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small smoke pass")
    ap.add_argument("--full", action="store_true",
                    help="paper-exact: 100 MIP cases at 80 GPUs, 30s cap")
    args = ap.parse_args()

    if args.quick:
        cases8, cases80, mip80, tl8, tl80 = 10, 10, 2, 5.0, 10.0
    elif args.full:
        cases8, cases80, mip80, tl8, tl80 = 100, 100, 100, 30.0, 30.0
    else:
        cases8, cases80, mip80, tl8, tl80 = 100, 100, 8, 10.0, 30.0

    t00 = time.time()
    for i, case in enumerate(("initial", "compaction", "reconfiguration"), 1):
        print(f"\n######## [{i}] paper Fig {8 + i}: {case} ########")
        t0 = time.time()
        table = run_case(case, 8, cases8, tl8)
        print_table(case, 8, table)
        print(f"   ({time.time() - t0:.0f}s, {cases8} cases, MIP cap {tl8}s)")
        t0 = time.time()
        table = run_case(case, 80, cases80, tl80, mip_cases=mip80)
        print_table(case, 80, table)
        print(f"   ({time.time() - t0:.0f}s, {cases80} cases "
              f"[MIP on first {mip80}], MIP cap {tl80}s)")

    print("\n######## [4] solver scaling (beyond paper) ########")
    import sys

    argv = sys.argv
    sys.argv = ["solver_scaling", "--sizes", "8", "16", "32",
                "--seeds", "2", "--time-limit", "10"]
    if args.full:
        sys.argv += ["80"]
    try:
        solver_scaling.main()
    finally:
        sys.argv = argv

    print("\n######## [5] kernel micro-bench ########")
    kernel_bench.main()

    print("\n######## [6] roofline table (dry-run artifacts) ########")
    cells = roofline.load_cells()
    roofline.print_report(cells)

    print(f"\ntotal: {time.time() - t00:.0f}s")


if __name__ == "__main__":
    enable_compile_cache()
    main()
