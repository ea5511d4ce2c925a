"""Master benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Default is the quick profile (a representative subset, minutes on 1 CPU
core); --full reproduces every benchmark x CGRA size cell with the paper's
budgets. CSV rows are ``name,us_per_call,derived``-style per section.
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from . import fig6_ii, kernel_bench, table_time

    print("# === Fig. 6: II comparison (SAT-MapIt vs heuristic SoA) ===")
    fig6_ii.main(quick=quick)
    print()
    print("# === Tables I-IV: mapping time ===")
    table_time.main(quick=quick)
    print()
    print("# === Kernel / solver microbenchmarks ===")
    kernel_bench.main()
    print()
    print("# === Roofline (from dry-run artifacts, if present) ===")
    for path in ("results/dryrun_final.jsonl", "results/dryrun.jsonl"):
        if os.path.exists(path):
            from . import roofline_report
            rows = roofline_report.load(path)
            print(roofline_report.roofline_table(rows))
            break
    else:
        print("no dry-run results found — run: "
              "PYTHONPATH=src python -m repro.launch.dryrun --all")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
