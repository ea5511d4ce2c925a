"""Runs of one cell with a fault planted under the timed path (see
``chipbench/faults.py``), one run per seed in this one process:

    python3 benchmarks/chip/control.py --workload suite5x5-sweep.fresh \
        --fault control --seconds 10 --seeds 11,12,13

Each run prints its result line as ``run.py`` does; the benchmark's own
runs never plant a fault. A line ``control <seed> correct=<bool>`` per
seed closes the output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from chipbench import faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=faults.FAULTS, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(run.ROOT / "src"))
    verdicts = []
    for seed in (int(s) for s in args.seeds.split(",")):
        undo = faults.plant(args.fault)
        try:
            out = run.run_cell(run.parse(
                ["--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", "0"]))
        finally:
            undo()
        print(json.dumps(out), flush=True)
        verdicts.append((seed, out["correct"], out["checks"]))
    for seed, ok, checks in verdicts:
        print(f"control {seed} correct={ok} "
              f"{json.dumps({k: v['value'] for k, v in checks.items()})}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
