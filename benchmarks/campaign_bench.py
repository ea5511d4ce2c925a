"""Mapping-campaign benchmark: the data flywheel end to end.

Thin driver over :func:`repro.launch.campaign.run` — corpus generation
(seeded grammar + mutants, isomorphism dedup), (DFG x fabric) cells fanned
through the :class:`~repro.core.workers.WorkerPool`, sharded dataset
append, guide training, and the soundness/efficiency gates — reported as
``BENCH_campaign.json``:

  * ``campaign.cells_per_sec`` — cells through the pool per second;
  * ``dedup_rate`` — fraction of generated DFGs collapsed by canonical-
    form dedup;
  * ``guide.hit1`` / ``guide.hit2`` — held-out predictor accuracy vs the
    ``guide.baseline_hit1`` always-start-at-MII baseline;
  * ``eval.attempts_saved`` — solver attempts the guided sweep avoided on
    held-out cells (guided vs unguided at the same ``sweep_width``);
  * ``suite_gate`` — guided final II == unguided final II on every suite
    cell (the soundness contract).

``--check`` gates (see :func:`repro.launch.campaign.check_gates`):
>= 200 cells mapped, dedup > 0, dataset round-trips, guided attempts <
unguided attempts, zero II mismatches anywhere.

    PYTHONPATH=src python benchmarks/campaign_bench.py --quick --check
"""
from __future__ import annotations

import argparse
import json
import tempfile

from repro.launch.campaign import check_gates, run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (~250 cells, 2 workers)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless every gate passes")
    ap.add_argument("--out", default="BENCH_campaign.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="keep campaign artifacts (dataset shards, store, "
                         "guide.npz) in DIR instead of a temp directory")
    args = ap.parse_args()

    if args.quick:
        knobs = dict(workers=2, n_random=64, n_mutants=40,
                     fabrics="2x2,3x3,4x4", eval_cells=40)
    else:
        knobs = dict(workers=None, n_random=256, n_mutants=128,
                     fabrics="2x2,3x3,4x4,3x3-torus,4x4-onehop,"
                             "4x4:mem2,4x4-torus:r8",
                     eval_cells=96)

    def go(outdir: str):
        return run(seed=args.seed, out=outdir, compact=True, **knobs)

    if args.keep:
        summary = go(args.keep)
    else:
        with tempfile.TemporaryDirectory() as d:
            summary = go(d)

    with open(args.out, "w") as f:
        json.dump(summary, f, indent=2, default=str)
    print(f"wrote {args.out}")
    if args.check:
        errs = check_gates(summary)
        if errs:
            raise SystemExit("campaign_bench --check failed: " +
                             "; ".join(errs))
        print("campaign_bench --check OK")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
