"""Paper Tables I-IV reproduction: mapping time (seconds) per benchmark for
each CGRA size, SAT-MapIt vs the heuristic baseline, plus the paper's
'faster when it matters' aggregate (mean delta split by who wins)."""
from __future__ import annotations

import json
import statistics
from typing import Dict

from . import fig6_ii


def main(quick: bool = False) -> None:
    names = ["sha", "gsm", "srand", "bitcount", "nw"] if quick else None
    res = fig6_ii.run(timeout_s=30 if quick else 120, names=names,
                      heuristic_restarts=10 if quick else 30,
                      service=False)   # only sat/heur timings are read
    print("benchmark/size,sat_time_s,heur_time_s,delta_s")
    sat_slower, sat_faster = [], []
    for k, v in res.items():
        d = v["sat_time"] - v["heur_time"]
        print(f"{k},{v['sat_time']},{v['heur_time']},{round(d,3)}")
        (sat_slower if d > 0 else sat_faster).append(abs(d))
    agg = {
        "sat_slower_cells": len(sat_slower),
        "sat_slower_mean_s": round(statistics.mean(sat_slower), 2)
        if sat_slower else 0.0,
        "sat_faster_cells": len(sat_faster),
        "sat_faster_mean_s": round(statistics.mean(sat_faster), 2)
        if sat_faster else 0.0,
    }
    print(json.dumps(agg))


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
