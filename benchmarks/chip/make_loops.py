"""Writes a fixed list of loops for a ``listed`` traffic mix: seeded suite
mutants or grammar loops (as the ``suite_mutants`` and ``grammar`` sources
draw them), each distinct under the isomorphism key and from the suite
kernels, kept only where the plain reference places the loop at its MII
on the given fabric. Each entry carries that II, which every verdict on
the loop must then equal.

    python3 benchmarks/chip/make_loops.py --name walk_loops \
        --config suite5x5-portfolio --seed 20251202 --count 480

The list is made once and committed, so no run pays for the selection.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import graphs, reference, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--name", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--count", type=int, required=True)
    ap.add_argument("--source", choices=("suite_mutants", "grammar"),
                    default="suite_mutants")
    args = ap.parse_args()
    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    fab = reference.fabric_from_config(config["fabric"])
    mix = {"source": args.source, "mutations": [1, 3],
           "kinds": list(traffic.KINDS)}
    seen = {graphs.canonical_key(g) for g in traffic.suite_kernels().values()}
    loops, drawn = [], 0
    for name, g in traffic.source_stream(mix, args.seed):
        key = graphs.canonical_key(g)
        if key in seen:
            continue
        seen.add(key)
        drawn += 1
        if not reference.mii_feasible((g, fab)):
            continue
        loops.append({"name": name, "kernel": name.split("~")[0]
                      if args.source == "suite_mutants" else "",
                      "ii": fab.mii(g), "graph": [list(nd) for nd in g]})
        if len(loops) >= args.count:
            break
    what = (f"suite mutants (1-3 edits of {', '.join(traffic.KINDS)})"
            if args.source == "suite_mutants" else
            "grammar loops (campaign defaults)")
    out = {"source": f"{what} of seed {args.seed}, {len(loops)} of the "
                     f"first {drawn} distinct draws: those the reference "
                     f"places at their MII on {config['fabric']['name']}",
           "fabric": config["fabric"], "loops": loops}
    path = HERE / "data" / f"{args.name}.json"
    path.write_text(json.dumps(out, separators=(",", ":")) + "\n")
    print(f"{path}: {len(loops)} loops of {drawn} draws")
    return 0


if __name__ == "__main__":
    sys.exit(main())
