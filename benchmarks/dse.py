"""CGRA design-space exploration with the SAT mapper (beyond-paper).

Because SAT-MapIt is exact within the KMS window, the II it returns is a
*property of the fabric*, not of heuristic luck — which makes it usable as
a DSE inner loop: sweep topology (paper mesh vs torus vs +diagonals) and
register-file size, and report the best II per kernel.

    PYTHONPATH=src python -m benchmarks.dse
"""
from __future__ import annotations

import time

from repro.core import suite
from repro.core.cgra import CGRA
from repro.core.mapper import MapperConfig, map_loop

KERNELS = ["sha", "sha2", "hotspot", "patricia", "srand"]
FABRICS = [
    ("2x2 mesh", CGRA(2, 2, topology="mesh")),
    ("2x2 torus", CGRA(2, 2, topology="torus")),
    ("2x2 diag", CGRA(2, 2, topology="diag")),
    ("2x3 mesh", CGRA(2, 3, topology="mesh")),
    ("3x3 mesh", CGRA(3, 3, topology="mesh")),
]


def main() -> None:
    print("(+r = with routing-node insertion; None = no mapping in budget)")
    print("kernel," + ",".join(n for n, _ in FABRICS) + ",3x3 mesh +r")
    for k in KERNELS:
        row = [k]
        for _, cgra in FABRICS:
            g = suite.get(k)
            r = map_loop(g, cgra, MapperConfig(solver="auto", timeout_s=60))
            row.append(str(r.ii))
        g = suite.get(k)
        r = map_loop(g, CGRA(3, 3), MapperConfig(
            solver="auto", timeout_s=120, routing=True, max_route_nodes=4))
        row.append(str(r.ii))
        print(",".join(row))


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
