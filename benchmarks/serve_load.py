"""Synthetic serving load for the async compile front door.

Drives the full serving tier end to end — asyncio clients ->
:class:`repro.launch.serve.CompileFrontDoor` (micro-batching, coalescing,
deadlines, backpressure) -> :class:`repro.core.workers.WorkerPool`
(affinity-routed forked solver shards) -> shared
:class:`repro.core.store.MappingStore` — in four phases:

  1. **cold**: a fresh pool over a fresh store serves a corpus of suite
     kernels plus near-shape *variants* (one rewired edge: same node/edge
     counts and kinds, different exact wiring — exactly one lattice
     bucket apart), populating the disk store and measuring solve-path
     wall-clock. Variants land on the same affinity shard as their base
     kernel and must warm-seed from it (``near_hits``).
  2. **warm restart**: the pool is torn down and rebuilt over the *same*
     store directory — every corpus request must now be served from disk
     (``via="disk"``), and corpus wall-clock must drop >= 3x.
  3. **re-solve**: ``use_cache=False`` requests on the restarted pool
     force fresh solves; their sessions preload yesterday's proven-UNSAT
     cores from the store and prune IIs without solving
     (``cores_preloaded``/``iis_pruned``).
  4. **storm**: thousands of concurrent asyncio clients hammer the
     corpus through the front door with per-request deadlines; client-
     side latencies give p50/p99 and sustained req/s.

Writes ``BENCH_serve.json`` (p50/p99 latency, req/s, cache / disk /
near-shape / core-prune hit rates — the serving-throughput trajectory,
following ``BENCH_sweep.json``'s shape). ``--check`` additionally
asserts: served results bit-identical to a direct ``compile()`` of the
same requests, warm restart >= 3x cold, >= 1000 storm clients with zero
deadline violations, and near-shape hits > 0.

    PYTHONPATH=src python benchmarks/serve_load.py --quick --check
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import json
import statistics
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from repro.core import suite
from repro.core.cgra import cgra_from_name
from repro.core.mapper import MapperConfig
from repro.core.workers import WorkerPool
from repro.launch.serve import CompileFrontDoor

QUICK_KERNELS = ["sha", "gsm", "srand", "bitcount", "nw"]
QUICK_SIZES = ["3x3"]
FULL_SIZES = ["3x3", "4x4"]


def near_variant(g, v: int):
    """A near-shape sibling of ``g``: input ``v % sites`` of some
    two-input node is rewired onto the node's *other* producer. Node
    count, edge count, per-node indegree/kind, and the distance set are
    all preserved (same lattice bucket); the exact edge set is not (a
    different shape class, so a different CNF and pooled session)."""
    g2 = copy.deepcopy(g)
    sites = []
    for nid in sorted(g2.nodes):
        ins = g2.nodes[nid].ins
        if (len(ins) == 2 and ins[0][1] == 0 and ins[1][1] == 0
                and ins[0][0] != ins[1][0]):
            sites.append(nid)
    if not sites:
        return None
    nid = sites[v % len(sites)]
    node = g2.nodes[nid]
    keep = node.ins[v // len(sites) % 2][0]
    node.ins = ((keep, 0), (keep, 0))
    g2.touch()
    g2.name = f"{g.name}~v{v}"
    g2.validate()
    return g2


def build_corpus(names: List[str], sizes: List[str], n_variants: int,
                 cfg: MapperConfig) -> Tuple[List[Dict], List[Dict]]:
    """(base requests, near-variant requests); every entry is one unique
    (dfg, fabric) cell served through the door with ``cfg``."""
    base, variants = [], []
    for size in sizes:
        cgra = cgra_from_name(size)
        for name in names:
            g = suite.get(name)
            base.append({"name": f"{name}/{size}", "dfg": g, "cgra": cgra})
            for v in range(n_variants):
                gv = near_variant(g, v)
                if gv is not None:
                    variants.append({"name": f"{gv.name}/{size}", "dfg": gv,
                                     "cgra": cgra})
    return base, variants


async def serve_corpus(door: CompileFrontDoor, corpus: List[Dict],
                       cfg: MapperConfig, use_cache: bool = True,
                       deadline_s: float = 300.0) -> Tuple[List, float]:
    t0 = time.perf_counter()
    res = await asyncio.gather(*[
        door.compile(c["dfg"], c["cgra"], cfg, sweep_width=1,
                     use_cache=use_cache, deadline_s=deadline_s)
        for c in corpus])
    return list(res), time.perf_counter() - t0


async def storm(door: CompileFrontDoor, corpus: List[Dict],
                cfg: MapperConfig, n_clients: int,
                deadline_s: float) -> Dict:
    """``n_clients`` concurrent clients, one request each, drawn round-
    robin from the corpus. Returns client-side latency stats."""
    lat: List[float] = []
    violations = 0
    errors = 0

    async def client(i: int) -> None:
        nonlocal violations, errors
        c = corpus[i % len(corpus)]
        t0 = time.perf_counter()
        try:
            await door.compile(c["dfg"], c["cgra"], cfg, sweep_width=1,
                              deadline_s=deadline_s)
            lat.append(time.perf_counter() - t0)
        except Exception as exc:
            from repro.launch.serve import DeadlineExceeded
            if isinstance(exc, DeadlineExceeded):
                violations += 1
            else:
                errors += 1

    t0 = time.perf_counter()
    await asyncio.gather(*[client(i) for i in range(n_clients)])
    wall = time.perf_counter() - t0
    lat_ms = sorted(x * 1e3 for x in lat)

    def pct(p: float) -> float:
        if not lat_ms:
            return 0.0
        return lat_ms[min(len(lat_ms) - 1, int(p / 100.0 * len(lat_ms)))]

    return {
        "clients": n_clients,
        "served": len(lat),
        "deadline_violations": violations,
        "errors": errors,
        "wall_s": round(wall, 3),
        "req_per_s": round(len(lat) / max(wall, 1e-9), 1),
        "p50_ms": round(pct(50), 3),
        "p90_ms": round(pct(90), 3),
        "p99_ms": round(pct(99), 3),
        "mean_ms": round(statistics.fmean(lat_ms), 3) if lat_ms else 0.0,
    }


def direct_reference(corpus: List[Dict], cfg: MapperConfig) -> List:
    """The bit-identity oracle: the same requests through the plain
    ``compile()`` front door, no service, no store — the sequential
    deterministic path every served result must match exactly."""
    from repro.core.api import MapRequest, compile as compile_request
    out = []
    for c in corpus:
        out.append(compile_request(MapRequest(
            dfg=c["dfg"], arch=c["cgra"], config=cfg, sweep_width=1)))
    return out


def _bit_identical(a, b) -> bool:
    """Served-vs-reference identity on everything the client consumes:
    verdict, II bound pair, and the exact placement."""
    return (a.success == b.success and a.ii == b.ii and a.mii == b.mii
            and a.placement == b.placement)


async def run(quick: bool, workers: Optional[int], n_clients: int,
              store_dir: Optional[str], window_ms: float,
              deadline_s: float) -> Dict:
    names = QUICK_KERNELS if quick else suite.names()
    sizes = QUICK_SIZES if quick else FULL_SIZES
    # deterministic corpus config: sequential sweep (bit-reproducible
    # solver trajectory), explicit learnt cap matching the service default
    # so direct-reference sessions are constructed identically
    cfg = MapperConfig(solver="auto", timeout_s=120.0 if quick else 300.0,
                       max_learnt=100_000)
    base, variants = build_corpus(names, sizes, 2 if quick else 3, cfg)
    corpus = base + variants
    store_path = store_dir or tempfile.mkdtemp(prefix="satmapit-store-")
    out: Dict = {"quick": quick, "store": store_path,
                 "corpus_cells": len(corpus),
                 "base_cells": len(base), "variant_cells": len(variants)}

    # ---- phase 1: cold pool over a fresh store -------------------------
    with WorkerPool(workers=workers, store_path=store_path,
                    near_delta=1) as pool:
        async with CompileFrontDoor(pool, window_ms=window_ms,
                                    max_batch=64) as door:
            cold_base, t_base = await serve_corpus(door, base, cfg,
                                                   deadline_s=deadline_s)
            cold_var, t_var = await serve_corpus(door, variants, cfg,
                                                 deadline_s=deadline_s)
        cold = cold_base + cold_var
        t_cold = t_base + t_var
        cold_stats = pool.stats()
    out["cold_s"] = round(t_cold, 3)
    out["cold_workers"] = {k: v for k, v in cold_stats.items()
                           if isinstance(v, (int, float))}

    # ---- phase 2: warm restart over the same store ---------------------
    with WorkerPool(workers=workers, store_path=store_path,
                    near_delta=1) as pool:
        async with CompileFrontDoor(pool, window_ms=window_ms,
                                    max_batch=64) as door:
            warm, t_warm = await serve_corpus(door, corpus, cfg,
                                              deadline_s=deadline_s)

            # ---- phase 3: forced re-solves adopt persisted cores -------
            resolved, t_resolve = await serve_corpus(
                door, base, cfg, use_cache=False, deadline_s=deadline_s)

            # ---- phase 4: client storm --------------------------------
            storm_stats = await storm(door, corpus, cfg, n_clients,
                                      deadline_s)
            door_stats = door.stats.snapshot()
        warm_stats = pool.stats()

    out["warm_s"] = round(t_warm, 3)
    out["warm_speedup"] = round(t_cold / max(t_warm, 1e-9), 1)
    out["warm_via"] = sorted({r.service.via for r in warm})
    out["resolve_s"] = round(t_resolve, 3)
    out["storm"] = storm_stats
    out["front_door"] = door_stats
    out["warm_workers"] = {k: v for k, v in warm_stats.items()
                           if isinstance(v, (int, float))}

    req_cold = max(cold_stats.get("requests", 0), 1)
    req_warm = max(warm_stats.get("requests", 0), 1)
    out["hit_rates"] = {
        "near_shape": round(cold_stats.get("near_hits", 0)
                            / max(len(variants), 1), 3),
        "disk": round(warm_stats.get("disk_hits", 0) / req_warm, 3),
        "cache": round((cold_stats.get("cache_hits", 0)
                        + warm_stats.get("cache_hits", 0))
                       / (req_cold + req_warm), 3),
        "core_prune_iis": warm_stats.get("iis_pruned", 0),
        "cores_preloaded": warm_stats.get("cores_preloaded", 0),
        "near_hits": cold_stats.get("near_hits", 0),
    }
    out["summary"] = {
        "req_per_s": storm_stats["req_per_s"],
        "p50_ms": storm_stats["p50_ms"],
        "p99_ms": storm_stats["p99_ms"],
        "warm_speedup": out["warm_speedup"],
        "deadline_violations": storm_stats["deadline_violations"],
        "near_hits": cold_stats.get("near_hits", 0),
        "disk_hits": warm_stats.get("disk_hits", 0),
        "cores_preloaded": warm_stats.get("cores_preloaded", 0),
    }
    # stash result objects for --check (not serialised)
    out["_cold"] = cold
    out["_warm"] = warm
    out["_resolved"] = resolved
    out["_corpus"] = corpus
    out["_cfg"] = cfg
    return out


def check(out: Dict) -> None:
    bad: List[str] = []
    corpus, cfg = out["_corpus"], out["_cfg"]
    cold, warm = out["_cold"], out["_warm"]

    # served results must be bit-identical to a direct compile() of the
    # same requests (the sequential deterministic reference)
    ref = direct_reference(corpus, cfg)
    mismatch = [c["name"] for c, a, b in zip(corpus, cold, ref)
                if not _bit_identical(a, b)]
    if mismatch:
        bad.append(f"served != direct compile() on {mismatch}")
    # the warm (disk) restart must return the *same bits* it stored
    drift = [c["name"] for c, a, b in zip(corpus, warm, cold)
             if not _bit_identical(a, b)]
    if drift:
        bad.append(f"warm restart drifted from cold results on {drift}")
    not_disk = [c["name"] for c, r in zip(corpus, warm)
                if r.service.via != "disk"]
    if not_disk:
        bad.append(f"warm restart did not hit the disk store on {not_disk}")
    if out["warm_speedup"] < 3.0:
        bad.append(f"warm restart speedup {out['warm_speedup']}x < 3x")
    if out["hit_rates"]["near_hits"] < 1:
        bad.append("no near-shape warm admissions (near_hits == 0)")
    if out["hit_rates"]["cores_preloaded"] < 1:
        bad.append("restarted sessions preloaded no persisted UNSAT cores")
    st = out["storm"]
    if st["clients"] < 1000:
        bad.append(f"storm ran only {st['clients']} clients (< 1000)")
    if st["deadline_violations"] or st["errors"]:
        bad.append(f"storm: {st['deadline_violations']} deadline "
                   f"violations, {st['errors']} errors")
    if st["served"] != st["clients"]:
        bad.append(f"storm served {st['served']}/{st['clients']}")
    if bad:
        raise SystemExit("serve_load --check failed: " + "; ".join(bad))
    print("serve_load --check OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--clients", type=int, default=1000)
    ap.add_argument("--store", default=None,
                    help="store directory (default: fresh tempdir)")
    ap.add_argument("--window-ms", type=float, default=4.0)
    ap.add_argument("--deadline-s", type=float, default=120.0)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    out = asyncio.run(run(args.quick, args.workers, args.clients,
                          args.store, args.window_ms, args.deadline_s))
    public = {k: v for k, v in out.items() if not k.startswith("_")}
    print(json.dumps(public, indent=1, sort_keys=True))
    with open(args.out, "w") as f:
        json.dump(public, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    if args.check:
        check(out)


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
