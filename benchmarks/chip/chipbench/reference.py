"""The plain reference: what a correct verdict is, decided without the
program under test.

A verdict for one loop on one fabric is an II (or "no mapping in
[MII, MII + 16]"), a placement {node: (pe, cycle, iteration)}, a register
assignment, and one claim per lower II: refuted (UNSAT) or solved but
rejected by register allocation. The reference checks

* the placement: every node on a capable PE, one node per (PE, kernel
  cycle) and per output-register write cycle, every edge between
  neighbouring PEs inside its timing window, every value either read from
  the producer's output register before the next write lands there or
  held in a local register no other live value shares, and the pipelined
  execution equal to the sequential one (values and memory);
* the claims: each II from the reference MII up to the verdict's (up to
  MII + 16 for "no mapping") must carry one, and an ILP over the same
  kernel mobility schedule (scipy's HiGHS) must agree with it: infeasible
  where the program refuted the II, feasible where it says its model
  failed register allocation (the reference cannot re-derive which model
  the program had, so it only checks that such a model can exist).

Everything here is numpy and scipy; nothing imports the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import graphs
from .graphs import Graph

N_ITERS = 8          # loop iterations the simulator runs per verdict
ILP_TIME_LIMIT = 60.0


@dataclass(frozen=True)
class Fabric:
    rows: int
    cols: int
    regs: Tuple[int, ...]
    classes: Dict[str, Tuple[int, ...]]       # op class -> capable PEs
    latency: Dict[str, int]
    neighbours: Tuple[frozenset, ...]

    @property
    def n_pes(self) -> int:
        return self.rows * self.cols

    def reachable(self, src: int, dst: int) -> bool:
        return src == dst or dst in self.neighbours[src]

    def lat(self, g: Graph) -> List[int]:
        return graphs.latencies(g, self.latency)

    def allowed(self, op: str) -> Tuple[int, ...]:
        return self.classes[graphs.op_class(op)]

    def mii(self, g: Graph) -> int:
        per_class = {c: len(p) for c, p in self.classes.items()}
        return max(graphs.res_mii(g, self.n_pes, per_class),
                   graphs.rec_mii(g, self.lat(g)))


def fabric_from_config(spec: dict) -> Fabric:
    """A fabric as a configuration file states it: a mesh of ``rows`` x
    ``cols`` PEs, each reading its own and its four neighbours' output
    registers; per-class PE regions (only ``all`` is known) and
    latencies; ``regs`` local registers per PE."""
    rows, cols = int(spec["rows"]), int(spec["cols"])
    if spec.get("interconnect", "mesh") != "mesh":
        raise ValueError("the reference knows only the mesh interconnect")
    n = rows * cols
    classes = {}
    for cls in ("alu", "mem", "mul"):
        region = spec.get("classes", {}).get(cls, "all")
        if region != "all":
            raise ValueError(f"unknown region {region!r} for {cls}")
        classes[cls] = tuple(range(n))
    nbrs = []
    for p in range(n):
        r, c = divmod(p, cols)
        nbrs.append(frozenset(rr * cols + cc for rr, cc in
                              ((r - 1, c), (r + 1, c), (r, c - 1), (r, c + 1))
                              if 0 <= rr < rows and 0 <= cc < cols))
    return Fabric(rows, cols, (int(spec["regs"]),) * n, classes,
                  {k: int(v) for k, v in spec.get("latency", {}).items()},
                  tuple(nbrs))


# ------------------------------------------------------------ feasibility
def kms_feasible(g: Graph, fab: Fabric, ii: int,
                 time_limit: float = ILP_TIME_LIMIT) -> Optional[bool]:
    """Is there a placement at ``ii`` with every node inside its mobility
    window [ASAP, ALAP] of the distance-0 schedule (the paper's kernel
    mobility schedule)? None when the solver hit its time limit."""
    from scipy.optimize import Bounds, LinearConstraint, milp
    from scipy.sparse import coo_matrix
    lat = fab.lat(g)
    asap, alap, _ = graphs.asap_alap(g, lat)
    var: Dict[Tuple[int, int, int], int] = {}
    for n, (op, _, _) in enumerate(g):
        for t in range(asap[n], alap[n] + 1):
            for p in fab.allowed(op):
                var[(n, p, t)] = len(var)
    rows: List[int] = []
    cols: List[int] = []
    vals: List[float] = []
    lo: List[float] = []
    hi: List[float] = []

    def add(entries, low, high):
        r = len(lo)
        for j, v in entries:
            rows.append(r)
            cols.append(j)
            vals.append(v)
        lo.append(low)
        hi.append(high)

    by_node: Dict[int, List[int]] = {}
    issue: Dict[Tuple[int, int], List[int]] = {}
    write: Dict[Tuple[int, int], List[int]] = {}
    for (n, p, t), j in var.items():
        by_node.setdefault(n, []).append(j)
        issue.setdefault((p, t % ii), []).append(j)
        write.setdefault((p, (t + lat[n]) % ii), []).append(j)
    for n in range(len(g)):
        js = by_node.get(n, [])
        if not js:
            return False
        add([(j, 1) for j in js], 1, 1)
    slots = list(issue.values())
    if len(set(lat)) > 1:
        slots += list(write.values())
    for js in slots:
        if len(js) > 1:
            add([(j, 1) for j in js], 0, 1)
    for s, d, dist in graphs.edges(g):
        w_lo, w_hi = lat[s] - dist * ii, (1 - dist) * ii + lat[s] - 1
        for td in range(asap[d], alap[d] + 1):
            ok = [ts for ts in range(asap[s], alap[s] + 1)
                  if w_lo <= td - ts <= w_hi]
            for pd in fab.allowed(g[d][0]):
                ent = [(var[(d, pd, td)], 1)]
                ent += [(var[(s, ps, ts)], -1) for ts in ok
                        for ps in fab.allowed(g[s][0])
                        if fab.reachable(ps, pd)]
                add(ent, -np.inf, 0)
    a = coo_matrix((vals, (rows, cols)), shape=(len(lo), len(var))).tocsr()
    res = milp(np.zeros(len(var)), integrality=np.ones(len(var)),
               bounds=Bounds(0, 1),
               constraints=LinearConstraint(a, np.asarray(lo), np.asarray(hi)),
               options={"time_limit": time_limit})
    if res.status == 0:
        return True
    if res.status == 2:
        return False
    return None


def mii_feasible(args) -> bool:
    """Pre-selection: the loop places at its MII (picklable for a pool)."""
    g, fab = args
    return kms_feasible(g, fab, fab.mii(g)) is True


# --------------------------------------------------------------- placement
def placement_faults(g: Graph, fab: Fabric, ii: int,
                     placement: Dict[int, Tuple[int, int, int]],
                     regs: Dict[int, int]) -> List[str]:
    """Static faults of one verdict's placement and register assignment."""
    errs: List[str] = []
    if set(placement) != set(range(len(g))):
        return ["placement does not cover every node"]
    lat = fab.lat(g)
    t = {n: it * ii + c for n, (p, c, it) in placement.items()}
    issue: Dict[Tuple[int, int], int] = {}
    write: Dict[Tuple[int, int], int] = {}
    for n in range(len(g)):
        p, c, _ = placement[n]
        if not 0 <= p < fab.n_pes or not 0 <= c < ii:
            errs.append(f"node {n}: slot {(p, c)} outside the fabric/II")
            continue
        if p not in fab.allowed(g[n][0]):
            errs.append(f"node {n}: PE {p} cannot run {g[n][0]}")
        if (p, c) in issue:
            errs.append(f"nodes {issue[(p, c)]} and {n} share PE {p} "
                        f"cycle {c}")
        issue[(p, c)] = n
        wk = (p, (c + lat[n]) % ii)
        if wk in write and placement[write[wk]][1] != c:
            errs.append(f"nodes {write[wk]} and {n} write PE {p}'s output "
                        f"register in one cycle")
        write[wk] = n
    if errs:
        return errs
    last_read: Dict[int, int] = {}
    for s, d, dist in graphs.edges(g):
        ps, pd = placement[s][0], placement[d][0]
        if not fab.reachable(ps, pd):
            errs.append(f"edge {s}->{d}: PEs {ps} and {pd} not adjacent")
        span = t[d] - t[s] + dist * ii
        if not lat[s] <= span <= ii + lat[s] - 1:
            errs.append(f"edge {s}->{d}: read {span} cycles after issue, "
                        f"outside [{lat[s]}, {ii + lat[s] - 1}]")
        last_read[s] = max(last_read.get(s, 0), span)
    writes_on: Dict[int, set] = {}
    for n, (p, c, _) in placement.items():
        writes_on.setdefault(p, set()).add((c + lat[n]) % ii)
    held: Dict[Tuple[int, int], List[Tuple[int, set]]] = {}
    for n, life in last_read.items():
        p = placement[n][0]
        w0 = (t[n] + lat[n]) % ii
        after = life - lat[n]            # cycles from the write to the read
        if n in regs:
            r = regs[n]
            if not 0 <= r < fab.regs[p]:
                errs.append(f"node {n}: register {r} outside PE {p}'s "
                            f"{fab.regs[p]}")
            occ = {(w0 + k) % ii for k in range(after + 1)}
            for m, other in held.get((p, r), []):
                if occ & other:
                    errs.append(f"nodes {m} and {n} share PE {p} register "
                                f"{r} while both are live")
            held.setdefault((p, r), []).append((n, occ))
        elif any((w0 + k) % ii in writes_on[p] for k in range(1, after + 1)) \
                or after >= ii:
            errs.append(f"node {n}: value overwritten in PE {p}'s output "
                        f"register before its last read, and no register")
    return errs


def simulate(g: Graph, fab: Fabric, ii: int,
             placement: Dict[int, Tuple[int, int, int]],
             n_iters: int = N_ITERS) -> Tuple[List[List[int]], Dict[int, int]]:
    """Pipelined execution: iteration i of node n issues at i*II + t_n and
    completes lat(n) later; operations take effect in completion order."""
    lat = fab.lat(g)
    t = {n: it * ii + c for n, (p, c, it) in placement.items()}
    order = sorted((i * ii + t[n] + lat[n], i, n)
                   for i in range(n_iters) for n in range(len(g)))
    vals: List[Dict[int, int]] = [dict() for _ in range(n_iters)]
    mem: Dict[int, int] = {}
    for _, i, n in order:
        args = []
        for src, dist in g[n][1]:
            j = i - dist
            if j < 0:
                args.append(graphs.init_value(src))
            elif src not in vals[j]:
                raise ValueError(f"node {n} iteration {i} reads node {src} "
                                 f"iteration {j} before it completes")
            else:
                args.append(vals[j][src])
        vals[i][n] = graphs.wrap(graphs.eval_node(g[n], args, i, mem))
    return [[v[n] for n in range(len(g))] for v in vals], mem


def sim_matches(g: Graph, fab: Fabric, ii: int,
                placement: Dict[int, Tuple[int, int, int]]) -> bool:
    want = graphs.execute(g, N_ITERS)
    try:
        got = simulate(g, fab, ii, placement)
    except ValueError:
        return False
    return got == want


# -------------------------------------------------------------- verdicts
def claims(attempts: Sequence[Tuple[int, str, Optional[bool]]],
           ) -> Dict[int, str]:
    """Per-II claim of a verdict: "unsat" (refuted) or "regalloc" (solved,
    the model rejected by register allocation)."""
    out: Dict[int, str] = {}
    for ii, status, ra_ok in attempts:
        if status == "SAT" and ra_ok is False:
            out[ii] = "regalloc"
        elif status == "UNSAT" and ii not in out:
            out[ii] = "unsat"
    return out


def ii_faults(args) -> Tuple[List[str], int]:
    """(faults, solver timeouts) of one verdict's lower-II claims. Picklable
    for a process pool: ``args`` = (graph, fabric, ii or None, attempts)."""
    g, fab, ii, attempts = args
    mii = fab.mii(g)
    top = ii if ii is not None else mii + 17
    if ii is not None and ii < mii:
        return [f"II {ii} below the lower bound {mii}"], 0
    said = claims(attempts)
    faults: List[str] = []
    unknown = 0
    for k in range(mii, top):
        claim = said.get(k)
        if claim is None:
            faults.append(f"II {k} below the verdict carries no refutation")
            continue
        feas = kms_feasible(g, fab, k)
        if feas is None:
            unknown += 1
        elif claim == "unsat" and feas:
            faults.append(f"II {k} claimed UNSAT but a placement exists")
        elif claim == "regalloc" and not feas:
            faults.append(f"II {k} claimed solved but no placement exists")
    return faults, unknown
