"""Loop DFGs as plain data, with their semantics, kept apart from the
program under test.

A graph is a tuple of nodes; node ``i`` is ``(op, ins, imm)`` where
``ins`` is a tuple of ``(src, distance)`` pairs (distance 0 = a value of
the same iteration, d >= 1 = the value ``src`` produced d iterations
earlier) and ``imm`` is a constant's value or a load/store base address.
Node ids are positions. This is the shape every traffic generator
produces, the reference judges, and the harness converts into the
program's own DFG only when it submits a request.

The operation semantics below are those of the SAT-MapIt loop model as
the paper's simulator defines them (single-output ops, 64-bit two's
complement wrap, loads of unwritten addresses read a fixed pattern).
"""
from __future__ import annotations

import hashlib
import math
from typing import Dict, List, Optional, Sequence, Tuple

Node = Tuple[str, Tuple[Tuple[int, int], ...], int]
Graph = Tuple[Node, ...]

_MASK64 = (1 << 64) - 1

BINOPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "and": lambda a, b: a & b,
    "or": lambda a, b: a | b,
    "xor": lambda a, b: a ^ b,
    "shl": lambda a, b: a << (b & 63),
    "shr": lambda a, b: (a % (1 << 64)) >> (b & 63),
    "min": min,
    "max": max,
    "lt": lambda a, b: int(a < b),
    "le": lambda a, b: int(a <= b),
    "eq": lambda a, b: int(a == b),
    "ne": lambda a, b: int(a != b),
    "div": lambda a, b: a // b if b else 0,
    "rem": lambda a, b: a % b if b else 0,
}
UNARY = ("route", "phi", "not", "neg")
OP_CLASS = {"load": "mem", "store": "mem", "mul": "mul", "div": "mul",
            "rem": "mul"}


def op_class(op: str) -> str:
    return OP_CLASS.get(op, "alu")


def wrap(v: int) -> int:
    v &= _MASK64
    return v - (1 << 64) if v >= (1 << 63) else v


def node(op: str, ins=(), imm: int = 0) -> Node:
    return (op, tuple((int(s), int(d)) for s, d in ins), int(imm))


# ------------------------------------------------------------- structure
def edges(g: Graph) -> List[Tuple[int, int, int]]:
    """(src, dst, distance) triples, in node then operand order."""
    return [(s, d, dist) for d, (_, ins, _) in enumerate(g)
            for s, dist in ins]


def topo_order(g: Graph) -> List[int]:
    """Order over distance-0 edges; raises ValueError on a forward cycle."""
    indeg = [0] * len(g)
    succ: List[List[int]] = [[] for _ in g]
    for s, d, dist in edges(g):
        if dist == 0:
            indeg[d] += 1
            succ[s].append(d)
    ready = [i for i in range(len(g)) if indeg[i] == 0]
    order: List[int] = []
    while ready:
        x = ready.pop(0)
        order.append(x)
        for d in succ[x]:
            indeg[d] -= 1
            if indeg[d] == 0:
                ready.append(d)
    if len(order) != len(g):
        raise ValueError("forward edges contain a cycle")
    return order


def validate(g: Graph) -> None:
    topo_order(g)
    for i, (op, ins, _) in enumerate(g):
        for s, dist in ins:
            if not 0 <= s < len(g) or dist < 0:
                raise ValueError(f"node {i}: bad input {(s, dist)}")
        want = 2 if op in BINOPS else 3 if op == "select" else \
            1 if op in UNARY else None
        if want is not None and len(ins) != want:
            raise ValueError(f"{op} node {i} needs {want} inputs")


# ------------------------------------------------------------- execution
def mem_default(addr: int) -> int:
    """What a load of a never-written address reads: a fixed pattern, so
    a misordered load and store differ in value."""
    return ((addr * 2654435761 + 40503) & 0xFFFFFFF) - (1 << 27)


def eval_node(nd: Node, args: List[int], it: int, mem: Dict[int, int]) -> int:
    op, _, imm = nd
    if op in BINOPS:
        return BINOPS[op](args[0], args[1])
    if op == "const":
        return imm
    if op == "iv":
        return it
    if op in ("route", "phi"):
        return args[0]
    if op == "not":
        return ~args[0]
    if op == "neg":
        return -args[0]
    if op == "select":
        return args[1] if args[0] else args[2]
    if op == "load":
        a = imm + (args[0] if args else 0)
        return mem.get(a, mem_default(a))
    if op == "store":
        mem[imm + args[0]] = args[1]
        return args[1]
    raise ValueError(f"unknown op {op!r}")


def init_value(nid: int) -> int:
    """Value of a loop-carried read that reaches before iteration 0."""
    return (nid * 7919 + 17) % 1009 - 504


def execute(g: Graph, n_iters: int) -> Tuple[List[List[int]], Dict[int, int]]:
    """Sequential execution of ``n_iters`` iterations of the loop body."""
    mem: Dict[int, int] = {}
    order = topo_order(g)
    hist: List[List[int]] = []
    for it in range(n_iters):
        vals = [0] * len(g)
        for nid in order:
            args = []
            for src, dist in g[nid][1]:
                if dist == 0:
                    args.append(vals[src])
                elif it - dist >= 0:
                    args.append(hist[it - dist][src])
                else:
                    args.append(init_value(src))
            vals[nid] = wrap(eval_node(g[nid], args, it, mem))
        hist.append(vals)
    return hist, mem


# ------------------------------------------------------------ scheduling
def latencies(g: Graph, lat_of_class: Dict[str, int]) -> List[int]:
    return [int(lat_of_class.get(op_class(op), 1)) for op, _, _ in g]


def asap_alap(g: Graph, lat: Optional[Sequence[int]] = None,
              ) -> Tuple[List[int], List[int], int]:
    """Mobility windows over distance-0 edges: a node issued at t has its
    result at t + lat; sinks finish at the schedule length L."""
    order = topo_order(g)
    lat = list(lat) if lat is not None else [1] * len(g)
    asap = [0] * len(g)
    for x in order:
        for s, dist in g[x][1]:
            if dist == 0:
                asap[x] = max(asap[x], asap[s] + lat[s])
    length = max((asap[x] + lat[x] for x in order), default=0)
    alap = [length - lat[x] for x in range(len(g))]
    succ: List[List[int]] = [[] for _ in g]
    for s, d, dist in edges(g):
        if dist == 0:
            succ[s].append(d)
    for x in reversed(order):
        for d in succ[x]:
            alap[x] = min(alap[x], alap[d] - lat[x])
    return asap, alap, length


def res_mii(g: Graph, n_pes: int, pes_per_class: Dict[str, int]) -> int:
    mii = math.ceil(len(g) / n_pes)
    counts: Dict[str, int] = {}
    for op, _, _ in g:
        counts[op_class(op)] = counts.get(op_class(op), 0) + 1
    for cls, cnt in counts.items():
        cap = pes_per_class.get(cls, 0)
        if cap == 0:
            raise ValueError(f"no PE executes class {cls!r}")
        mii = max(mii, math.ceil(cnt / cap))
    return max(mii, 1)


def _no_positive_cycle(n: int, es, lat: Sequence[int], ii: int) -> bool:
    d = [0] * n
    for _ in range(n + 1):
        changed = False
        for s, t, dist in es:
            w = d[s] + lat[s] - dist * ii
            if w > d[t]:
                d[t] = w
                changed = True
        if not changed:
            return True
    return False


def rec_mii(g: Graph, lat: Optional[Sequence[int]] = None) -> int:
    """Smallest II at which no dependence cycle needs more cycles than its
    iteration distance gives (longest-path feasibility, binary search)."""
    lat = list(lat) if lat is not None else [1] * len(g)
    es = edges(g)
    lo, hi = 1, max(1, sum(lat))
    while lo < hi:
        mid = (lo + hi) // 2
        if _no_positive_cycle(len(g), es, lat, mid):
            hi = mid
        else:
            lo = mid + 1
    return lo


# --------------------------------------------------------- canonical form
def _refine(g: Graph, colors: List, out_edges) -> List[int]:
    for _ in range(len(g) + 1):
        sigs = []
        for nid, (_, ins, _) in enumerate(g):
            ins_sig = tuple((dist, colors[src]) for src, dist in ins)
            outs_sig = tuple(sorted((dist, slot, colors[dst])
                                    for dst, slot, dist in out_edges[nid]))
            sigs.append((colors[nid], ins_sig, outs_sig))
        ranks = {s: i for i, s in enumerate(sorted(set(sigs)))}
        new = [ranks[s] for s in sigs]
        if new == colors:
            return new
        colors = new
    return colors


def relabel(g: Graph, order: Sequence[int]) -> Graph:
    idx = {old: new for new, old in enumerate(order)}
    return tuple((g[old][0], tuple((idx[s], d) for s, d in g[old][1]),
                  g[old][2]) for old in order)


def canonical(g: Graph, budget: int = 128) -> Graph:
    """A relabelling shared by every isomorphic copy of ``g``: colour
    refinement, then individualise-and-refine on the first ambiguous
    class, keeping the smallest resulting graph. Past ``budget`` leaves
    the order is only best effort, which over-counts distinct graphs."""
    out_edges: List[List[Tuple[int, int, int]]] = [[] for _ in g]
    for nid, (_, ins, _) in enumerate(g):
        for slot, (src, dist) in enumerate(ins):
            out_edges[src].append((nid, slot, dist))
    init = [(op, imm, len(ins)) for op, ins, imm in g]
    ranks = {c: i for i, c in enumerate(sorted(set(init), key=repr))}
    base = _refine(g, [ranks[c] for c in init], out_edges)
    best: List[Optional[Graph]] = [None]
    leaves = [0]

    def consider(order):
        cand = relabel(g, order)
        if best[0] is None or repr(cand) < repr(best[0]):
            best[0] = cand

    def search(colors):
        groups: Dict[int, List[int]] = {}
        for nid, c in enumerate(colors):
            groups.setdefault(c, []).append(nid)
        amb = [c for c in sorted(groups) if len(groups[c]) > 1]
        if not amb or leaves[0] >= budget:
            leaves[0] += 1
            consider(sorted(range(len(g)), key=lambda n: (colors[n], n)))
            return
        for nid in groups[amb[0]]:
            if leaves[0] >= budget:
                break
            forced = list(colors)
            forced[nid] = -1
            search(_refine(g, forced, out_edges))

    search(base)
    return best[0]


def canonical_key(g: Graph) -> str:
    """Isomorphism-invariant digest: two requests with equal keys are the
    same loop under some node numbering."""
    return hashlib.sha256(repr(canonical(g)).encode()).hexdigest()


# ------------------------------------------------------------------ JSON
def from_json(rows) -> Graph:
    return tuple(node(op, ins, imm) for op, ins, imm in rows)
