"""The one traffic generator: every mix is a data file
``traffic/<name>.json`` that this module reads. A new mix is a new data
file; nothing here names one.

Where the loops come from (``source``):

* ``suite_mutants`` -- a suite kernel (``data/suite_kernels.json``) with
  ``mutations`` = [lo, hi] seeded edits, each one of ``kinds``: ``near``
  (a two-input node's operand rewired onto its other producer: same node,
  edge and distance counts), ``op``, ``imm``, ``rewire``, ``grow`` or
  ``carry``. A fresh stream per ``--seed``.
* ``grammar`` -- loops grown by the seeded level grammar with the
  ``grammar`` knobs. With ``corpus_seed`` the set of loops is fixed by the
  file and ``--seed`` only orders it.
* ``listed`` -- the loops of ``data/<loops>.json``, each with the II at
  which the reference places it (``ii``), in one fixed order: every run
  sends the same loops in the same order, whatever the seed, because the
  order decides which loops a window reaches and, with concurrent
  clients, the schedule on the service's shards and the chip's queue.

How they are sent:

* ``arrival`` -- ``{"kind": "closed", "clients": n}``: n clients, each
  sending its next request when its verdict arrives; or ``{"kind":
  "open", "rate_per_s": r, "burst": b, "deadline_s": d}``: bursts of b
  requests at exponential gaps of mean b / r seconds drawn from the seed,
  each request with a deadline of d seconds (a verdict past it is late).
* ``requests`` -- how many loops a run may send at most.
* ``repeat`` -- a working set of that many distinct loops, served once in
  set-up; the window's requests are drawn from it (with replacement, by
  the seed), so the service's caches answer them.
* ``fabrics`` -- {program fabric name: fabric spec}: each request's fabric
  drawn by the seed from these (a design-space sweep); without it, the
  configuration's fabric.
* ``warm`` -- ``"suite"``: the 11 suite kernels are served in set-up.

Without ``repeat``, every request of one run is distinct under the
isomorphism key and from the loops served in set-up, so no request is a
repeat that a cache could answer. The mutation and grammar code follows
the repository's campaign grammar (``random_dfg`` / ``mutate_dfg``) and
the serving benchmark's ``near_variant``, rewritten on plain graphs so
that later changes to the program cannot move the traffic.
"""
from __future__ import annotations

import json
import random
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

from . import graphs
from .graphs import Graph

HERE = Path(__file__).resolve().parent.parent
ALU_OPS = ("add", "sub", "and", "or", "xor", "shl", "shr", "min", "max",
           "lt", "eq", "ne")
KINDS = ("near", "op", "imm", "rewire", "grow", "carry")
GRAMMAR_DEFAULTS = {"min_nodes": 6, "max_nodes": 18, "p_mem": 0.22,
                    "p_mul": 0.12, "p_select": 0.05, "recent_window": 4,
                    "p_far_edge": 0.30, "p_carry": 0.65, "max_carry": 2}
SOURCES = ("suite_mutants", "grammar", "listed")
MIX_KEYS = {"source", "why", "arrival", "requests", "repeat", "fabrics",
            "warm", "mutations", "kinds", "grammar", "corpus_seed", "loops"}


@dataclass(frozen=True)
class Request:
    name: str
    graph: Graph
    fabric: Optional[str] = None    # a key of the mix's "fabrics"
    ii: Optional[int] = None        # where the reference's II is listed


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    return check_mix(json.loads(path.read_text()), str(path))


def check_mix(mix: dict, where: str = "mix") -> dict:
    if mix.get("source") not in SOURCES:
        raise ValueError(f"{where}: unknown source {mix.get('source')!r}")
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"{where}: unknown keys {sorted(unknown)}")
    arr = mix.get("arrival", {})
    if arr.get("kind") == "closed":
        if int(arr.get("clients", 0)) < 1:
            raise ValueError(f"{where}: a closed loop needs clients >= 1")
    elif arr.get("kind") == "open":
        if float(arr.get("rate_per_s", 0)) <= 0:
            raise ValueError(f"{where}: an open loop needs rate_per_s > 0")
    else:
        raise ValueError(f"{where}: arrival kind must be closed or open")
    if mix.get("warm") not in (None, "suite"):
        raise ValueError(f"{where}: unknown warm {mix['warm']!r}")
    return mix


def suite_kernels() -> Dict[str, Graph]:
    data = json.loads((HERE / "data" / "suite_kernels.json").read_text())
    return {k: graphs.from_json(v) for k, v in data["kernels"].items()}


# ---------------------------------------------------------------- edits
def near_variant(g: Graph, v: int) -> Optional[Graph]:
    """Input ``v % sites`` of some two-input node rewired onto the node's
    other producer; None when no node has two distinct same-iteration
    producers."""
    sites = [n for n, (_, ins, _) in enumerate(g)
             if len(ins) == 2 and ins[0][1] == 0 and ins[1][1] == 0
             and ins[0][0] != ins[1][0]]
    if not sites:
        return None
    nid = sites[v % len(sites)]
    keep = g[nid][1][v // len(sites) % 2][0]
    out = list(g)
    out[nid] = (g[nid][0], ((keep, 0), (keep, 0)), g[nid][2])
    return tuple(out)


def mutate(g: Graph, rng: random.Random, kind: str,
           max_carry: int = 2) -> Graph:
    """One edit of ``kind`` (see the module docstring); raises ValueError
    when the edit leaves an invalid loop."""
    nodes = [list(nd) for nd in g]
    if kind == "near":
        out = near_variant(g, rng.randrange(64))
        if out is None:
            raise ValueError("no near-shape site")
        return out
    if kind == "op":
        cands = [i for i, nd in enumerate(nodes) if nd[0] in ALU_OPS]
        if cands:
            i = cands[rng.randrange(len(cands))]
            choices = [op for op in ALU_OPS if op != nodes[i][0]]
            nodes[i][0] = choices[rng.randrange(len(choices))]
    elif kind == "imm":
        cands = [i for i, nd in enumerate(nodes) if nd[0] == "const"]
        if cands:
            i = cands[rng.randrange(len(cands))]
            nodes[i][2] += rng.randint(1, 97)
    elif kind == "rewire":
        topo = graphs.topo_order(g)
        pos = {n: i for i, n in enumerate(topo)}
        cands = [(n, slot) for n, nd in enumerate(nodes)
                 for slot, (_, dist) in enumerate(nd[1])
                 if dist == 0 and pos[n] > 0]
        if cands:
            n, slot = cands[rng.randrange(len(cands))]
            earlier = topo[:pos[n]]
            ins = list(nodes[n][1])
            ins[slot] = (earlier[rng.randrange(len(earlier))], 0)
            nodes[n][1] = tuple(ins)
    elif kind == "grow":
        a, b = rng.randrange(len(nodes)), rng.randrange(len(nodes))
        nodes.append([ALU_OPS[rng.randrange(len(ALU_OPS))],
                      ((a, 0), (b, 0)), 0])
    elif kind == "carry":
        back = [(n, slot) for n, nd in enumerate(nodes)
                for slot, (_, dist) in enumerate(nd[1]) if dist > 0]
        if back:
            n, slot = back[rng.randrange(len(back))]
            ins = list(nodes[n][1])
            ins[slot] = (ins[slot][0], rng.randint(1, max(2, max_carry)))
            nodes[n][1] = tuple(ins)
        else:
            targets = [n for n, nd in enumerate(nodes) if nd[1]]
            if targets:
                n = targets[rng.randrange(len(targets))]
                ins = list(nodes[n][1])
                ins[rng.randrange(len(ins))] = (
                    rng.randrange(len(nodes)),
                    rng.randint(1, max(1, max_carry)))
                nodes[n][1] = tuple(ins)
    else:
        raise ValueError(f"unknown edit {kind!r}")
    out = tuple(graphs.node(*nd) for nd in nodes)
    graphs.validate(out)
    return out


def random_graph(rng: random.Random, knobs: dict) -> Graph:
    """One grammar loop: iv and constant sources, a body whose op classes
    follow the knobs' mix, input locality by ``recent_window`` against
    ``p_far_edge``, and with ``p_carry`` one or two loop-carried back-edges
    from a late producer to an early consumer."""
    k = {**GRAMMAR_DEFAULTS, **knobs}
    n_target = rng.randint(k["min_nodes"], k["max_nodes"])
    nodes: List[list] = [["iv", (), 0]]
    for _ in range(rng.randint(1, 3)):
        nodes.append(["const", (), rng.randint(-64, 64)])
    _grow(rng, k, n_target, nodes, list(range(len(nodes))))
    g = tuple(graphs.node(*nd) for nd in nodes)
    if rng.random() < k["p_carry"]:
        asap, _, _ = graphs.asap_alap(g)
        nodes = [list(nd) for nd in g]
        targets = sorted((n for n in range(len(nodes)) if nodes[n][1]),
                         key=lambda n: (asap[n], n))
        for _ in range(rng.randint(1, 2)):
            dst = targets[rng.randrange(max(1, len(targets) // 2))]
            dist = 1 if (k["max_carry"] < 2 or rng.random() < 0.8) \
                else rng.randint(2, k["max_carry"])
            late = [n for n in range(len(nodes))
                    if asap[n] >= asap[dst] + (dist - 1)]
            if not late:
                dist, late = 1, [n for n in range(len(nodes))
                                 if asap[n] >= asap[dst]]
            src = late[rng.randrange(len(late))]
            ins = list(nodes[dst][1])
            ins[rng.randrange(len(ins))] = (src, dist)
            nodes[dst][1] = tuple(ins)
        g = tuple(graphs.node(*nd) for nd in nodes)
    graphs.validate(g)
    return g


def _grow(rng, k, n_target, nodes, values) -> None:
    def pick() -> int:
        if rng.random() < k["p_far_edge"]:
            return values[rng.randrange(len(values))]
        lo = max(0, len(values) - k["recent_window"])
        return values[rng.randrange(lo, len(values))]

    while len(nodes) < n_target:
        r = rng.random()
        if r < k["p_mem"]:
            if rng.random() < 0.5:
                nodes.append(["load", ((pick(), 0),),
                              rng.randrange(0, 512, 64)])
            else:
                nodes.append(["store", ((pick(), 0), (pick(), 0)),
                              rng.randrange(0, 512, 64)])
        elif r < k["p_mem"] + k["p_mul"]:
            nodes.append(["mul", ((pick(), 0), (pick(), 0)), 0])
        elif r < k["p_mem"] + k["p_mul"] + k["p_select"]:
            nodes.append(["select", ((pick(), 0), (pick(), 0), (pick(), 0)),
                          0])
        else:
            nodes.append([ALU_OPS[rng.randrange(len(ALU_OPS))],
                          ((pick(), 0), (pick(), 0)), 0])
        values.append(len(nodes) - 1)


# -------------------------------------------------------------- streams
def suite_mutant_stream(mix: dict, seed: int):
    """Endless stream of (name, graph) mutants from ``seed``."""
    kernels = suite_kernels()
    names = sorted(kernels)
    kinds = tuple(mix.get("kinds", KINDS))
    lo, hi = mix.get("mutations", [1, 3])
    rng = random.Random(seed)
    i = 0
    bases: List[str] = []
    while True:
        if not bases:           # every kernel once per round, seeded order
            bases = rng.sample(names, len(names))
        base = bases.pop()
        g = kernels[base]
        applied = []
        for _ in range(rng.randint(lo, hi)):
            kind = kinds[rng.randrange(len(kinds))]
            try:
                g = mutate(g, rng, kind)
                applied.append(kind)
            except ValueError:
                continue
        if applied:
            yield f"{base}~{'~'.join(applied)}#{i}", g
            i += 1


def grammar_stream(mix: dict, seed: int):
    rng = random.Random(seed)
    i = 0
    while True:
        yield f"grammar#{i}", random_graph(rng, mix.get("grammar", {}))
        i += 1


def distinct(stream, n: int) -> List[tuple]:
    """The first ``n`` items of ``stream`` with distinct isomorphism keys."""
    seen = set()
    out = []
    for name, g in stream:
        key = graphs.canonical_key(g)
        if key in seen:
            continue
        seen.add(key)
        out.append((name, g))
        if len(out) >= n:
            break
    return out



def listed(mix: dict) -> List[Request]:
    """The listed loops in one order that keeps each kernel's share even
    along the list (so every prefix a window reaches has about the same
    mix of kernels)."""
    data = json.loads((HERE / "data" / f"{mix['loops']}.json").read_text())
    rng = random.Random(0)
    by_kernel: Dict[str, List[Request]] = {}
    for e in data["loops"]:
        by_kernel.setdefault(e.get("kernel", ""), []).append(
            Request(e["name"], graphs.from_json(e["graph"]), ii=e.get("ii")))
    keyed = []
    for kernel in sorted(by_kernel):
        group = by_kernel[kernel]
        rng.shuffle(group)
        off = rng.random()
        keyed += [((j + off) / len(group), r) for j, r in enumerate(group)]
    keyed.sort(key=lambda kr: kr[0])
    return [r for _, r in keyed]


def source_stream(mix: dict, seed: int):
    if mix["source"] == "suite_mutants":
        return suite_mutant_stream(mix, seed)
    return grammar_stream(mix, int(mix.get("corpus_seed", seed)))


def warm_requests(mix: dict, seed: int) -> List[Request]:
    """The loops served in set-up, before the window."""
    if "repeat" in mix:
        return _working_set(mix, seed)
    if mix.get("warm") == "suite":
        return [Request(f"warm:{k}", g)
                for k, g in sorted(suite_kernels().items())]
    return []


def _working_set(mix: dict, seed: int) -> List[Request]:
    k = int(mix["repeat"])
    if mix["source"] == "listed":
        return _with_fabrics(mix, seed, listed(mix)[:k])
    return _with_fabrics(mix, seed, [Request(n, g) for n, g in
                                     distinct(source_stream(mix, seed), k)])


def requests(mix: dict, seed: int) -> List[Request]:
    """The window's requests, in the order they are sent."""
    n = int(mix["requests"])
    if "repeat" in mix:
        ws = _working_set(mix, seed)
        rng = random.Random(seed + 1)
        return [ws[rng.randrange(len(ws))] for _ in range(n)]
    if mix["source"] == "listed":
        return _with_fabrics(mix, seed, listed(mix)[:n])
    served = {graphs.canonical_key(r.graph)
              for r in warm_requests(mix, seed)}
    stream = ((name, g) for name, g in source_stream(mix, seed)
              if graphs.canonical_key(g) not in served)
    items = [Request(name, g) for name, g in distinct(stream, n)]
    if "corpus_seed" in mix:
        random.Random(seed).shuffle(items)
    return _with_fabrics(mix, seed, items)


def _with_fabrics(mix: dict, seed: int, items: List[Request]):
    names = sorted(mix.get("fabrics", {}))
    if not names:
        return items
    rng = random.Random(seed + 2)
    return [Request(r.name, r.graph, names[rng.randrange(len(names))], r.ii)
            for r in items]


def arrival_times(mix: dict, seed: int, seconds: float) -> List[float]:
    """Open loop: the offsets (s) from the window's start at which each
    request is sent; bursts at exponential gaps drawn from the seed."""
    arr = mix["arrival"]
    burst = int(arr.get("burst", 1))
    mean_gap = burst / float(arr["rate_per_s"])
    rng = random.Random(seed + 3)
    out: List[float] = []
    t = rng.expovariate(1.0 / mean_gap)
    while t < seconds:
        out += [t] * burst
        t += rng.expovariate(1.0 / mean_gap)
    return out
