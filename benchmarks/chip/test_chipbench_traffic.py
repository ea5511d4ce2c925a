"""The traffic generator: deterministic per seed, every request of a run
distinct as a loop, the walk mix one fixed list in one order, and
the arrival, repeat and fabric parts of a mix read from its data."""
import json
import random
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import graphs, reference, traffic  # noqa: E402

FAB = reference.fabric_from_config({"rows": 5, "cols": 5, "regs": 4})


def test_fresh_stream_is_deterministic_and_distinct():
    mix = traffic.load_mix("fresh")
    a = traffic.distinct(traffic.suite_mutant_stream(mix, 2**31 + 7), 200)
    assert len({n.split("~")[0] for n, _ in a[:11]}) == 11
    b = traffic.distinct(traffic.suite_mutant_stream(mix, 2**31 + 7), 200)
    c = traffic.distinct(traffic.suite_mutant_stream(mix, 5), 200)
    assert a == b and a != c
    keys = {graphs.canonical_key(g) for _, g in a}
    assert len(keys) == len(a)
    # distinct as loops implies distinct under the service's request key,
    # which reads ops, constants and operands by node id
    assert len({g for _, g in a}) == len(a)
    assert all(graphs.validate(g) is None for _, g in a)


def test_fresh_edits_keep_the_mix_kinds():
    mix = traffic.load_mix("fresh")
    names = [n for n, _ in traffic.distinct(
        traffic.suite_mutant_stream(mix, 3), 300)]
    kinds = {k.split("#")[0] for n in names for k in n.split("~")[1:]}
    assert kinds == set(mix["kinds"])
    assert all(1 <= len(n.split("#")[0].split("~")) - 1 <= 3 for n in names)


def test_walk_mix_is_one_set_that_the_seed_orders():
    walk = traffic.load_mix("walk")
    assert len(traffic.requests(walk, 11)) == walk["requests"]
    # one fixed order, whatever the seed
    assert traffic.requests(walk, 11) == traffic.requests(walk, 2**31 + 1)
    a = traffic.requests(dict(walk, requests=10**6), 5)    # the whole list
    data = json.loads((HERE / "data" / "walk_loops.json").read_text())
    assert sorted(r.name for r in a) \
        == sorted(e["name"] for e in data["loops"])
    assert len({graphs.canonical_key(r.graph) for r in a}) == len(a)
    # each kernel's share along the list is even
    share = {}
    for r in a:
        share[r.name.split("~")[0]] = share.get(r.name.split("~")[0], 0) + 1
    head = [r.name.split("~")[0] for r in a[:40]]
    for kernel, n in share.items():
        assert abs(head.count(kernel) - 40 * n / len(a)) <= 1.5
    for r in a[:4]:
        assert r.ii == FAB.mii(r.graph)
        assert reference.kms_feasible(r.graph, FAB, r.ii) is True


def test_walk_mix_offers_288_and_keeps_the_first_96_in_order():
    """Raising ``requests`` from 96 only lengthens the list: the loops a
    window reached at 96 come first, name for name and in order."""
    walk = traffic.load_mix("walk")
    data = json.loads((HERE / "data" / "walk_loops.json").read_text())
    assert walk["requests"] == 288 <= len(data["loops"]) == 480
    now = traffic.requests(walk, 2**31 + 13)
    assert len(now) == 288
    before = traffic.requests(dict(walk, requests=96), 2**31 + 13)
    assert [r.name for r in now[:96]] == [r.name for r in before]
    assert now[:96] == before


def test_open_loop_arrivals_are_seeded_bursts():
    mix = traffic.check_mix({"source": "suite_mutants", "requests": 10,
                             "arrival": {"kind": "open", "rate_per_s": 20,
                                         "burst": 4}})
    a = traffic.arrival_times(mix, 2**31 + 9, 30)
    assert a == traffic.arrival_times(mix, 2**31 + 9, 30)
    assert a != traffic.arrival_times(mix, 3, 30)
    assert a == sorted(a) and all(0 <= t < 30 for t in a)
    assert len(a) % 4 == 0 and all(len(set(a[i:i + 4])) == 1
                                   for i in range(0, len(a), 4))
    assert 400 <= len(a) <= 800


def test_repeat_mix_draws_the_window_from_its_working_set():
    mix = traffic.check_mix({"source": "suite_mutants", "repeat": 5,
                             "requests": 60,
                             "arrival": {"kind": "closed", "clients": 2}})
    ws = traffic.warm_requests(mix, 17)
    reqs = traffic.requests(mix, 17)
    assert len(ws) == 5 and len(reqs) == 60
    assert {r.graph for r in reqs} <= {r.graph for r in ws}
    assert len({r.graph for r in reqs}) > 1


def test_fabrics_are_drawn_per_request():
    spec = {"name": "5x5:r2:mul2:mem2", "rows": 5, "cols": 5, "regs": 2,
            "latency": {"alu": 1, "mem": 2, "mul": 2}}
    mix = traffic.check_mix({"source": "suite_mutants", "requests": 40,
                             "fabrics": {"5x5": dict(spec, regs=4,
                                                     latency={}),
                                         "5x5:r2:mul2:mem2": spec},
                             "arrival": {"kind": "closed", "clients": 1}})
    got = [r.fabric for r in traffic.requests(mix, 4)]
    assert set(got) == {"5x5", "5x5:r2:mul2:mem2"}
    assert got == [r.fabric for r in traffic.requests(mix, 4)]


@pytest.mark.parametrize("bad", [
    {"source": "nowhere"},
    {"source": "grammar", "arrival": {"kind": "sometimes"}},
    {"source": "grammar", "arrival": {"kind": "closed", "clients": 0}},
    {"source": "grammar", "arrival": {"kind": "closed", "clients": 1},
     "clients": 4},
])
def test_a_mix_with_unknown_parts_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.check_mix(dict(bad, requests=1))


def test_canonical_key_ignores_node_numbering():
    g = traffic.suite_kernels()["sha"]
    order = list(range(len(g)))
    random.Random(1).shuffle(order)
    h = graphs.relabel(g, order)
    assert h != g
    assert graphs.canonical_key(h) == graphs.canonical_key(g)
    assert graphs.canonical_key(traffic.mutate(g, random.Random(2), "op")) \
        != graphs.canonical_key(g)


@pytest.mark.parametrize("name", sorted(traffic.suite_kernels()))
def test_suite_kernels_execute_and_have_a_bound(name):
    g = traffic.suite_kernels()[name]
    graphs.validate(g)
    hist, _ = graphs.execute(g, 3)
    assert len(hist) == 3 and FAB.mii(g) >= 1


def test_grammar_source_is_deterministic_and_valid():
    mix = traffic.check_mix({"source": "grammar", "requests": 30,
                             "arrival": {"kind": "closed", "clients": 1}})
    a = traffic.requests(mix, 2**31 + 3)
    assert a == traffic.requests(mix, 2**31 + 3)
    assert a != traffic.requests(mix, 4)
    assert len({graphs.canonical_key(r.graph) for r in a}) == 30
    for r in a:
        graphs.validate(r.graph)
        assert 6 <= len(r.graph) <= 18 and FAB.mii(r.graph) >= 1
