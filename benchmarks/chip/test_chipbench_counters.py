"""The program's walk counters on each attempt (``IIAttempt.walk_*``) and
the reader of them, ``padded_clause_share``: the counters equal what the
probes count from outside, the reader weighs each walked attempt by its
steps and falls silent on a program without the counters, and a traced
run of the walk cell reports it."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import traffic  # noqa: E402

# loaded by path: another directory's run.py may sit on sys.path too
_spec = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_attempt_walk_counters_equal_the_probes():
    """Steps, segments and padded rows on each attempt equal the probes'
    count on a portfolio run of the walk cell's first loops."""
    from chipbench.probes import Probes
    from repro.core.arch import arch
    from repro.core.mapper import MapperConfig
    from repro.core.service import MappingService
    items = traffic.requests(traffic.load_mix("walk"), 7)[:2]
    svc, fabric = MappingService(), arch("5x5")
    probes = Probes(trace=False)
    probes.install()
    try:
        results = [svc.map(run.to_program(it.graph, it.name), fabric,
                           MapperConfig(solver="portfolio"))
                   for it in items]
    finally:
        probes.uninstall()
    walked = [a for r in results for a in r.attempts if a.walk_steps]
    assert walked and probes.segments
    assert sum(a.walk_steps for a in walked) == \
        sum(s["steps"] for s in probes.segments)
    assert sum(a.walk_segments for a in walked) == len(probes.segments)
    assert {a.walk_rows_padded for a in walked} == \
        {s["C"] for s in probes.segments}
    assert all(a.walk_rows <= a.walk_rows_padded for a in walked)


def test_padded_clause_share_weighs_attempts_by_their_steps():
    att = SimpleNamespace
    served = [{"res": att(attempts=[
        att(walk_steps=100, walk_rows=900, walk_rows_padded=1024),
        att(walk_steps=None, walk_rows=None, walk_rows_padded=None)])},
        {"res": att(attempts=[
            att(walk_steps=300, walk_rows=2000, walk_rows_padded=2048)])}]
    real, padded = 100 * 900 + 300 * 2000, 100 * 1024 + 300 * 2048
    assert _reader("padded_clause_share")(SimpleNamespace(served=served)) \
        == pytest.approx(100 * (1 - real / padded))


@pytest.mark.parametrize("served", [
    [{"res": SimpleNamespace(attempts=[SimpleNamespace(ii=3)])}],
    [{"res": SimpleNamespace(attempts=[SimpleNamespace(
        walk_steps=None, walk_rows=None, walk_rows_padded=None)])}],
    []], ids=["program-without-counters", "nothing-walked", "no-verdict"])
def test_padded_clause_share_is_silent_without_walk_counters(served):
    assert _reader("padded_clause_share")(
        SimpleNamespace(served=served)) is None


def test_traced_walk_cell_reports_padded_clause_share(capsys, monkeypatch):
    """A traced run of the walk cell off the chip reports the new metric
    beside the older ones, and every verdict is correct."""
    load = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix",
                        lambda name: dict(load(name), requests=4))
    rc = run.main(["--workload", "suite5x5-portfolio.walk", "--seed",
                   str(2**31 + 9), "--seconds", "2", "--trace", "1"],
                  require_chip=False, ref_workers=0)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    assert 0 <= out["metrics"]["padded_clause_share"]["value"] < 100
    assert "walk_decided_share" in out["metrics"]
