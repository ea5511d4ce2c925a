"""The harness end to end off the chip: it refuses a backend that is not
a TPU, a sound run comes out correct, and a run with each fault planted
under the timed path (the control among them) comes out not correct."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import faults, traffic  # noqa: E402

# loaded by path: another directory's run.py may sit on sys.path too
_spec = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)


# the host-bound sweep cell waits under Open questions (its window runs
# nothing on the device); its files stay, and these tests drive it
SWEEP_CELL = {"name": "suite5x5-sweep.fresh", "config": "suite5x5-sweep",
              "traffic": "fresh", "chips": 1, "why": "test"}
SWEEP_CONFIG = {"name": "suite5x5-sweep", "reduced": [], "why": "test",
                "source": "https://arxiv.org/abs/2512.02875",
                "file": "benchmarks/chip/configs/suite5x5-sweep.json"}


@pytest.fixture(autouse=True)
def with_sweep_cell(monkeypatch):
    load = run.load_bench

    def bench():
        b = load()
        if SWEEP_CELL["name"] not in {w["name"] for w in b["workloads"]}:
            b["workloads"].append(SWEEP_CELL)
        if SWEEP_CONFIG["name"] not in {c["name"] for c in b["configs"]}:
            b["configs"].append(SWEEP_CONFIG)
        return b
    monkeypatch.setattr(run, "load_bench", bench)


def test_refuses_a_backend_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "suite5x5-portfolio.walk", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def _run(capsys, monkeypatch, workload, seconds, clients=None, **mix):
    if clients is not None:
        mix["arrival"] = {"kind": "closed", "clients": clients}
    if mix:
        load = traffic.load_mix
        monkeypatch.setattr(traffic, "load_mix",
                            lambda name: dict(load(name), **mix))
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 5),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_chip=False, ref_workers=0)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


@pytest.mark.parametrize("fault", [None] + list(faults.FAULTS))
def test_sweep_cell_correct_only_without_a_fault(fault, capsys, monkeypatch):
    undo = faults.plant(fault) if fault else (lambda: None)
    try:
        out = _run(capsys, monkeypatch, "suite5x5-sweep.fresh", 2,
                   clients=2)
    finally:
        undo()
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert out["attempted"] >= 1
    if fault is None:
        assert out["correct"], checks
        want = {m["name"] for m in run.load_cell(SWEEP_CELL["name"])
                ["end_to_end"]}
        assert set(out["metrics"]) == want and "setup_s" in want
    else:
        want = {"control": "unproven_ii", "alter": "bad_placement",
                "lose": "lost"}[fault]
        assert not out["correct"] and checks[want] > 0, checks


@pytest.mark.parametrize("fault", [None, "control", "alter"])
def test_walk_cell_correct_only_without_a_fault(fault, capsys, monkeypatch):
    undo = faults.plant(fault) if fault else (lambda: None)
    try:
        out = _run(capsys, monkeypatch, "suite5x5-portfolio.walk", 2,
                   clients=1, requests=6)
    finally:
        undo()
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert out["attempted"] >= 1
    if fault is None:
        assert out["correct"], checks
    else:
        # the control answers at MII + 1 where the list says MII
        want = {"control": "ii_mismatch", "alter": "bad_placement"}[fault]
        assert not out["correct"] and checks[want] > 0, checks


def test_open_loop_repeats_on_two_fabrics(capsys, monkeypatch):
    """A mix that is data only: open-loop bursts with deadlines, a working
    set served in set-up, and a fabric drawn per request."""
    slow = {"name": "5x5:r2:mul2:mem2", "rows": 5, "cols": 5, "regs": 2,
            "latency": {"alu": 1, "mem": 2, "mul": 2}}
    out = _run(capsys, monkeypatch, "suite5x5-sweep.fresh", 2,
               repeat=3, requests=400, warm=None,
               arrival={"kind": "open", "rate_per_s": 20, "burst": 2,
                        "deadline_s": 30},
               fabrics={"5x5:r2:mul2:mem2": slow})
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], checks
    assert out["attempted"] >= 10
