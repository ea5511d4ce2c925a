"""BENCHMARK.json against the contract's naming rules, and every name in
it backed by the file the harness finds it by."""
import importlib.util
import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("name", [m["name"] for m in METRICS]
                         + [w["name"] for w in BENCH["workloads"]]
                         + [c["name"] for c in BENCH["configs"]]
                         + [w["traffic"] for w in BENCH["workloads"]])
def test_names(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_units_and_sources(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in [m["name"] for m in BENCH["end_to_end"]]


def test_every_name_has_its_file():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["config"] in [c["name"] for c in BENCH["configs"]]
    for m in BENCH["per_layer"]:
        spec = importlib.util.spec_from_file_location(
            m["name"], HERE / "metrics" / f"{m['name']}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)


def test_each_per_layer_metric_lists_cells_that_report_what_it_moves():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", [cell])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        mine = [m["name"] for m in BENCH["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in BENCH["per_layer"])


@pytest.mark.parametrize("path", sorted(
    str(p.relative_to(HERE)) for d in ("traffic", "configs", "metrics", "work")
    for p in (HERE / d).iterdir() if p.suffix in (".json", ".py")))
def test_every_file_of_a_kind_loads(path):
    """Files kept for cells that wait under Open questions load too."""
    import sys
    sys.path.insert(0, str(HERE))
    from chipbench import reference, traffic
    f = HERE / path
    kind = f.parent.name
    if kind == "traffic":
        assert NAME.match(f.stem)
        traffic.check_mix(json.loads(f.read_text()), path)
    elif kind == "configs":
        cfg = json.loads(f.read_text())
        assert cfg["name"] == f.stem and NAME.match(f.stem)
        assert reference.fabric_from_config(cfg["fabric"]).n_pes > 0
    else:
        spec = importlib.util.spec_from_file_location(f.stem, f)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(getattr(mod, "read" if kind == "metrics" else "work"))
