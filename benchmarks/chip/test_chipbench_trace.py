"""The trace reduction and the readers on it: busy time as the union of
device operations, idle share, kernel and collective time by name, and
idle gaps named by the host span open in them."""
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench.trace import reduce_events, union  # noqa: E402

# two chips over a 1000 ns window; chip 0 overlaps two ops
DEVICE = {
    "/device:TPU:0": [("fusion.1", 0, 100),
                      ("flip_update_kernel.2", 50, 100),
                      ("all-gather.3", 300, 50),
                      ("late.4", 990, 100)],       # clipped at the window
    "/device:TPU:1": [("fusion.1", 0, 400)],
}
HOST = [("solve_window", 0, 1000), ("encode", 600, 100)]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_union_merges_overlaps_only():
    assert union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_reduce_events_by_hand():
    red = reduce_events(DEVICE, HOST, (0, 1000))
    # chip 0: [0,150] + [300,350] + [990,1000] = 210 ns; chip 1: 400 ns
    assert red["busy_s"] == pytest.approx((210 + 400) / 2 / 1e9)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["op_s"]["fusion.1"] == pytest.approx(500e-9)
    assert red["op_s"]["flip_update_kernel.2"] == pytest.approx(100e-9)
    assert red["op_s"]["late.4"] == pytest.approx(10e-9)
    # chip 0's gaps: [350,990] (mid 670, inside "encode"), [150,300]
    assert red["idle_gaps"] == [["encode", pytest.approx(640e-9)],
                                ["solve_window", pytest.approx(150e-9)]]


def test_readers_on_the_reduced_trace():
    red = reduce_events(DEVICE, HOST, (0, 1000))
    segs = [{"steps": 10, "wall": 0.02, "K": 4, "B": 24, "O": 192},
            {"steps": 30, "wall": 0.06, "K": 4, "B": 24, "O": 192}]
    peaks = {"hbm_bytes_per_s": 819e9, "int8_op_per_s": 394e12}

    def work(k, b, o):
        return k * b * o, k * b * (13 * o + 1)
    ctx = SimpleNamespace(trace=red, segments=segs, peaks=lambda: peaks,
                          work=lambda _: SimpleNamespace(work=work))
    idle = _reader("device_idle_pct")(ctx)
    assert idle == pytest.approx(100 * (1 - 305 / 1000))
    assert _reader("flip_update_ms_per_step")(ctx) == \
        pytest.approx(1e3 * 100e-9 / 40)
    assert _reader("collective_ms_per_step")(ctx) == \
        pytest.approx(1e3 * 50e-9 / 40)
    assert _reader("walk_ms_per_step")(ctx) == pytest.approx(2.0)
    least = 40 * 96 * 2497 / 819e9
    assert _reader("flip_update_roofline")(ctx) == \
        pytest.approx(100 * least / 100e-9)


def test_readers_return_nothing_without_a_trace_or_steps():
    ctx = SimpleNamespace(trace=None, segments=[])
    for name in ("device_idle_pct", "flip_update_ms_per_step",
                 "flip_update_roofline", "collective_ms_per_step",
                 "walk_ms_per_step"):
        assert _reader(name)(ctx) is None
