"""The trace reduction and the readers on it: busy time as the union of
device operations, idle share, kernel and collective time by name, time
per named scope, idle gaps named by the host span open in them, and the
host's time per verdict from the program's spans."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent.parent / "src"))

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


# the same chip 0 as DEVICE, its ops carrying the scopes of their op_names,
# and a while loop that encloses two of them
SCOPED = {
    "/device:TPU:0": [("%while.5", 0, 150, None),
                      ("fusion.1", 0, 100, "walk.pick.break"),
                      ("flip_update_kernel.2", 50, 100, "walk.flip"),
                      ("all-gather.3", 300, 50, "walk.pick.clause"),
                      ("late.4", 990, 100, None)],
    "/device:TPU:1": [("fusion.1", 0, 400, "walk.pick.break")],
}
# what the reducer gave for DEVICE and HOST before it read scopes
BEFORE_SCOPES = {
    "busy_s": 3.05e-07, "window_s": 1e-06,
    "op_s": {"fusion.1": 5e-07, "flip_update_kernel.2": 1e-07,
             "all-gather.3": 5e-08, "late.4": 1e-08},
    "idle_gaps": [["encode", 6.4e-07], ["solve_window", 1.5e-07]]}
EXISTING = ("walk_decided_share", "walk_ms_per_step",
            "flip_update_ms_per_step", "flip_update_roofline",
            "device_idle_pct", "compiles_in_window", "padded_clause_share")


def _walk_ctx(trace, spans=()):
    att = SimpleNamespace
    served = [{"res": att(ii=4, attempts=[att(
        ii=4, status="SAT", via="walksat", walk_steps=40, walk_rows=900,
        walk_rows_padded=1024)])}]
    segs = [{"steps": 10, "wall": 0.02, "K": 1, "B": 32, "O": 192},
            {"steps": 30, "wall": 0.06, "K": 1, "B": 32, "O": 192}]
    peaks = {"hbm_bytes_per_s": 819e9, "int8_op_per_s": 394e12}
    work = _work_module()
    return SimpleNamespace(
        trace=trace, segments=segs, served=served, spans=list(spans),
        t_start=0.0, t_end=1.0, peaks=lambda: peaks, work=lambda _: work,
        probes=SimpleNamespace(compiles_between=lambda a, b: 0))


def _work_module():
    spec = importlib.util.spec_from_file_location(
        "flip_update_work", HERE / "work" / "flip_update.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scopes_move_no_existing_metric():
    """The reduction with scoped ops keeps every field it had, and the
    cell's seven older per-layer metrics read what they read before."""
    plain = reduce_events(DEVICE, HOST, (0, 1000))
    scoped = reduce_events(SCOPED, HOST, (0, 1000))
    # the while loop is a container: it adds no busy time, and its own
    # name is the only new op
    assert {k: v for k, v in scoped["op_s"].items() if k != "%while.5"} \
        == pytest.approx(plain["op_s"])
    for red in (plain, scoped):
        assert red["busy_s"] == pytest.approx(BEFORE_SCOPES["busy_s"])
        assert red["window_s"] == BEFORE_SCOPES["window_s"]
        assert red["idle_gaps"] == [[n, pytest.approx(s)] for n, s in
                                    BEFORE_SCOPES["idle_gaps"]]
    before = _walk_ctx(BEFORE_SCOPES)
    for name in EXISTING:
        want = _reader(name)(before)
        assert want is not None, name
        assert _reader(name)(_walk_ctx(plain)) == pytest.approx(want), name
        assert _reader(name)(_walk_ctx(scoped)) == pytest.approx(want), name


def test_scope_seconds_of_the_reduction():
    red = reduce_events(SCOPED, HOST, (0, 1000))
    # chip 0's while encloses fusion.1 and flip_update: a container;
    # late.4 is clipped to 10 ns and has no scope; chip 1 adds 400 ns
    assert red["scope_s"] == {"walk.pick.break": pytest.approx(500e-9),
                              "walk.flip": pytest.approx(100e-9),
                              "walk.pick.clause": pytest.approx(50e-9)}
    assert reduce_events(DEVICE, HOST, (0, 1000))["scope_s"] == {}


def test_pick_ms_per_step_sums_the_pick_scopes_over_the_steps():
    red = reduce_events(SCOPED, HOST, (0, 1000))
    assert _reader("pick_ms_per_step")(_walk_ctx(red)) == \
        pytest.approx(1e3 * 550e-9 / 40)


@pytest.mark.parametrize("trace, steps", [
    (None, 40), ({"scope_s": {"walk.pick.break": 1.0}}, 0),
    ({"scope_s": {"walk.flip": 1.0}}, 40), ({"scope_s": {}}, 40)],
    ids=["no-trace", "no-steps", "no-pick-scope", "no-scope"])
def test_pick_ms_per_step_is_silent_without_pick_time_or_steps(trace, steps):
    ctx = SimpleNamespace(trace=trace, segments=[{"steps": steps}])
    assert _reader("pick_ms_per_step")(ctx) is None


def _span(sid, name, request, start, end, parent=None, thread=1):
    """A record as ``repro.core.spans.drain`` gives it."""
    from repro.core.spans import Span
    return Span(sid, parent, name, request, thread, start, end)


def test_host_ms_per_verdict_subtracts_each_requests_walk_segments():
    spans = [
        # request 1: 100 ms, two overlapping segments in the racer thread
        # (union 50 ms) and one that runs past the request's end
        _span(1, "service.map", 1, 0.0, 0.100),
        _span(2, "walk.segment", 1, 0.010, 0.040, 1, thread=2),
        _span(3, "walk.segment", 1, 0.030, 0.060, 1, thread=2),
        _span(4, "walk.segment", 1, 0.090, 0.120, 1, thread=2),
        _span(5, "walk.pack", 1, 0.005, 0.010, 1),
        # request 2: 40 ms, no walk; another request's segment inside it
        _span(6, "service.map", 2, 0.200, 0.240),
        _span(7, "walk.segment", 3, 0.210, 0.230),
    ]
    host = (0.100 - 0.050 - 0.010) + 0.040
    assert _reader("host_ms_per_verdict")(_walk_ctx(None, spans)) == \
        pytest.approx(1e3 * host / 2)


@pytest.mark.parametrize("spans", [
    [], [_span(1, "walk.segment", 1, 0.0, 0.1)]],
    ids=["recorder-off", "no-request-span"])
def test_host_ms_per_verdict_is_silent_without_request_spans(spans):
    assert _reader("host_ms_per_verdict")(_walk_ctx(None, spans)) is None


def test_traced_walk_cell_reports_host_ms_per_verdict(capsys, monkeypatch):
    """A traced run of the walk cell off the chip records the program's
    spans for its window only and reports the host's time per verdict; a
    CPU trace has no TPU plane, so ``pick_ms_per_step`` is left out."""
    from chipbench import traffic
    from repro.core import spans
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  HERE / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    load = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix",
                        lambda name: dict(load(name), requests=4))
    rc = run.main(["--workload", "suite5x5-portfolio.walk", "--seed",
                   str(2**31 + 21), "--seconds", "2", "--trace", "1"],
                  require_chip=False, ref_workers=0)
    assert rc == 0
    std = capsys.readouterr()
    out = json.loads(std.out.strip().splitlines()[-1])
    assert out["correct"]
    assert out["metrics"]["host_ms_per_verdict"]["value"] > 0
    assert "pick_ms_per_step" not in out["metrics"]
    assert "[spans] recorded=" in std.err and " dropped=0" in std.err
    assert not spans.enabled()
