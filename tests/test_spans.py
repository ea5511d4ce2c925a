"""Host spans (``repro.core.spans``) and the walk counters on the attempt
record: nesting and parent links, self time, request ids across a racer
thread, recording off, the bounded buffer, and the layer spans of one
served request."""
import pickle
import threading
import time

import pytest

from repro.core import spans, suite
from repro.core.cgra import CGRA
from repro.core.encode import EncoderSession
from repro.core.mapper import IIAttempt, MapperConfig
from repro.core.sat.portfolio import solve_window
from repro.core.schedule import min_ii
from repro.core.service import MappingService


@pytest.fixture
def recording():
    spans.enable()
    try:
        yield
    finally:
        spans.enable(False)
        spans.drain()


def _by_name(recorded):
    out = {}
    for s in recorded:
        out.setdefault(s.name, []).append(s)
    return out


def test_nesting_parent_links_and_self_time(recording):
    with spans.span("a", request=True):
        time.sleep(0.02)
        with spans.span("b"):
            time.sleep(0.03)
            with spans.span("c"):
                time.sleep(0.01)
        with spans.span("d"):
            time.sleep(0.01)
    got = _by_name(spans.drain())
    a, b, c, d = (got[n][0] for n in "abcd")
    assert a.parent is None
    assert b.parent == a.id and d.parent == a.id and c.parent == b.id
    assert a.request is not None
    assert {b.request, c.request, d.request} == {a.request}
    assert a.start <= b.start <= c.start <= c.end <= b.end <= d.start \
        <= d.end <= a.end
    own = spans.self_seconds([a, b, c, d])
    assert own[a.id] == pytest.approx(
        (a.end - a.start) - (b.end - b.start) - (d.end - d.start))
    assert own[b.id] == pytest.approx((b.end - b.start) - (c.end - c.start))
    assert own[c.id] == pytest.approx(c.end - c.start)
    assert own[a.id] >= 0.015


def test_self_time_subtracts_the_union_of_overlapping_children():
    S = spans.Span
    root = S(1, None, "r", 1, 0, 0.0, 10.0)
    kids = [S(2, 1, "x", 1, 0, 1.0, 5.0), S(3, 1, "y", 1, 1, 3.0, 7.0),
            S(4, 1, "z", 1, 1, 9.0, 12.0)]       # runs past its parent
    assert spans.self_seconds([root] + kids)[1] == pytest.approx(10 - 6 - 1)


def test_each_request_root_gets_its_own_id(recording):
    for _ in range(2):
        with spans.span("service.map", request=True):
            with spans.span("map.encode"):
                pass
    got = _by_name(spans.drain())
    r1, r2 = (s.request for s in got["service.map"])
    assert r1 != r2
    assert sorted(s.request for s in got["map.encode"]) == sorted([r1, r2])


def test_request_id_carried_into_a_thread(recording):
    def work(token):
        with spans.adopt(token):
            with spans.span("walk.segment"):
                pass
        with spans.span("unadopted"):
            pass

    with spans.span("service.map", request=True):
        t = threading.Thread(target=work, args=(spans.handoff(),))
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    got = _by_name(spans.drain())
    root, seg = got["service.map"][0], got["walk.segment"][0]
    assert seg.request == root.request and seg.parent == root.id
    assert seg.thread != root.thread
    assert got["unadopted"][0].request is None


def test_off_records_nothing():
    spans.enable(False)
    spans.drain()
    with spans.span("service.map", request=True):
        with spans.span("walk.segment"):
            pass
    assert spans.drain() == []
    assert not spans.enabled()


def test_the_buffer_is_bounded_and_counts_what_it_drops():
    spans.enable(capacity=3)
    try:
        for _ in range(5):
            with spans.span("walk.extract"):
                pass
        assert len(spans.drain()) == 3
        assert spans.dropped() == 2
        spans.enable(capacity=3)       # turning it on starts afresh
        assert spans.dropped() == 0
    finally:
        spans.enable(False)
        spans.drain()


def test_racer_thread_spans_carry_the_request(recording):
    """The portfolio's walk racer runs in its own thread: its spans carry
    the request id of the window that started it."""
    g = suite.get("sha")
    cgra = CGRA(3, 3)
    mii = max(min_ii(g, cgra), 1)
    sess = EncoderSession(g, cgra)
    cnfs = [sess.encode(ii).cnf for ii in range(mii, mii + 2)]
    with spans.span("service.map", request=True):
        solve_window(cnfs, method="portfolio", seed=3, walksat_delay=0.0,
                     walksat_steps=256, walksat_batch=4)
        me = threading.get_ident()
    recorded, deadline = [], time.time() + 120
    while time.time() < deadline:      # the racer is left unjoined
        recorded += spans.drain()
        if "walk.upload" in _by_name(recorded):
            break
        time.sleep(0.05)
    got = _by_name(recorded)
    root = got["service.map"][0]
    racer = got.get("walk.upload", []) + got.get("walk.pack", [])
    assert racer and all(s.request == root.request for s in racer)
    assert all(s.thread != me for s in racer)


def test_a_served_request_names_its_layers(recording):
    """One portfolio request through the service: a root span, the
    layers below it under the same request, and the walk counters on the
    attempt that walked."""
    g = suite.get("srand")
    res = MappingService().map(g, CGRA(3, 3), MapperConfig(
        solver="portfolio"), use_cache=False)
    assert res.success
    got = _by_name(spans.drain())
    (root,) = got["service.map"]
    for name in ("map.encode", "walk.pack", "walk.upload", "walk.segment",
                 "walk.extract", "map.decode", "map.regalloc", "map.verify"):
        assert name in got, name
        assert all(s.request == root.request for s in got[name]), name
        assert all(root.start <= s.start <= s.end <= root.end
                   for s in got[name]), name
    walked = [a for a in res.attempts if a.walk_steps]
    assert walked
    assert sum(a.walk_segments for a in walked) == len(got["walk.segment"])
    for a in walked:
        assert 0 < a.walk_rows <= a.walk_rows_padded
        assert a.walk_rows_padded % 1024 == 0
        assert a.walk_break_cached == a.walk_steps


def test_attempts_pickled_without_walk_counters_still_load():
    att = IIAttempt(ii=3, n_vars=10, n_clauses=20, status="SAT",
                    solve_time=0.1, encode_time=0.01)
    for name in ("walk_steps", "walk_segments", "walk_rows",
                 "walk_rows_padded", "walk_break_cached"):
        del att.__dict__[name]        # as an attempt of an older program
    old = pickle.loads(pickle.dumps(att))
    assert old.walk_steps is None and old.walk_rows_padded is None
    assert getattr(old, "walk_rows", None) is None
    assert getattr(old, "walk_break_cached", None) is None


def test_the_span_module_imports_no_jax():
    """Worker shards fork from modules that import it."""
    import subprocess
    import sys
    code = ("import sys; import repro.core.spans; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr
