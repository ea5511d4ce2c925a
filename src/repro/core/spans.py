"""Host spans at the mapper's layer boundaries.

``span(name)`` marks one piece of host work: a request through the
service (``service.map``), an II's encoding (``map.encode``), a walk's
pack, upload, device segments and model extraction (``walk.*``), decode,
register allocation, verification and the CDCL solve. Recording is off by
default, and then a span costs one flag check and records nothing.
:func:`enable` turns it on: each span then opens a
``jax.profiler.TraceAnnotation``, so that it sits on the device trace's
clock in a profiler capture, and is kept in a bounded in-memory buffer
that :func:`drain` empties.

A record holds the span's name, start and end (``time.perf_counter``
seconds), its own id and its parent's (the innermost span open in the
same thread, or the one handed over with :func:`handoff`), the thread
and a request id. Spans of one request share the id, which
``MappingService.map`` sets; a thread that a request starts (the
portfolio's walk racer) is given it explicitly with :func:`handoff` and
:func:`adopt`. When the buffer is full, new spans are dropped and
counted (:func:`dropped`).

This module imports nothing of jax at module scope: worker shards fork
from modules that import it (``python -m repro.analysis`` checks the
import chain). ``jax.profiler`` is imported when recording is turned on.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

DEFAULT_CAPACITY = 1 << 16


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    request: Optional[int]
    thread: int
    start: float
    end: float


_NULL = nullcontext()
_ON = False
_LOCK = threading.Lock()
_BUF: List[Span] = []
_CAPACITY = DEFAULT_CAPACITY
_DROPPED = 0
_IDS = itertools.count(1)
_REQUESTS = itertools.count(1)
_LOCAL = threading.local()
_ANNOTATION = None          # jax.profiler.TraceAnnotation once enabled


def enable(on: bool = True, capacity: int = DEFAULT_CAPACITY) -> None:
    """Turn span recording on (with a buffer of ``capacity`` spans) or
    off. Turning it on empties the buffer and the dropped count."""
    global _ON, _CAPACITY, _DROPPED, _ANNOTATION
    if on and _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    with _LOCK:
        if on:
            _BUF.clear()
            _CAPACITY = int(capacity)
            _DROPPED = 0
        _ON = bool(on)


def enabled() -> bool:
    return _ON


def drain() -> List[Span]:
    """The spans recorded since the last drain, in the order they ended;
    the buffer is emptied."""
    with _LOCK:
        out = list(_BUF)
        _BUF.clear()
    return out


def dropped() -> int:
    """Spans dropped because the buffer was full, since :func:`enable`."""
    with _LOCK:
        return _DROPPED


def _stack() -> List[Tuple[int, Optional[int]]]:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def handoff() -> Tuple[Optional[int], Optional[int]]:
    """(request id, span id) of the innermost span open in this thread:
    what a thread that this one starts should :func:`adopt`."""
    st = _stack()
    if st:
        return st[-1][1], st[-1][0]
    return getattr(_LOCAL, "base", (None, None))


@contextmanager
def adopt(token: Tuple[Optional[int], Optional[int]]) -> Iterator[None]:
    """Run this thread's spans under the request and parent span that
    another thread's :func:`handoff` returned."""
    prev = getattr(_LOCAL, "base", (None, None))
    _LOCAL.base = token
    try:
        yield
    finally:
        _LOCAL.base = prev


class _Open:
    __slots__ = ("name", "root", "id", "parent", "rid", "start", "ann")

    def __init__(self, name: str, root: bool):
        self.name = name
        self.root = root

    def __enter__(self):
        rid, parent = handoff()
        if self.root:
            rid = next(_REQUESTS)
        self.id, self.parent, self.rid = next(_IDS), parent, rid
        self.ann = _ANNOTATION(self.name)
        self.ann.__enter__()
        _stack().append((self.id, rid))
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _DROPPED
        end = time.perf_counter()
        _stack().pop()
        self.ann.__exit__(*exc)
        rec = Span(self.id, self.parent, self.name, self.rid,
                   threading.get_ident(), self.start, end)
        with _LOCK:
            if len(_BUF) < _CAPACITY:
                _BUF.append(rec)
            else:
                _DROPPED += 1
        return False


def span(name: str, request: bool = False):
    """Context manager marking one piece of host work as ``name``. With
    ``request`` the span starts a new request id (a request's root span);
    otherwise it carries the request of the span it is opened in."""
    if not _ON:
        return _NULL
    return _Open(name, request)


def self_seconds(spans: List[Span]) -> Dict[int, float]:
    """Each span's self time: its duration less the part of it that its
    child spans cover (children of one parent may overlap, as a racer
    thread's do; their union is subtracted)."""
    kids: Dict[int, List[Tuple[float, float]]] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append((s.start, s.end))
    out = {}
    for s in spans:
        covered, cursor = 0.0, s.start
        for a, b in sorted(kids.get(s.id, [])):
            a, b = max(a, cursor), min(b, s.end)
            if b > a:
                covered += b - a
                cursor = b
        out[s.id] = (s.end - s.start) - covered
    return out
