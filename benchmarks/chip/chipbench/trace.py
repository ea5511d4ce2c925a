"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time (the union of operation intervals per chip), time
per operation name, time per named scope of the leaf operations, and the
longest idle gaps named by the host span that was open in them.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes
are those named ``/device:TPU:<n>``; their operations are the events of
the ``XLA Ops`` line (``XLA Modules`` where a plane has no op line), each
with the scope of its HLO op_name (``scopes.py``). Host spans are the
events of the ``/host:CPU`` plane, where ``TraceAnnotation`` writes them
on the same clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

from .scopes import op_names, scope_of, scope_seconds

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_events(device_ops: Dict[str, List[tuple]],
                  host_spans: List[Tuple[str, float, float]],
                  window: Interval) -> dict:
    """``device_ops``: chip -> [(op name, start_ns, dur_ns[, scope])];
    ``host_spans``: [(span name, start_ns, dur_ns)]; ``window``: the traced
    interval in ns. Returns busy and window seconds (busy averaged over
    chips), seconds per op name (summed over chips), seconds per scope of
    the leaf ops (``scopes.scope_seconds``; an op without one counts in
    none), and the ten longest idle gaps of the first chip named by the
    innermost host span covering each gap's midpoint."""
    w0, w1 = window
    busy = []
    by_name: Dict[str, float] = {}
    first_busy: List[Interval] = []
    for k, chip in enumerate(sorted(device_ops)):
        ivs = []
        for name, s, d, *_ in device_ops[chip]:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
        merged = union(ivs)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if k == 0:
            first_busy = merged
    gaps = []
    cursor = w0
    for s, e in first_busy + [(w1, w1)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:10]:
        mid = (s + e) / 2
        inside = [(d, n) for n, st, d in host_spans if st <= mid <= st + d]
        named.append([min(inside)[1] if inside else "no host span",
                      (e - s) / 1e9])
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": (w1 - w0) / 1e9,
            "op_s": by_name,
            "scope_s": scope_seconds(device_ops, window)["scope_s"],
            "idle_gaps": named}


def read_xplane(trace_dir: str, window_span: str = "benchmark.window",
                ) -> dict:
    """Load the newest ``.xplane.pb`` under ``trace_dir`` and reduce it
    over the host span ``window_span`` (or, where the trace lacks it, from
    the first to the last event of any plane). ``planes`` lists each
    plane with its lines, for a reader that meets an unknown trace."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(max(paths, key=os.path.getmtime), "rb") as fh:
        data = fh.read()
    names = op_names(data)
    pd = ProfileData.from_serialized_xspace(data)
    device_ops: Dict[str, List[tuple]] = {}
    host: List[Tuple[str, float, float]] = []
    lo, hi = float("inf"), float("-inf")
    window: Optional[Interval] = None
    planes = {}
    for plane in pd.planes:
        planes[plane.name] = sorted({line.name for line in plane.lines})
        if plane.name.startswith("/device:TPU"):
            ops = device_ops.setdefault(plane.name, [])
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops", lines.get("XLA Modules"))
            op_name = names.get(plane.name, {})
            if line is not None:
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns, ev.duration_ns,
                                scope_of(op_name.get(ev.name, ""))))
                    lo = min(lo, ev.start_ns)
                    hi = max(hi, ev.start_ns + ev.duration_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_span:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.duration_ns > 0:
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
                        lo = min(lo, ev.start_ns)
                        hi = max(hi, ev.start_ns + ev.duration_ns)
    if window is None:
        window = (lo, hi) if lo < hi else (0.0, 0.0)
    red = reduce_events(device_ops, host, window)
    red["chips"] = len(device_ops)
    red["planes"] = planes
    return red
