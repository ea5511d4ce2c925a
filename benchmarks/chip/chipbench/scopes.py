"""Device time per ``jax.named_scope`` in a profiler trace.

    python3 benchmarks/chip/chipbench/scopes.py <trace dir>

prints one JSON object: the seconds of leaf device operations
(``leaf_s``), of those that carry a scope, per scope (``scope_s``), and
the share of leaf time that scopes cover. The trace dir is what
``jax.profiler.start_trace`` wrote; the newest ``.xplane.pb`` under it is
read, over the host span ``benchmark.window`` where the trace has it and
otherwise from the first to the last device operation.

An operation's scope is the innermost named scope (a dotted lower-case
name such as ``walk.pick.clause``) in the op_name metadata of its HLO
instruction, which a TPU trace keeps in the ``tf_op`` stat of the
event's metadata (``OP_NAME_STAT``). ``jax.profiler.ProfileData`` does
not show those stats, so :func:`op_names` reads them from the file's
protobuf wire format. Only leaf operations count: an event that encloses
another on its line (a ``while`` and its body) is a container.

``trace.read_xplane`` gives each device operation its scope the same
way, and its ``scope_s`` is :func:`scope_seconds`'s.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

# the stat of an XLA op's event metadata that holds its HLO op_name
OP_NAME_STAT = "tf_op"
# a named scope in an op_name path: "jit(f)/while/vmap(walk.pick.break)/gather"
SCOPE = re.compile(r"(?<![\w.<>])([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost named scope in an op_name path, or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def containers(ops: List[tuple]) -> set:
    """Indices of the events (name, start, duration, ...) of one line that
    enclose another event."""
    out = set()
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    stack: List[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and ops[stack[-1]][1] + ops[stack[-1]][2] >= e:
            out.add(stack[-1])
        stack.append(i)
    return out


def scope_seconds(device_ops: Dict[str, List[tuple]],
                  window: Tuple[float, float]) -> dict:
    """``device_ops``: chip -> [(op name, start_ns, dur_ns[, scope])];
    ``window``: the interval in ns. Over leaf operations only, summed
    over chips: their seconds (``leaf_s``) and the seconds of those that
    carry a scope, per scope (``scope_s``)."""
    w0, w1 = window
    leaf, by_scope = 0.0, {}
    for chip in sorted(device_ops):
        ops = device_ops[chip]
        outer = containers(ops)
        for j, (_, s, d, *rest) in enumerate(ops):
            scope = rest[0] if rest else None
            s, e = max(s, w0), min(s + d, w1)
            if j in outer or e <= s:
                continue
            leaf += (e - s) / 1e9
            if scope:
                by_scope[scope] = by_scope.get(scope, 0.0) + (e - s) / 1e9
    return {"leaf_s": leaf, "scope_s": by_scope}


# ------------------------------------------- the file's op-name metadata
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for the rest."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield field, val


def op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: op_name}} from a serialized XSpace
    (``xplane.proto``: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2). An event's name is its metadata's name."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, val in _fields(plane):
            if pf == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pf == 4:
                metas.append(dict(_fields(val)).get(2, b""))
            elif pf == 5:
                entry = dict(_fields(val))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:TPU"):
            continue
        ops = out.setdefault(name, {})
        for meta in metas:
            ev, stats = "", {}
            for mf, val in _fields(meta):
                if mf == 2:
                    ev = bytes(val).decode("utf-8", "replace")
                elif mf == 5:
                    st = dict(_fields(val))
                    key = stat_names.get(st.get(1, 0), "")
                    if 5 in st:
                        stats[key] = bytes(st[5]).decode("utf-8", "replace")
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
            if stats.get(OP_NAME_STAT):
                ops.setdefault(ev, stats[OP_NAME_STAT])
    return out


def read(trace_dir: str, window_span: str = "benchmark.window") -> dict:
    """:func:`scope_seconds` of the newest ``.xplane.pb`` under
    ``trace_dir``, with ``covered``, the share of leaf time in scopes."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    with open(path, "rb") as fh:
        data = fh.read()
    names = op_names(data)
    device_ops: Dict[str, List[tuple]] = {}
    window = None
    lo, hi = float("inf"), float("-inf")
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:TPU"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops", lines.get("XLA Modules"))
            op_name = names.get(plane.name, {})
            ops = device_ops.setdefault(plane.name, [])
            for ev in (line.events if line is not None else []):
                ops.append((ev.name, ev.start_ns, ev.duration_ns,
                            scope_of(op_name.get(ev.name, ""))))
                lo = min(lo, ev.start_ns)
                hi = max(hi, ev.start_ns + ev.duration_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_span:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        window = (lo, hi) if lo < hi else (0.0, 0.0)
    out = scope_seconds(device_ops, window)
    out["covered"] = (sum(out["scope_s"].values()) / out["leaf_s"]
                      if out["leaf_s"] else None)
    return out


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    print(json.dumps(read(sys.argv[1]), sort_keys=True))
