"""Device time per named scope (``chipbench/scopes.py``): the op_name of
each operation read from the trace's event metadata, its innermost
scope, and leaf operations only counted."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench.scopes import (op_names, read, scope_of,  # noqa: E402
                              scope_seconds)

# one chip: a %while holds three body ops; two carry walk scopes, one
# carries none; an op outside the loop carries a scope too
SCOPED = {
    "/device:TPU:0": [("%while.1", 0, 500, None),
                      ("%fusion.131", 10, 300, "walk.pick.break"),
                      ("%fusion.7", 320, 40, "walk.pick.clause"),
                      ("%copy.2", 370, 20, None),
                      ("%flip_update.9", 600, 100, "walk.flip")],
}

# the same chip as a serialized XSpace: one XLA Ops line, op_names in the
# events' metadata, and a host plane with the benchmark's window span
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 300000 }
    events { metadata_id: 3 offset_ps: 320000 duration_ps: 40000 }
    events { metadata_id: 4 offset_ps: 370000 duration_ps: 20000 }
    events { metadata_id: 5 offset_ps: 600000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[])"
    stats { metadata_id: 10 str_value: "jit(f)/while:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.131 = s32[8]"
    stats { metadata_id: 10 str_value: "jit(f)/while/body/vmap(walk.pick.break)/gather:" }
    stats { metadata_id: 11 int64_value: 7 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.7 = s32[8]"
    stats { metadata_id: 10 str_value: "jit(f)/while/body/walk.pick.clause/select:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.2" } }
  event_metadata { key: 5 value { id: 5 name: "%flip_update.9"
    stats { metadata_id: 10 str_value: "jit(f)/walk.flip/pallas_call:" } } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "flops" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 650000 } }
  event_metadata { key: 1 value { id: 1 name: "benchmark.window"
    stats { metadata_id: 1 str_value: "walk.flip" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }
"""


def _xspace() -> bytes:
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(XSPACE)


def test_scope_seconds_counts_leaf_ops_only():
    red = scope_seconds(SCOPED, (0, 1000))
    assert red["scope_s"] == {"walk.pick.break": pytest.approx(300e-9),
                              "walk.pick.clause": pytest.approx(40e-9),
                              "walk.flip": pytest.approx(100e-9)}
    assert red["leaf_s"] == pytest.approx(460e-9)


def test_scope_seconds_clips_to_the_window_and_keeps_unscoped_leaves():
    ops = {"/device:TPU:0": [("a", 0, 100, None), ("b", 50, 100, None),
                             ("c", 990, 100, "walk.flip")]}
    red = scope_seconds(ops, (0, 1000))
    # overlapping ops that do not nest are leaves each
    assert red["leaf_s"] == pytest.approx(210e-9)
    assert red["scope_s"] == {"walk.flip": pytest.approx(10e-9)}


def test_scope_of_takes_the_innermost_named_scope():
    assert scope_of("jit(_device_segment)/while/body/while/body/"
                    "vmap(walk.pick.break)/jit(take_along_axis)/gather:") \
        == "walk.pick.break"
    assert scope_of("jit(_device_segment)/while/body/walk.chunk_end/"
                    "jit(_threefry_split)/_device_segment.<locals>.body/"
                    "add") == "walk.chunk_end"
    assert scope_of("jit(_device_segment)/while/body/while:") is None
    assert scope_of("") is None


def test_op_names_read_from_the_event_metadata():
    """The op_name of an XLA op lives in a stat of its event metadata
    (``tf_op`` on a TPU), which the wire-format reader finds by name; a
    host plane's stats are not read."""
    assert op_names(_xspace()) == {"/device:TPU:0": {
        "%while.1 = (s32[])": "jit(f)/while:",
        "%fusion.131 = s32[8]":
            "jit(f)/while/body/vmap(walk.pick.break)/gather:",
        "%fusion.7 = s32[8]": "jit(f)/while/body/walk.pick.clause/select:",
        "%flip_update.9": "jit(f)/walk.flip/pallas_call:"}}


def test_read_a_trace_file_over_its_window(tmp_path):
    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "host.xplane.pb").write_bytes(_xspace())
    red = read(str(tmp_path))
    # the window span ends at 650 ns: the flip op keeps 50 of its 100 ns
    assert red["scope_s"] == {"walk.pick.break": pytest.approx(300e-9),
                              "walk.pick.clause": pytest.approx(40e-9),
                              "walk.flip": pytest.approx(50e-9)}
    assert red["leaf_s"] == pytest.approx(410e-9)
    assert red["covered"] == pytest.approx(390 / 410)
    out = subprocess.run([sys.executable, str(HERE / "chipbench" /
                                              "scopes.py"), str(tmp_path)],
                         capture_output=True, text=True, check=True)
    assert json.loads(out.stdout) == json.loads(json.dumps(red))
