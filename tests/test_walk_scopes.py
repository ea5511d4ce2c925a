"""The walk step's device scopes: every part of the jitted walk carries
its ``walk.*`` scope in its op_name metadata, and the scopes change no
computed value on either engine, with the kernels or without."""
import hashlib
import json
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import suite
from repro.core.cgra import CGRA
from repro.core.encode import EncoderSession
from repro.core.sat.walksat_jax import (_POLL_CHUNKS, _device_segment,
                                        _run_chains_window,
                                        solve_walksat_window)
from repro.core.schedule import min_ii

K, B, V, C, L, O = 2, 4, 128, 1024, 4, 8
STEP = ("walk.pick.clause", "walk.pick.break", "walk.flip")


def _S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _clauses():
    return (_S((K, C, L), jnp.int32), _S((K, C, L), jnp.bool_),
            _S((K, V + 1, O), jnp.int32), _S((K, V + 1, O), jnp.bool_))


@pytest.mark.parametrize("kernels", [None, "interpret"])
def test_device_segment_carries_each_scope(kernels):
    v1 = V + 1
    state = (_S((K, B, v1), jnp.bool_), _S((K, B, C), jnp.int32),
             _S((2,), jnp.uint32), _S((), jnp.int32), _S((), jnp.int32),
             _S((K,), jnp.bool_), _S((K, v1), jnp.bool_),
             _S((K,), jnp.bool_), _S((K,), jnp.int32),
             _S((K, v1), jnp.bool_),
             _S((K, B, C), jnp.int32), _S((K, B, v1), jnp.int32),
             _S((), jnp.int32))
    text = _device_segment.lower(
        _POLL_CHUNKS, 2.3, kernels, None, *_clauses(),
        _S((), jnp.int32), _S((), jnp.int32), state,
    ).as_text(debug_info=True)
    for scope in STEP + ("walk.chunk_end",):
        assert scope in text, scope
    # the pick reads the break cache: no gather of the true counts at
    # every occurrence of the picked clause's literals (B·L·O indices)
    for n in _gather_index_counts(text):
        assert n < B * L * O, n


def _gather_index_counts(text):
    """The number of indices of each stablehlo gather in ``text``."""
    for line in text.splitlines():
        m = re.search(r'stablehlo\.gather".*index_vector_dim = (\d+)'
                      r'.*: \(tensor<[^>]*>, tensor<([\dx]*)x?i\d+>\)', line)
        if m:
            dims = [int(d) for d in m.group(2).split("x") if d]
            ivd = int(m.group(1))
            yield math.prod(dims) // (dims[ivd] if ivd < len(dims) else 1)


@pytest.mark.parametrize("kernels", [None, "interpret"])
def test_host_engine_chunk_carries_each_scope(kernels):
    text = _run_chains_window.lower(
        *_clauses(), V, 64, 2.3, _S((K, B, V + 1), jnp.bool_),
        _S((K, 2), jnp.uint32), kernels, None).as_text(debug_info=True)
    for scope in STEP + ("walk.init", "walk.chunk_end"):
        assert scope in text, scope


# results of this walk before the scopes were added (sha256 of the JSON
# of the statuses, models and near-misses; the same on both engines)
BEFORE = "4c76f4de6ca17cc8"


@pytest.mark.parametrize("kernels", ["0", "interpret"])
@pytest.mark.parametrize("engine", ["host", "device"])
def test_scopes_change_no_computed_value(engine, kernels, monkeypatch):
    monkeypatch.setenv("REPRO_SAT_KERNELS", kernels)
    g = suite.get("sha")
    cgra = CGRA(3, 3)
    mii = max(min_ii(g, cgra), 1)
    sess = EncoderSession(g, cgra)
    cnfs = [sess.encode(ii).cnf for ii in range(mii, mii + 3)]
    near = {}
    res = solve_walksat_window(cnfs, seed=11, steps=600, batch=6,
                               engine=engine, near_miss=near)
    blob = json.dumps([res, sorted(near.items())], default=str)
    assert [r[0] for r in res] == ["UNKNOWN", "SAT", "SAT"]
    assert hashlib.sha256(blob.encode()).hexdigest()[:16] == BEFORE
