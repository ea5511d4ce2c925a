"""Compile the device walk for a TPU v5e that is described, not attached.

Interpret mode accepts kernels the chip's compiler refuses (an in-kernel
gather, an int8 compare relayout), so these tests lower the SAT kernels and
the walk segment with ``interpret=False`` for a ``v5e:2x2`` topology at the
padded shapes of real 5x5 suite windows (``pack_cnf_window`` over IIs
MII..MII+3, batch 24) and let Mosaic and XLA:TPU compile them. Nothing
runs; a refused kernel or a VMEM overrun fails here instead of on the chip.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.clause_eval import true_counts_window
from repro.kernels.flip_update import flip_update

# (K, B, V, C, L, O) of stacked 5x5 windows, as packed by pack_cnf_window
WINDOWS = {
    "sha2": (4, 24, 768, 37888, 152, 192),   # largest suite window
    "nw": (4, 24, 256, 5120, 32, 56),        # smallest
}


@pytest.fixture(scope="module")
def topo():
    # compile logs stay out of the temp dir; the persistent compilation
    # cache is off, since entries compiled without a chip cannot be read
    # back and would only warn
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _segment_args(name, rep, bat):
    """Abstract (cvars, csign, ovars, osign, steps, cap, state) of
    ``_device_segment`` for one window shape."""
    K, B, V, C, L, O = WINDOWS[name]
    v1 = V + 1
    state = (_spec((K, B, v1), jnp.bool_, bat),
             _spec((K, B, C), jnp.int32, bat),
             _spec((2,), jnp.uint32, rep), _spec((), jnp.int32, rep),
             _spec((), jnp.int32, rep), _spec((K,), jnp.bool_, rep),
             _spec((K, v1), jnp.bool_, rep), _spec((K,), jnp.bool_, rep),
             _spec((K,), jnp.int32, rep), _spec((K, v1), jnp.bool_, rep),
             _spec((K, B, C), jnp.int32, bat),
             _spec((K, B, v1), jnp.int32, bat), _spec((), jnp.int32, rep))
    return (_spec((K, C, L), jnp.int32, rep), _spec((K, C, L), jnp.bool_, rep),
            _spec((K, v1, O), jnp.int32, rep),
            _spec((K, v1, O), jnp.bool_, rep),
            _spec((), jnp.int32, rep), _spec((), jnp.int32, rep), state)


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_clause_eval_compiles_for_v5e(one_chip, name):
    K, B, V, C, L, O = WINDOWS[name]
    f = jax.jit(lambda cv, cs, a: true_counts_window(cv, cs, a,
                                                     interpret=False))
    compiled = f.lower(_spec((K, C, L), jnp.int32, one_chip),
                       _spec((K, C, L), jnp.bool_, one_chip),
                       _spec((K, B, V + 1), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (K, B, C) and out.dtype == jnp.int32


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_flip_update_compiles_for_v5e(one_chip, name):
    K, B, V, C, L, O = WINDOWS[name]
    f = jax.jit(lambda *a: flip_update(*a, interpret=False))
    compiled = f.lower(_spec((K, B, V + 1), jnp.bool_, one_chip),
                       _spec((K, B, C), jnp.int32, one_chip),
                       _spec((K, B), jnp.int32, one_chip),
                       _spec((K, B, O), jnp.int32, one_chip),
                       _spec((K, B, O), jnp.bool_, one_chip),
                       _spec((K, B), jnp.bool_, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    a_out, tc_out = compiled.out_info
    assert a_out.shape == (K, B, V + 1) and tc_out.shape == (K, B, C)


def test_device_segment_compiles_for_v5e(one_chip, monkeypatch):
    """The whole on-device walk segment with the compiled kernels
    (``kernels="auto"``), as the device engine runs it on one chip."""
    from repro.core.sat.walksat_jax import _POLL_CHUNKS, _device_segment
    # the described chip is not this process's backend: pin the compiled
    # lowering that the TPU backend would choose by itself
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    args = _segment_args("nw", one_chip, one_chip)
    compiled = _device_segment.lower(_POLL_CHUNKS, 2.3, "auto", None,
                                     *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_segment_compiles_sharded_over_four_chips(topo, monkeypatch):
    """On a 4-chip host the restart batch is sharded over the chips and the
    kernels run per chip on their slice (``shard_map``): the program must
    compile and move only small per-candidate flags between chips, never
    the [K, B, C] true counts."""
    from repro.core.sat.walksat_jax import _POLL_CHUNKS, _device_segment
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    mesh = Mesh(np.asarray(topo.devices), ("dev",))
    assert mesh.size == 4
    args = _segment_args("nw", NamedSharding(mesh, P()),
                         NamedSharding(mesh, P(None, "dev", None)))
    compiled = _device_segment.lower(_POLL_CHUNKS, 2.3, "auto", mesh,
                                     *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    K, B, _, C, _, _ = WINDOWS["nw"]
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* all-gather(?:-start)?\(", line)
        if m:
            n = int(np.prod([int(d) for d in m.group(1).split(",") if d]))
            assert n < K * B * C // 4, line.strip()[:160]


@pytest.mark.parametrize("chips", [1, 4])
def test_walk_start_compiles_for_v5e(topo, monkeypatch, chips):
    """The walk's start (true counts and the break cache's build) at the
    largest suite window; on four chips each chip builds its own chains'
    cache, so no [K, B, C] or [K, B, V+1] tensor crosses chips."""
    from repro.core.sat.walksat_jax import _walk_start
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    K, B, V, C, L, O = WINDOWS["sha2"]
    mesh = None
    if chips == 1:
        rep = bat = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.asarray(topo.devices), ("dev",))
        rep = NamedSharding(mesh, P())
        bat = NamedSharding(mesh, P(None, "dev", None))
    compiled = _walk_start.lower(
        _spec((K, C, L), jnp.int32, rep), _spec((K, C, L), jnp.bool_, rep),
        _spec((K, V + 1, O), jnp.int32, rep),
        _spec((K, V + 1, O), jnp.bool_, rep),
        _spec((K, B, V + 1), jnp.bool_, bat), "auto", mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    tc, tsum, brk = compiled.out_info
    assert tc.shape == tsum.shape == (K, B, C) and brk.shape == (K, B, V + 1)
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* all-(?:gather|reduce|to-all)"
                      r"(?:-start)?\(", line)
        if m:
            n = int(np.prod([int(d) for d in m.group(1).split(",") if d]))
            assert n < K * B * (V + 1) // 4, line.strip()[:160]
