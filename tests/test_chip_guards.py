"""No silent fallback from the chip: racer errors are counted, a process
that needs the chip refuses another backend, one process owns the chip,
and the compile cache follows ``JAX_COMPILATION_CACHE_DIR``.

The chip host is simulated by patching the hardware probe; everything
else runs as on any host."""
import functools
import threading

import pytest

import jax

from repro.core import device, suite
from repro.core.cgra import CGRA
from repro.core.mapper import MapperConfig, map_loop
from repro.core.sat import portfolio, walksat_jax
from repro.core.service import MappingService
from repro.core.workers import WorkerPool

CFG = MapperConfig(solver="auto", timeout_s=90)


@pytest.fixture
def chip_host(monkeypatch):
    """Pretend this host has a TPU that JAX_PLATFORMS does not rule out."""
    monkeypatch.setattr(device, "_probe_hardware", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)


# ------------------------------------------------------------ racer errors
def test_racer_error_is_counted_and_ii_matches_sequential(monkeypatch):
    """A racer that raises (as a kernel the chip's compiler refuses would)
    no longer vanishes: the request and the service count it and keep its
    message, and the II is still the sequential reference's."""
    from repro.core import sweep
    raised = threading.Event()

    def broken_walk(*args, **kwargs):
        raised.set()
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(walksat_jax, "solve_walksat_window", broken_walk)
    # start the racer at once and hold the complete leg until the racer
    # thread has failed and finished, so the error lands while its window
    # is open
    monkeypatch.setattr(sweep, "solve_window", functools.partial(
        portfolio.solve_window, walksat_delay=0.0))
    solve_complete = portfolio.SolverSession.solve_complete

    def gated(self, *args, **kwargs):
        assert raised.wait(10)
        for t in threading.enumerate():
            if "run_walksat" in t.name:
                t.join(10)
                assert not t.is_alive()
        return solve_complete(self, *args, **kwargs)

    monkeypatch.setattr(portfolio.SolverSession, "solve_complete", gated)
    n0, _ = portfolio.racer_errors()
    g, cgra = suite.get("sha"), CGRA(3, 3)
    svc = MappingService()
    res = svc.map(g, cgra, CFG, sweep_width=4)
    ref = map_loop(g, cgra, CFG)
    assert res.success and ref.success and res.ii == ref.ii
    st = res.service
    assert st.racer_errors >= 1
    assert "kernel refused by the compiler" in st.racer_error
    assert st.racer_errors == sum(1 for a in res.attempts if a.racer_error)
    assert svc.stats.racer_errors == st.racer_errors
    assert svc.describe()["racer_error"] == st.racer_error
    count, first = portfolio.racer_errors()
    assert count >= n0 + st.racer_errors and first is not None


def test_clean_sweep_reports_no_racer_errors():
    svc = MappingService()
    res = svc.map(suite.get("srand"), CGRA(3, 3), CFG, sweep_width=4)
    assert res.success
    assert res.service.racer_errors == 0 and res.service.racer_error is None
    assert svc.stats.racer_errors == 0


# ------------------------------------------------------------ chip backend
def test_attached_platform_respects_jax_platforms(monkeypatch):
    monkeypatch.setattr(device, "_probe_hardware", lambda: "tpu")
    for allowed, want in (("", "tpu"), ("tpu", "tpu"), ("tpu,cpu", "tpu"),
                          ("cpu", None)):
        monkeypatch.setenv("JAX_PLATFORMS", allowed)
        assert device.attached_platform() == want
    monkeypatch.setattr(device, "_probe_hardware", lambda: None)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert device.attached_platform() is None


def test_walk_refuses_cpu_backend_on_chip_host(chip_host):
    """A process that needs the chip but got the CPU backend (the chip is
    held elsewhere) fails instead of walking on the CPU."""
    from repro.core.dfg import running_example
    from repro.core.encode import encode
    cnf = encode(running_example(), CGRA(2, 2), 3).cnf
    with pytest.raises(RuntimeError, match="held by another process"):
        device.require_attached_backend()
    with pytest.raises(RuntimeError, match="held by another process"):
        walksat_jax.solve_walksat_window([cnf], steps=64)


@pytest.mark.parametrize("env", [("REPRO_SAT_KERNELS", "interpret"),
                                 ("REPRO_PALLAS_INTERPRET", "1")])
def test_interpret_kernels_refused_on_tpu(monkeypatch, env):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_SAT_KERNELS", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert walksat_jax._sat_kernels_mode() == "auto"
    monkeypatch.setenv(*env)
    with pytest.raises(RuntimeError, match="interpret-mode"):
        walksat_jax._sat_kernels_mode()


# ------------------------------------------------------ one process per chip
def test_worker_pool_runs_shards_as_threads_on_chip_host(chip_host):
    with WorkerPool(workers=2) as pool:
        assert pool.inline
        res = pool.map(suite.get("srand"), CGRA(3, 3), CFG)
        assert res.success
        st = pool.stats()
        assert st["inline"] and st["requests"] == 1


# ------------------------------------------------------------ compile cache
def test_compile_cache_follows_env_or_fixed_checkout_dir(monkeypatch,
                                                         tmp_path):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir \
            == was["jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = device.enable_compile_cache()
        assert path == str(device._REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert device.enable_compile_cache() == path   # fixed, not per run
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
