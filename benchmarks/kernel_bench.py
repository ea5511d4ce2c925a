"""Kernel microbenchmarks.

On this CPU container the Pallas kernels run in interpret mode (Python), so
wall-clock there is meaningless; what IS meaningful on CPU:
  * the jnp oracle paths (XLA-compiled) at realistic sizes — these are the
    portable implementations the models actually run on non-TPU backends;
  * solver-backend timings on real KMS instances (paper's runtime claim).
Pallas kernels are timed at small sizes purely to prove the code path runs.
"""
from __future__ import annotations

import time
from typing import Callable, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def _time(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_clause_eval() -> Tuple[str, float, str]:
    from repro.core.cgra import CGRA
    from repro.core.dfg import running_example
    from repro.core.encode import encode
    from repro.core.sat.walksat_jax import pack_cnf, true_counts_batch
    enc = encode(running_example(), CGRA(4, 4), 3)
    packed = pack_cnf(enc.cnf)
    B = 64
    assign = jnp.asarray(np.random.rand(B, enc.cnf.n_vars + 1) > 0.5)
    fn = jax.jit(lambda a: true_counts_batch(packed, a, use_kernel=False))
    us = _time(fn, assign)
    per = us / (B * enc.cnf.n_clauses)
    return ("clause_eval_ref_jit", us,
            f"{per*1e3:.1f}ns/clause-chain C={enc.cnf.n_clauses} B={B}")


def bench_blockwise_attention() -> Tuple[str, float, str]:
    from repro.models.layers import blockwise_attention
    b, s, h, kv, d = 1, 1024, 8, 2, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    fn = jax.jit(lambda q, k, v: blockwise_attention(q, k, v, pos, pos))
    us = _time(fn, q, k, v)
    flops = 4 * b * h * s * s * d / 2
    return ("blockwise_attn_1k", us, f"{flops/us/1e3:.1f}GFLOP/s-equBk")


def bench_ssd() -> Tuple[str, float, str]:
    from repro.models.layers import ssd_chunked
    b, s, h, p, n = 1, 2048, 8, 64, 64
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(rng.rand(b, s, h) * 0.5, jnp.float32)
    A = jnp.asarray(rng.rand(h), jnp.float32)
    B = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    C = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    D = jnp.asarray(rng.rand(h), jnp.float32)
    fn = jax.jit(lambda *a: ssd_chunked(*a, chunk=256))
    us = _time(fn, x, dt, A, B, C, D)
    return ("ssd_chunked_2k", us, f"{b*s/(us/1e3):.1f}tok/ms")


def bench_pallas_interpret() -> Tuple[str, float, str]:
    from repro.kernels.flash_attention import flash_attention
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    us = _time(lambda *a: flash_attention(*a), q, k, v, iters=2, warmup=1)
    return ("flash_pallas_interpret_128", us, "interpret-mode (CPU)")


def bench_solvers() -> list:
    """Solver backends on one real KMS instance (paper's runtime claim)."""
    import time as _t
    from repro.core.cgra import CGRA
    from repro.core.dfg import running_example
    from repro.core.encode import encode
    from repro.core.sat import solve
    enc = encode(running_example(), CGRA(2, 2), 3)
    rows = []
    for method in ("z3", "cdcl", "walksat"):
        t0 = _t.perf_counter()
        st, _ = solve(enc.cnf, method, walksat_steps=4096, walksat_batch=16)
        rows.append((f"solver_{method}", (_t.perf_counter() - t0) * 1e6,
                     f"status={st} vars={enc.cnf.n_vars} "
                     f"clauses={enc.cnf.n_clauses}"))
    return rows


def main() -> None:
    rows = [bench_clause_eval(), bench_blockwise_attention(), bench_ssd(),
            bench_pallas_interpret()] + bench_solvers()
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.core.device import enable_compile_cache
    enable_compile_cache()
    main()
