"""jit'd public wrappers: padding + backend dispatch for clause_eval."""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .kernel import clause_eval_window_pallas
from .ref import true_counts_ref, true_counts_window_ref


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret-vs-compiled policy shared by the SAT kernels.

    Compiled by default on TPU (Mosaic) *and* GPU (Triton); interpret mode
    — same kernel body, Python evaluation — everywhere else, since Pallas
    has no CPU lowering. ``REPRO_PALLAS_INTERPRET=1/0`` overrides (CI uses
    it to force interpret-mode coverage on CPU runners and compiled mode
    where an accelerator is present); an explicit ``interpret=`` argument
    wins over everything.
    """
    if interpret is not None:
        return interpret
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off"):
        return False
    return jax.default_backend() not in ("tpu", "gpu")


@functools.partial(jax.jit, static_argnames=("block_c", "block_v",
                                             "interpret"))
def true_counts_window(cvars: jnp.ndarray, csign: jnp.ndarray,
                       assign: jnp.ndarray, *, block_c: int = 256,
                       block_v: int = 128,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Window true counts: cvars [K,C,L] int32 (1-based, 0 = padding);
    csign [K,C,L] bool; assign [K,B,V+1] bool -> [K,B,C] int32. The
    sweep's padded window tensors are already bucketed, but arbitrary
    shapes are padded here too so the tests can drive odd sizes.

    Compiled on TPU/GPU, interpret mode elsewhere (see
    :func:`resolve_interpret`); ``interpret=False`` forces compilation.
    """
    interpret = resolve_interpret(interpret)
    k, b, v1 = assign.shape
    c = cvars.shape[1]
    bp = _pad_to(max(b, 1), 16)
    cp = _pad_to(max(c, 1), block_c)
    vp = _pad_to(v1, block_v)
    # signed literal ids, clause axis last so a literal slot is one row
    lits = jnp.where(csign, cvars, -cvars).astype(jnp.int32)
    lits = jnp.pad(jnp.swapaxes(lits, 1, 2), ((0, 0), (0, 0), (0, cp - c)))
    a = jnp.pad(assign.astype(jnp.bfloat16),
                ((0, 0), (0, bp - b), (0, vp - v1)))
    tc = clause_eval_window_pallas(a, lits, block_c=block_c,
                                   block_v=block_v, interpret=interpret)
    return tc[:, :b, :c]


@functools.partial(jax.jit, static_argnames=("block_c", "block_v",
                                             "interpret"))
def true_counts(cvars: jnp.ndarray, csign: jnp.ndarray, assign: jnp.ndarray,
                *, block_c: int = 256, block_v: int = 128,
                interpret: bool | None = None) -> jnp.ndarray:
    """Batched per-clause true counts of one formula. cvars [C,L] int32
    (0-padded, 1-based); csign [C,L] bool; assign [B,V+1] bool -> [B,C]
    int32. The K=1 window."""
    return true_counts_window(cvars[None], csign[None], assign[None],
                              block_c=block_c, block_v=block_v,
                              interpret=interpret)[0]


__all__ = ["true_counts", "true_counts_window", "true_counts_ref",
           "true_counts_window_ref", "resolve_interpret"]
