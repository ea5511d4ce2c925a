"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Grid (B, H, nChunks); the chunk dim is sequential ("arbitrary") and carries
the [P, N] inter-chunk state in VMEM scratch — the HBM-resident state
tensor of a naive scan never exists. Within a chunk the dual (quadratic)
form runs on the MXU: chunk x chunk decay matrix, [chunk, N] x [N, chunk]
contraction — all VMEM-resident with chunk=128..256, P,N <= 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                state_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)          # [l, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)           # [l]
    A = -jnp.exp(a_ref[0].astype(jnp.float32))         # scalar
    Bm = b_ref[0].astype(jnp.float32)                  # [l, N]
    Cm = c_ref[0].astype(jnp.float32)                  # [l, N]
    D = d_ref[0].astype(jnp.float32)

    dA = dt * A                                        # [l]
    seg = jnp.cumsum(dA)                               # [l]
    # intra-chunk: y_diag[l] = sum_{m<=l} exp(seg_l - seg_m) dt_m (C_l.B_m) x_m
    rel = seg[:, None] - seg[None, :]                  # [l, l]
    causal = jax.lax.iota(jnp.int32, chunk)[:, None] >= \
        jax.lax.iota(jnp.int32, chunk)[None, :]
    decay = jnp.where(causal, jnp.exp(rel), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # [l, l]
    w = cb * decay * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))      # [l, P]
    # carried-state contribution: C_l . (exp(seg_l) * S_prev)
    state = state_ref[...]                             # [P, N]
    y += jnp.exp(seg)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())))           # [l, P]
    y += x * D
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    # state update: S = exp(seg_last) S_prev + sum_l exp(seg_last-seg_l) dt_l x_l B_l^T
    w2 = (jnp.exp(seg[-1] - seg) * dt)[:, None] * x    # [l, P]
    state_new = jnp.exp(seg[-1]) * state + jax.lax.dot_general(
        w2, Bm, (((0,), (0,)), ((), ())))              # [P, N]
    state_ref[...] = state_new


def ssd_scan_pallas(x, dt, A_log, B, C, D, *, chunk: int = 128,
                    interpret: bool = False):
    """x: [b,s,h,p]; dt: [b,s,h]; A_log: [h]; B,C: [b,s,n]; D: [h].
    s % chunk == 0. Returns y [b,s,h,p] (x.dtype)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    nc = s // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p),
                               lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A_log, B, C, D)
