"""Pallas kernel: batched clause true-count evaluation.

Accelerator adaptation of the WalkSAT clause evaluation. A TPU has no
general in-kernel gather, so the per-literal lookup ``assign[b, var]`` is
recast as a matrix product. With signed literal ids (``+v`` / ``-v``, 0 =
padding) the true count of clause ``c`` under assignment ``a`` is

    tc[b, c] = #neg_lits[c] + sum_v a[b, v] * W[v, c],
    W[v, c]  = sum_l sign(lit[c, l]) * [|lit[c, l]| == v],

because a positive literal adds ``a[v]`` and a negative one adds
``1 - a[v]``. Each grid cell builds one ``[block_v, block_c]`` tile of the
signed incidence matrix ``W`` with broadcast compares on the VPU (one pass
over the clause-length axis) and contracts it against the matching
``[B, block_v]`` assignment slice on the MXU. The variable-tile axis is the
innermost, sequential grid axis and accumulates into the resident output
tile. All operands are small integers, exact in bf16 with f32
accumulation, so the result is bit-identical to the gather oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _clause_eval_window_kernel(assign_ref, lits_ref, out_ref):
    a = assign_ref[0]                        # [B, bV] bf16 (0/1)
    bv = a.shape[1]
    n_lits, bc = lits_ref.shape[1], lits_ref.shape[2]
    vbase = pl.program_id(2) * bv
    vid = jax.lax.broadcasted_iota(jnp.int32, (bv, bc), 0) + vbase

    def body(l, carry):
        w, neg = carry
        lit = lits_ref[0, pl.ds(l, 1), :]    # [1, bC] signed var ids
        is_neg = (lit < 0).astype(jnp.int32)
        sign = (lit > 0).astype(jnp.int32) - is_neg   # 0 on padding
        w = w + jnp.where(vid == jnp.abs(lit), sign, 0)
        return w, neg + is_neg

    w, neg = jax.lax.fori_loop(
        0, n_lits, body,
        (jnp.zeros((bv, bc), jnp.int32), jnp.zeros((1, bc), jnp.int32)))
    part = jnp.dot(a, w.astype(jnp.float32).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[0] = jnp.broadcast_to(neg, part.shape)

    out_ref[0] += part


def clause_eval_window_pallas(assign: jnp.ndarray, lits: jnp.ndarray, *,
                              block_c: int = 256, block_v: int = 128,
                              interpret: bool = False) -> jnp.ndarray:
    """assign [K, B, Vp] bf16 (0/1, variable axis padded to ``block_v``);
    lits [K, L, C] int32 signed literal ids, clause axis last (0 =
    padding). Returns tc [K, B, C] int32. C % block_c == 0, Vp % block_v
    == 0 and B % 16 == 0 (ops pads). Grid (K, C tiles, V tiles); the V
    axis accumulates into the output tile and must stay sequential."""
    k, b, vp = assign.shape
    _, l, c = lits.shape
    grid = (k, c // block_c, vp // block_v)
    return pl.pallas_call(
        _clause_eval_window_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, b, block_v), lambda g, j, v: (g, 0, v)),
            pl.BlockSpec((1, l, block_c), lambda g, j, v: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, b, block_c), lambda g, j, v: (g, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, b, c), jnp.int32),
        interpret=interpret,
    )(assign, lits)
