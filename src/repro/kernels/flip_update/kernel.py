"""Pallas kernel: fused probSAT flip + incremental true-count update.

TPU/GPU have no efficient per-row scatter, so the occurrence-list update is
recast as a dense one-hot compare-accumulate: each grid cell owns a
[block_b, block_c] tile of the true-count matrix for one formula, rebases
the flipped variable's (pre-gathered) occurrence clause ids against the
tile origin, and accumulates ``sum_o onehot(rel_o) * delta_o`` — a
vectorized broadcast-compare-reduce the VPU handles natively. The
assignment flip itself is a one-hot select over the variable axis, emitted
once per (formula, chain-block) by the clause-tile-0 program.

Occurrence rows are tiny (Omax is bucketed to a few dozen for mapper
CNFs), so the [block_b, Omax, block_c] one-hot intermediate stays well
inside VMEM at the default tile sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flip_update_kernel(assign_ref, tc_ref, vflip_ref, occ_ref, osign_ref,
                        newval_ref, assign_out_ref, tc_out_ref):
    # Mosaic cannot relayout i1 masks built from int8 compares, so the
    # compares and selects run in int32; only the stored assignment is int8
    tc = tc_ref[0]                           # [bB, bC] int32
    oc = occ_ref[0]                          # [bB, O] int32, -1 = padding
    os_ = osign_ref[0].astype(jnp.int32)     # [bB, O] 0/1
    nv = newval_ref[0].astype(jnp.int32)     # [bB, 1] 0/1
    bb, bc = tc.shape
    o = oc.shape[1]
    cbase = pl.program_id(2) * bc
    rel = oc - cbase                         # [bB, O] tile-local clause ids
    valid = oc >= 0
    delta = jnp.where(os_ == nv, 1, -1) * valid.astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bb, o, bc), 2)
    onehot = (rel[:, :, None] == iota).astype(jnp.int32)
    tc_out_ref[0] = tc + jnp.sum(onehot * delta[:, :, None], axis=1)

    @pl.when(pl.program_id(2) == 0)
    def _flip_assign():
        a = assign_ref[0].astype(jnp.int32)  # [bB, V+1] 0/1
        vf = vflip_ref[0]                    # [bB, 1] int32
        vidx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        assign_out_ref[0] = jnp.where(vidx == vf, nv, a).astype(jnp.int8)


def flip_update_pallas(assign: jnp.ndarray, tc: jnp.ndarray,
                       v_flip: jnp.ndarray, occ_c: jnp.ndarray,
                       occ_s: jnp.ndarray, new_val: jnp.ndarray, *,
                       block_b: int = 8, block_c: int = 256,
                       interpret: bool = False,
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """assign [K,B,V+1] int8; tc [K,B,C] int32; v_flip/new_val [K,B,1]
    int32/int8; occ_c/occ_s [K,B,O] int32/int8 (occ_c padded with -1,
    *including* any padded chain rows, so they update nothing).
    B % block_b == 0 and C % block_c == 0 (ops pads). Returns
    (assign' [K,B,V+1] int8, tc' [K,B,C] int32)."""
    k, b, v1 = assign.shape
    c = tc.shape[2]
    o = occ_c.shape[2]
    grid = (k, b // block_b, c // block_c)
    return pl.pallas_call(
        _flip_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, v1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_b, block_c), lambda g, i, j: (g, i, j)),
            pl.BlockSpec((1, block_b, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_b, o), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_b, o), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_b, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_b, v1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_b, block_c), lambda g, i, j: (g, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, b, v1), jnp.int8),
            jax.ShapeDtypeStruct((k, b, c), jnp.int32),
        ],
        interpret=interpret,
    )(assign, tc, v_flip, occ_c, occ_s, new_val)
