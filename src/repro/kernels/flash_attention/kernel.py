"""Pallas TPU flash attention (GQA, causal, sliding window).

Grid (B, Hq, nQ, nK); the last dim is sequential ("arbitrary") — running
max / sum / accumulator live in VMEM scratch across the KV sweep, so HBM
traffic is O(S) per tile instead of O(S^2): the online-softmax rewrite of
the paper-agnostic attention bottleneck, tiled so q/k/v blocks are
MXU-aligned (block sizes multiples of 128 on the matmul dims).

GQA is handled in the k/v index_map (q head h reads kv head h // group) —
no repeated K/V materialization in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  sk_valid: int, block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = qi * block_q + jax.lax.iota(jnp.int32, block_q) + q_offset
    kpos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
    # block-level skip: nothing to do if every (q, k) pair is masked
    needed = jnp.asarray(True)
    if causal:
        needed &= kpos[0] <= qpos[-1]
    if window:
        needed &= kpos[-1] > qpos[0] - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [bQ, D]
        k = k_ref[0, 0].astype(jnp.float32)              # [bK, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        mask = kpos[None, :] < sk_valid
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, q_offset=0,
                           sk_valid=None, block_q=128, block_k=128,
                           interpret=False):
    """q: [B,Hq,Sq,D] (Sq % block_q == 0); k,v: [B,Hkv,Sk,D]
    (Sk % block_k == 0). sk_valid masks padded KV tail. -> [B,Hq,Sq,D]."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    n_k = sk // block_k
    if sk_valid is None:
        sk_valid = sk
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal,
        window=window, q_offset=q_offset, sk_valid=sk_valid,
        block_q=block_q, block_k=block_k, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, sq // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, qi, ki, g=group: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, qi, ki, g=group: (b_, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # running sum
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
