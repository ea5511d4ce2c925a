"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                          # optional dep: local shim
    from _propshim import given, settings, strategies as st

from repro.kernels.clause_eval import true_counts, true_counts_window
from repro.kernels.clause_eval.ref import (true_counts_ref,
                                           true_counts_window_ref)
from repro.kernels.flip_update import flip_update
from repro.kernels.flip_update.ref import flip_update_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref


# ------------------------------------------------------------ clause_eval
@pytest.mark.parametrize("c,l,v,b", [
    (17, 3, 33, 4), (333, 7, 97, 11), (1025, 2, 250, 1), (64, 12, 64, 16),
])
def test_clause_eval_matches_ref(c, l, v, b):
    rng = np.random.RandomState(c + l)
    cvars = jnp.asarray(rng.randint(0, v + 1, (c, l)), jnp.int32)
    csign = jnp.asarray(rng.rand(c, l) > 0.5)
    assign = jnp.asarray(rng.rand(b, v + 1) > 0.5)
    got = true_counts(cvars, csign, assign)
    want = true_counts_ref(cvars, csign, assign)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_clause_eval_on_real_instance():
    from repro.core.cgra import CGRA
    from repro.core.dfg import running_example
    from repro.core.encode import encode
    from repro.core.sat.walksat_jax import pack_cnf
    enc = encode(running_example(), CGRA(2, 2), 3)
    packed = pack_cnf(enc.cnf)
    rng = np.random.RandomState(0)
    assign = jnp.asarray(rng.rand(4, enc.cnf.n_vars + 1) > 0.5)
    got = true_counts(packed.cvars, packed.csign.astype(bool), assign)
    want = true_counts_ref(packed.cvars, packed.csign.astype(bool), assign)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------- clause_eval window + flip_update
_COMPILED = jax.default_backend() in ("tpu", "gpu")


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 40), st.integers(2, 4),
       st.integers(2, 40), st.integers(1, 9), st.integers(0, 10_000))
def test_clause_eval_window_matches_ref_property(k, c, l, v, b, seed):
    """The window kernel (interpret) is bit-identical to the jnp oracle
    across arbitrary (K, C, L, V, B) shapes — including the padding the
    ops wrapper adds to reach the block grid."""
    rng = np.random.RandomState(seed)
    cvars = jnp.asarray(rng.randint(0, v + 1, (k, c, l)), jnp.int32)
    csign = jnp.asarray(rng.rand(k, c, l) > 0.5)
    assign = jnp.asarray(rng.rand(k, b, v + 1) > 0.5)
    got = true_counts_window(cvars, csign, assign, interpret=True)
    want = true_counts_window_ref(cvars, csign, assign)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_clause_eval_window_on_real_packed_window():
    """Bucketed padded shapes from the real packer, tautology-padded
    clause rows included: the kernel must count the (v1 or not v1) padding
    rows as exactly one true literal like the oracle does."""
    from repro.core.cgra import CGRA
    from repro.core.dfg import running_example
    from repro.core.encode import EncoderSession
    from repro.core.sat.walksat_jax import pack_cnf_window
    sess = EncoderSession(running_example(), CGRA(2, 2))
    cnfs = [sess.encode(ii).cnf for ii in (2, 3, 4)]
    p = pack_cnf_window(cnfs)
    # every window has tautology padding (clause counts differ across IIs)
    assert any(c.n_clauses < p.n_clauses for c in cnfs)
    rng = np.random.RandomState(1)
    assign = jnp.asarray(rng.rand(3, 4, p.n_vars + 1) > 0.5)
    got = true_counts_window(p.cvars, p.csign.astype(bool), assign)
    want = true_counts_window_ref(p.cvars, p.csign.astype(bool), assign)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # padded rows are tautologies: exactly one true literal, never unsat
    for i, cnf in enumerate(cnfs):
        pad = np.asarray(got)[i, :, cnf.n_clauses:]
        np.testing.assert_array_equal(pad, 1)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 9), st.integers(2, 30),
       st.integers(1, 12), st.integers(1, 6), st.integers(0, 10_000))
def test_flip_update_matches_ref_property(k, b, v, c, o, seed):
    """The fused flip+tc-update kernel (interpret) is bit-identical to
    the occurrence-list oracle, including -1 occ padding and the dummy
    var-0 no-op flip of already-solved chains."""
    rng = np.random.RandomState(seed)
    assign = jnp.asarray(rng.rand(k, b, v + 1) > 0.5)
    tc = jnp.asarray(rng.randint(0, 4, (k, b, c)), jnp.int32)
    v_flip = jnp.asarray(rng.randint(0, v + 1, (k, b)), jnp.int32)
    occ_c = jnp.asarray(
        np.where(rng.rand(k, b, o) < 0.3, -1, rng.randint(0, c, (k, b, o))),
        jnp.int32)
    occ_s = jnp.asarray(rng.rand(k, b, o) > 0.5)
    new_val = jnp.asarray(rng.rand(k, b) > 0.5)
    ga, gt = flip_update(assign, tc, v_flip, occ_c, occ_s, new_val,
                         interpret=True)
    wa, wt = flip_update_ref(assign, tc, v_flip, occ_c, occ_s, new_val)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(wt))


def test_flip_update_keeps_true_counts_consistent():
    """Walking a real packed window with flip_update must keep the carried
    incremental counts equal to a fresh recount — the invariant both
    walksat engines rely on for the solved flag."""
    from repro.core.cgra import CGRA
    from repro.core.dfg import running_example
    from repro.core.encode import EncoderSession
    from repro.core.sat.walksat_jax import pack_cnf_window
    sess = EncoderSession(running_example(), CGRA(2, 2))
    p = pack_cnf_window([sess.encode(ii).cnf for ii in (3, 4)])
    rng = np.random.RandomState(7)
    K, B = 2, 4
    assign = jnp.asarray(rng.rand(K, B, p.n_vars + 1) > 0.5)
    tc = true_counts_window_ref(p.cvars, p.csign.astype(bool), assign)
    kk = jnp.arange(K)[:, None]
    for step in range(5):
        v_flip = jnp.asarray(rng.randint(0, p.n_vars + 1, (K, B)), jnp.int32)
        # a flip always *negates* the current value (the incremental
        # update's contract; probSAT never "re-sets" a var to itself)
        new_val = ~jnp.take_along_axis(assign, v_flip[..., None],
                                       axis=-1)[..., 0]
        occ_c = p.ovars[kk, v_flip]
        occ_s = p.osign[kk, v_flip]
        assign, tc = flip_update(assign, tc, v_flip, occ_c, occ_s, new_val)
        recount = true_counts_window_ref(p.cvars, p.csign.astype(bool),
                                         assign)
        np.testing.assert_array_equal(np.asarray(tc), np.asarray(recount))


@pytest.mark.skipif(not _COMPILED,
                    reason="Pallas compiled mode needs TPU/GPU; interpret "
                           "mode is covered on CPU")
def test_kernels_compiled_match_interpret():
    """On real accelerators the compiled lowering (Mosaic/Triton) must be
    bit-identical to interpret mode for both SAT kernels."""
    rng = np.random.RandomState(0)
    k, c, l, v, b, o = 2, 37, 3, 50, 8, 4
    cvars = jnp.asarray(rng.randint(0, v + 1, (k, c, l)), jnp.int32)
    csign = jnp.asarray(rng.rand(k, c, l) > 0.5)
    assign = jnp.asarray(rng.rand(k, b, v + 1) > 0.5)
    np.testing.assert_array_equal(
        np.asarray(true_counts_window(cvars, csign, assign,
                                      interpret=False)),
        np.asarray(true_counts_window(cvars, csign, assign,
                                      interpret=True)))
    tc = jnp.asarray(rng.randint(0, 4, (k, b, c)), jnp.int32)
    v_flip = jnp.asarray(rng.randint(0, v + 1, (k, b)), jnp.int32)
    occ_c = jnp.asarray(rng.randint(-1, c, (k, b, o)), jnp.int32)
    occ_s = jnp.asarray(rng.rand(k, b, o) > 0.5)
    new_val = jnp.asarray(rng.rand(k, b) > 0.5)
    got = flip_update(assign, tc, v_flip, occ_c, occ_s, new_val,
                      interpret=False)
    want = flip_update(assign, tc, v_flip, occ_c, occ_s, new_val,
                       interpret=True)
    for a, b_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


# -------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,window", [
    (2, 4, 2, 256, 256, 64, 0),
    (1, 2, 1, 200, 200, 32, 0),      # unaligned seq -> padding path
    (2, 4, 4, 128, 384, 64, 0),      # decode-ish: kv longer than q
    (1, 2, 2, 256, 256, 64, 64),     # sliding window
    (1, 8, 2, 128, 128, 128, 0),     # GQA group 4
])
def test_flash_matches_ref(b, hq, hkv, sq, sk, d, window):
    rng = np.random.RandomState(hq * sq)
    q = jnp.asarray(rng.randn(b, hq, sq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, hkv, sk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hkv, sk, d), jnp.float32)
    off = sk - sq
    got = flash_attention(q, k, v, causal=True, window=window, q_offset=off)
    want = attention_ref(q, k, v, causal=True, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.bfloat16)
    got = flash_attention(q, k, v)
    want = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


# --------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 3, 16, 8, 64),
    (1, 128, 2, 8, 4, 128),
    (1, 200, 1, 4, 4, 64),           # unaligned seq -> padding path
    (2, 64, 4, 32, 16, 16),
])
def test_ssd_scan_matches_sequential_ref(b, s, h, p, n, chunk):
    rng = np.random.RandomState(s + h)
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(rng.rand(b, s, h) * 0.5, jnp.float32)
    A_log = jnp.asarray(rng.rand(h), jnp.float32)
    B = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    C = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    D = jnp.asarray(rng.rand(h), jnp.float32)
    got = ssd_scan(x, dt, A_log, B, C, D, chunk=chunk)
    want = ssd_ref(x, dt, A_log, B, C, D)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-3, rtol=2e-3)


def test_layers_ssd_chunked_matches_sequential_ref():
    from repro.models.layers import ssd_chunked
    rng = np.random.RandomState(3)
    b, s, h, p, n = 2, 96, 2, 8, 8
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(rng.rand(b, s, h) * 0.5, jnp.float32)
    A_log = jnp.asarray(rng.rand(h), jnp.float32)
    B = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    C = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    D = jnp.asarray(rng.rand(h), jnp.float32)
    got = ssd_chunked(x, dt, A_log, B, C, D, chunk=32)   # 96 % 32 == 0
    want = ssd_ref(x, dt, A_log, B, C, D)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=2e-3, rtol=2e-3)
