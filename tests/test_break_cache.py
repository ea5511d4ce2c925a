"""The walk's break cache: after any number of probSAT steps, on either
engine, with the kernels or without, each chain's cached break counts equal
the recomputing oracle ``break_counts_ref``, and its per-clause sums of true
variable ids equal a from-scratch sum."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cgra import CGRA
from repro.core.cnf import CNF
from repro.core.dfg import running_example
from repro.core.mapper import MapperConfig, map_loop
from repro.core.sat.walksat_jax import (_POLL_CHUNKS, _break_update_one,
                                        _device_segment, _sat_kernels_mode,
                                        _walk_start, _window_chunk,
                                        break_counts_ref, pack_cnf_window)

B = 8


def _planted(seed: int, n_vars: int, n_clauses: int) -> CNF:
    """A random CNF that a hidden assignment satisfies, with a repeated
    variable, a tautology and a clause that holds both."""
    rng = np.random.default_rng(seed)
    hidden = rng.random(n_vars + 1) < 0.5
    cnf = CNF()
    cnf.new_vars(n_vars)
    for _ in range(n_clauses):
        k = int(rng.integers(1, 4))
        vs = rng.choice(np.arange(1, n_vars + 1), size=k, replace=False)
        lits = [int(v) if rng.random() < 0.5 else -int(v) for v in vs]
        v = int(vs[0])                      # one literal the hidden model sets
        lits[0] = v if hidden[v] else -v
        cnf.add(*lits)
    cnf.add(2, 2, 3)                        # repeated variable
    cnf.add(4, -4)                          # tautology
    cnf.add(5, -5, 5, 6)                    # both, with a third literal
    return cnf


CNFS = [_planted(1, 12, 30), _planted(2, 20, 56)]


def _true_sums(cnfs, assign, n_clauses):
    """[K, B, C] sum of the ids of each clause's true literals, by brute
    force over the clause lists (padding rows 0)."""
    out = np.zeros(assign.shape[:2] + (n_clauses,), np.int64)
    for k, cnf in enumerate(cnfs):
        for c, lits in enumerate(cnf.clauses):
            for lit in lits:
                v = abs(lit)
                out[k, :, c] += v * (assign[k, :, v] == (lit > 0))
    return out


def _breaks(cnf, assign):
    """[B, V+1] break counts by brute force: for each variable, the clauses
    whose one true literal is on it. Flipping it leaves them unsatisfied,
    the tautology (v or not v) aside, which the walk counts all the same."""
    out = np.zeros(assign.shape, np.int64)
    for lits in cnf.clauses:
        true = [[abs(l) for l in lits if assign[b, abs(l)] == (l > 0)]
                for b in range(assign.shape[0])]
        for b, vs in enumerate(true):
            if len(vs) == 1:
                out[b, vs[0]] += 1
    return out


def _assign0(packed, seed=3):
    return jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5,
                                (len(CNFS), B, packed.n_vars + 1))


def _check(packed, assign, tc, tsum, brk):
    want = jax.vmap(break_counts_ref)(packed.ovars, packed.osign, assign, tc)
    np.testing.assert_array_equal(np.asarray(brk), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(tsum),
        _true_sums(CNFS, np.asarray(assign), packed.n_clauses))


def test_break_counts_ref_counts_what_a_flip_breaks():
    packed = pack_cnf_window(CNFS)
    assign = _assign0(packed)
    tc = _walk_start(packed.cvars, packed.csign, packed.ovars, packed.osign,
                     assign, None)[0]
    for k, cnf in enumerate(CNFS):
        got = break_counts_ref(packed.ovars[k], packed.osign[k], assign[k],
                               tc[k])
        want = _breaks(cnf, np.asarray(assign[k]))
        np.testing.assert_array_equal(np.asarray(got)[:, :cnf.n_vars + 1],
                                      want[:, :cnf.n_vars + 1])
        assert not np.asarray(got)[:, cnf.n_vars + 1:].any()


def test_each_flip_keeps_the_cache_equal_to_the_oracle():
    """Flip every variable (the dummy 0 too) of every chain, one at a
    time, through the walk step's two updates alone."""
    packed = pack_cnf_window(CNFS)
    assign = _assign0(packed)[0]
    ov, os_ = packed.ovars[0], packed.osign[0]
    tc, tsum, brk = (x[0] for x in _walk_start(
        packed.cvars[:1], packed.csign[:1], ov[None], os_[None],
        assign[None], None))
    upd = jax.jit(jax.vmap(_break_update_one,
                           in_axes=(0, 0, 0, None, None, None, 0)))
    for v in range(CNFS[0].n_vars + 1):
        new_val = ~assign[:, v]
        brk = upd(brk, tsum, tc, ov[v], os_[v], jnp.int32(v), new_val)
        assign = assign.at[:, v].set(new_val)
        tc_new = _walk_start(packed.cvars[:1], packed.csign[:1], ov[None],
                             os_[None], assign[None], None)[0][0]
        tsum = tsum + v * (tc_new - tc)
        tc = tc_new
        np.testing.assert_array_equal(
            np.asarray(brk),
            np.asarray(break_counts_ref(ov, os_, assign, tc)))
        np.testing.assert_array_equal(
            np.asarray(tsum)[:, :CNFS[0].n_clauses],
            _true_sums(CNFS[:1], np.asarray(assign)[None],
                       CNFS[0].n_clauses)[0])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _host_walk(packed_arrays, assign0, keys, steps, kernels):
    cvars, csign, ovars, osign = packed_arrays
    tc, tsum, brk = _walk_start(cvars, csign, ovars, osign, assign0, kernels)
    return _window_chunk(cvars, csign, ovars, osign, assign0, tc, tsum, brk,
                         keys, steps, 2.3, kernels)


@pytest.mark.parametrize("kernels", ["0", "interpret"])
@pytest.mark.parametrize("engine", ["host", "device"])
def test_cache_equals_the_oracle_after_a_walk(engine, kernels, monkeypatch):
    """A K = 2 window of two formulas of different sizes (padded clause
    rows and variables), walked long enough that chains solve and then
    flip the dummy variable 0."""
    monkeypatch.setenv("REPRO_SAT_KERNELS", kernels)
    mode = _sat_kernels_mode()
    packed = pack_cnf_window(CNFS)
    arrays = (packed.cvars, packed.csign, packed.ovars, packed.osign)
    assign0 = _assign0(packed)
    steps = 160
    if engine == "host":
        keys = jax.random.split(jax.random.PRNGKey(5), len(CNFS))
        assign, tc, tsum, brk, _, picks = _host_walk(arrays, assign0, keys,
                                                     steps, mode)
    else:
        K, v1 = len(CNFS), packed.n_vars + 1
        tc0, tsum0, brk0 = _walk_start(*arrays, assign0, mode)
        state = (assign0, tc0, jax.random.PRNGKey(5), jnp.int32(0),
                 jnp.int32(64), jnp.zeros(K, bool), jnp.zeros((K, v1), bool),
                 jnp.zeros(K, bool), jnp.full(K, 2**31 - 1, jnp.int32),
                 jnp.zeros((K, v1), bool), tsum0, brk0, jnp.int32(0))
        state = _device_segment(_POLL_CHUNKS, 2.3, mode, None, *arrays,
                                jnp.int32(steps), jnp.int32(64), state)
        assign, tc, tsum, brk = state[0], state[1], state[10], state[11]
        steps = int(state[3])
        picks = state[12]
    assert int(picks) == steps > 0
    solved = ~np.any(np.asarray(tc) == 0, axis=-1)
    assert solved.any(axis=-1).all()          # each formula has a model
    # chains flipped the dummy variable: their var 0 moved off its start
    assert (np.asarray(assign)[..., 0] != np.asarray(assign0)[..., 0]).any()
    want_tc = _walk_start(*arrays, assign, None)[0]
    np.testing.assert_array_equal(np.asarray(tc), np.asarray(want_tc))
    _check(packed, assign, tc, tsum, brk)


def test_every_walked_step_read_the_cache():
    cfg = MapperConfig(solver="portfolio", seed=7, timeout_s=90)
    res = map_loop(running_example(), CGRA(2, 2), cfg)
    assert res.success
    walked = [a for a in res.attempts if a.walk_steps]
    assert walked
    for a in walked:
        assert a.walk_break_cached == a.walk_steps
