"""Batched probSAT/WalkSAT in JAX — the accelerator-native mapper search path.

The KMS CNF is lowered to dense padded tensors; a *batch* of candidate
assignments walks in parallel (one probSAT chain per batch row), so clause
evaluation becomes regular tensor work that the VPU/MXU executes well. On a
pod the batch is sharded over the mesh (see ``_maybe_shard_window``); the
first chain to satisfy the formula wins.

Two engines drive the chunked walk:

  * ``engine="device"`` (default) — the whole chunk schedule runs inside a
    single jitted :func:`jax.lax.while_loop`. Per-candidate solved flags,
    first-solution snapshots, and best-over-all-chunks near-miss state are
    device arrays; the host blocks only every ``_POLL_CHUNKS`` chunks on a
    tiny status tuple (``jax.block_until_ready``) to poll ``stop()`` /
    ``should_skip`` and extract freshly certified models. Chunk sizes are
    *traced* values, so one XLA executable covers every chunk of the
    progressive schedule instead of one compile per chunk length.
  * ``engine="host"`` — the PR 1/2 reference loop: one jitted fixed-length
    chunk per host iteration, flags polled after every chunk. Kept as the
    bit-compatibility oracle (same seeds => same models as the device
    engine) and selectable via ``REPRO_WALKSAT_ENGINE=host``.

Both engines share one inner step (``_pick_flip_one`` + the flip/true-count
update + the break-cache update), so they consume the PRNG stream
identically and return identical results for a fixed seed.

The pick reads probSAT's incremental break cache rather than recomputing
break counts: each chain carries ``tsum [C]``, the sum of the variable ids
of each clause's true literals (where a clause has exactly one true
literal, its critical variable), and ``brk [V+1]``, the number of clauses
each variable is critical in. A pick reads ``brk`` at the picked clause's
L literals; a flip of v moves ``brk`` from v's O occurrences
(``_break_update_one``) and ``tsum`` by v times each clause's change of
true count. Both are built once per walk from the start assignment
(``_walk_start``), ``brk`` with :func:`break_counts_ref`, the recomputing
formula, which is also the tests' oracle for the cache.

On TPU/GPU the true-count evaluation routes through the
``kernels/clause_eval`` Pallas kernel and the flip+incremental true-count
update through the fused ``kernels/flip_update`` kernel
(``REPRO_SAT_KERNELS`` overrides: ``0`` forces the pure-jnp path,
``interpret`` forces the kernels in interpret mode — the CPU-testable
route).

This solver is incomplete: it can certify SAT but returns UNKNOWN instead of
UNSAT — the Fig. 3 loop then falls back to CDCL/Z3 for the UNSAT proof.

``pack_cnf``/``true_counts_ref`` are also the reference oracle for the
``kernels/clause_eval`` Pallas kernel; ``break_counts_ref`` is the oracle
for the break cache.
"""
from __future__ import annotations

import functools
import os
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import spans
from ..cnf import CNF

_INT32_MAX = np.iinfo(np.int32).max

# chunks walked on-device between host polls of the status array (device
# engine): larger values amortise dispatch, smaller values make stop()/
# should_skip() more responsive. The per-chunk step count is already
# bounded by formula size (see _chunk_plan), so 4 keeps cancellation
# latency well under a second on real instances.
_POLL_CHUNKS = 4


class NonModelError(RuntimeError):
    """A walksat leg returned an assignment that does not satisfy its CNF.

    This is a *miscompiled-kernel / packer-bug* guard, not a user error: a
    chain is only reported SAT after its padded true-count vector shows
    every clause satisfied, so a failing ``CNF.check`` means the device
    computation and the host formula disagree. Raised as a structured
    error (never a bare ``assert``) so the guard survives ``python -O``.
    """


def _validate_model(cnf: CNF, model: List[bool], ctx: str) -> None:
    if not cnf.check(model):
        raise NonModelError(
            f"walksat returned a non-model ({ctx}): device true-counts "
            f"claim SAT but CNF.check fails on {cnf.n_vars} vars / "
            f"{cnf.n_clauses} clauses")


class WalkCounts(NamedTuple):
    """What one candidate's walk cost: probSAT steps and device segments
    walked while it was pending (candidates of one window walk together,
    so they share these), its real clause rows, the window's padded
    clause rows C, and the steps whose pick read the break cache (counted
    on the device; equal to ``steps``)."""
    steps: int
    segments: int
    rows: int
    rows_padded: int
    break_cached: int


class PackedCNF(NamedTuple):
    cvars: jnp.ndarray   # [C, Lmax] int32 var ids (1-based), 0 = padding
    csign: jnp.ndarray   # [C, Lmax] bool, True = positive literal
    ovars: jnp.ndarray   # [V+1, Omax] int32 clause ids (0-based), -1 = padding
    osign: jnp.ndarray   # [V+1, Omax] bool sign of the var in that clause
    n_vars: int
    n_clauses: int


class HostPack(NamedTuple):
    """Host-side (numpy) twin of :class:`PackedCNF` — what the session-level
    pack cache stores, so reuse never round-trips through device arrays."""
    cvars: np.ndarray
    csign: np.ndarray
    ovars: np.ndarray
    osign: np.ndarray
    n_vars: int
    n_clauses: int


def pack_cnf_np(cnf: CNF) -> HostPack:
    """Vectorised dense pack of one CNF, straight off the clause arena.

    The arena *is* the CSR form of the formula — ``lits[offs[i]:offs[i+1]]``
    is clause i — so the padded clause matrix is one scatter of the literal
    buffer at ``(repeat(clause_id, lens), ranges(lens))`` and the occurrence
    lists are the same scatter after a stable sort of the literals by
    variable (stability keeps each variable's occurrences in (clause,
    position) order, exactly the order the old per-clause append built).
    No per-clause Python iteration anywhere.
    """
    arena = getattr(cnf, "arena", None)
    if arena is not None:
        lits = arena.lits_view()
        offs = arena.offs_view()
        lens = np.diff(offs)
    else:   # degenerate / mock CNFs without an arena
        rows = [list(c) for c in cnf.clauses]
        lens = np.asarray([len(r) for r in rows], dtype=np.int64)
        lits = np.asarray([l for r in rows for l in r], dtype=np.int32)
        offs = np.concatenate([[0], np.cumsum(lens)])
    C = cnf.n_clauses
    V = cnf.n_vars
    n = lits.size
    lmax = int(lens.max()) if C else 1
    cvars = np.zeros((C, lmax), np.int32)
    csign = np.zeros((C, lmax), bool)
    rows = np.repeat(np.arange(C), lens)
    cols = np.arange(n) - np.repeat(offs[:-1], lens)
    av = np.abs(lits)
    sg = lits > 0
    cvars[rows, cols] = av
    csign[rows, cols] = sg
    counts = np.bincount(av, minlength=V + 1)
    omax = int(counts.max()) if counts.size else 0
    ovars = np.full((V + 1, omax), -1, np.int32)
    osign = np.zeros((V + 1, omax), bool)
    if n:
        order = np.argsort(av, kind="stable")
        va = av[order]
        j = np.arange(n) - (np.cumsum(counts) - counts)[va]
        ovars[va, j] = rows[order]
        osign[va, j] = sg[order]
    return HostPack(cvars, csign, ovars, osign, V, C)


def pack_cnf(cnf: CNF) -> PackedCNF:
    p = pack_cnf_np(cnf)
    return PackedCNF(jnp.asarray(p.cvars), jnp.asarray(p.csign),
                     jnp.asarray(p.ovars), jnp.asarray(p.osign),
                     p.n_vars, p.n_clauses)


def true_counts_ref(packed: PackedCNF, assign: jnp.ndarray) -> jnp.ndarray:
    """Per-clause count of satisfied literals. assign: [V+1] bool -> [C] int32.

    Pure-jnp oracle; the Pallas ``clause_eval`` kernel computes the same
    quantity blockwise (see repro.kernels.clause_eval).
    """
    mask = packed.cvars > 0
    vals = assign[packed.cvars] == packed.csign
    return jnp.sum(jnp.where(mask, vals, False), axis=-1).astype(jnp.int32)


def true_counts_batch(packed: PackedCNF, assign: jnp.ndarray,
                      use_kernel: bool | None = None) -> jnp.ndarray:
    """Batched per-clause true counts [B, C]; routes to the Pallas
    clause_eval kernel on TPU/GPU (compiled), jnp oracle elsewhere."""
    if use_kernel is None:
        use_kernel = jax.default_backend() in ("tpu", "gpu")
    if use_kernel:
        from ...kernels.clause_eval import true_counts as tc_kernel
        return tc_kernel(packed.cvars, packed.csign.astype(bool), assign)
    return jax.vmap(lambda a: true_counts_ref(packed, a))(assign)


def break_counts_ref(ovars: jnp.ndarray, osign: jnp.ndarray,
                     assign: jnp.ndarray, tc: jnp.ndarray) -> jnp.ndarray:
    """Break count of every variable: the clauses in which it is the sole
    true literal. ovars/osign [V+1, O]; assign [B, V+1] bool; tc [B, C]
    int32 -> [B, V+1] int32.

    Pure-jnp oracle of the walk's break cache, and its initial value.
    Built from the occurrence lists, so the tautology rows that
    ``pack_cnf_window`` pads with count for no variable."""
    valid = ovars >= 0
    tc_at = tc[:, jnp.where(valid, ovars, 0)]            # [B, V+1, O]
    supports = osign[None] == assign[:, :, None]       # v satisfies c
    return jnp.sum(valid[None] & supports & (tc_at == 1), axis=-1,
                   dtype=jnp.int32)


# occurrence slots per scatter of the sums' build: XLA:TPU's compile time
# for one scatter grows steeply with its index count (for a v5e, 23 s at
# the largest suite window's 213,200 slots, 0.3 s at 6,656), so the build
# loops over blocks of variables instead
_SUMS_BLOCK_SLOTS = 4096


def _true_var_sums(ovars: jnp.ndarray, osign: jnp.ndarray,
                   assign: jnp.ndarray, n_clauses: int) -> jnp.ndarray:
    """Per clause, the sum of the variable ids of its true literals, from
    the occurrence lists of a window: ovars/osign [K, V+1, O], assign
    [K, B, V+1] -> [K, B, C] int32 (0 on padding rows). A loop over blocks
    of variables scatters each block's chain-wide rows over the K·C
    clauses (a vmapped scatter would also lose its named scope in the TPU
    compiler's rewrite)."""
    k, v1, o = ovars.shape
    b = assign.shape[1]
    vb = max(1, min(v1, _SUMS_BLOCK_SLOTS // (k * o)))
    pad = -v1 % vb
    ovars = jnp.pad(ovars, ((0, 0), (0, pad), (0, 0)), constant_values=-1)
    osign = jnp.pad(osign, ((0, 0), (0, pad), (0, 0)))
    assign = jnp.pad(assign, ((0, 0), (0, 0), (0, pad)))
    base = (jnp.arange(k, dtype=jnp.int32) * n_clauses)[:, None, None]

    def block(j, t):
        ov = jax.lax.dynamic_slice_in_dim(ovars, j * vb, vb, axis=1)
        os_ = jax.lax.dynamic_slice_in_dim(osign, j * vb, vb, axis=1)
        a = jax.lax.dynamic_slice_in_dim(assign, j * vb, vb, axis=2)
        valid = ov >= 0
        supports = valid[:, None] & (os_[:, None] == a[..., None])
        ids = (j * vb + jnp.arange(vb, dtype=jnp.int32))[None, None, :, None]
        upd = jnp.where(supports, ids, 0)              # [K, B, vb, O]
        rows = (base + jnp.where(valid, ov, 0)).reshape(-1)
        return t.at[rows].add(jnp.moveaxis(upd, 1, 3).reshape(-1, b))

    t = jax.lax.fori_loop(0, (v1 + pad) // vb, block,
                          jnp.zeros((k * n_clauses, b), jnp.int32))
    return jnp.swapaxes(t.reshape(k, n_clauses, b), 1, 2)


# ----------------------------------------------------------- kernel routing

def _sat_kernels_mode() -> Optional[str]:
    """How the walksat engines evaluate/update true counts.

    ``None``   — pure-jnp path (the default on CPU).
    ``"auto"`` — Pallas kernels, compiled (TPU Mosaic / GPU Triton).
    ``"interpret"`` — Pallas kernels in interpret mode (CPU-testable).

    ``REPRO_SAT_KERNELS`` overrides: ``0``/``off`` => jnp everywhere,
    ``interpret`` => interpret-mode kernels, ``1``/``compiled`` => compiled.
    On a TPU the kernels never run in interpret mode: asking for it (here
    or through ``REPRO_PALLAS_INTERPRET``) raises.
    """
    env = os.environ.get("REPRO_SAT_KERNELS", "").strip().lower()
    backend = jax.default_backend()
    if env in ("0", "false", "off", "jnp"):
        return None
    if env == "interpret":
        mode = "interpret"
    elif env in ("1", "true", "on", "compiled"):
        mode = "auto"
    else:
        mode = "auto" if backend in ("tpu", "gpu") else None
    if backend == "tpu" and mode is not None:
        from ...kernels.clause_eval import resolve_interpret
        if mode == "interpret" or resolve_interpret(None):
            raise RuntimeError(
                "interpret-mode Pallas kernels requested on a TPU "
                "(REPRO_SAT_KERNELS / REPRO_PALLAS_INTERPRET); the device "
                "walk runs the compiled kernels only")
    return mode


def _batch_sharded(fn, mesh, n_replicated: int, n_batch: int,
                   n_out: int):
    """Run a kernel wrapper per device on its slice of the restart batch
    (axis 1 of every batched operand). A ``pallas_call`` is not
    partitioned by GSPMD, so on a sharded walk each device must see only
    its own chains; without a mesh ``fn`` is returned as is."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P
    bat = P(None, "dev")
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(),) * n_replicated + (bat,) * n_batch,
        out_specs=bat if n_out == 1 else (bat,) * n_out,
        check_vma=False)   # pallas_call outputs carry no varying-axes type


def _window_tc(cvars: jnp.ndarray, csign: jnp.ndarray, assign: jnp.ndarray,
               kernels: Optional[str], mesh=None) -> jnp.ndarray:
    """Window true counts [K, B, C] — the inner evaluation of the sweep,
    routed through the Pallas ``clause_eval`` kernel when enabled."""
    if kernels is not None:
        from ...kernels.clause_eval import true_counts_window
        interpret = True if kernels == "interpret" else None
        return _batch_sharded(
            lambda cv, cs, a: true_counts_window(cv, cs, a,
                                                 interpret=interpret),
            mesh, 2, 1, 1)(cvars, csign, assign)

    def per_k(cv, cs, a):                     # a: [B, V+1]
        mask = cv > 0
        vals = a[:, cv] == cs[None]           # [B, C, L]
        return jnp.sum(jnp.where(mask[None], vals, False),
                       axis=-1).astype(jnp.int32)
    return jax.vmap(per_k)(cvars, csign, assign)


# ------------------------------------------------------------ probSAT step

def _pick_flip_one(cvars, brk, assign, tc, key, cb):
    """One probSAT variable pick for a batch of chains of one CNF.

    assign: [B, V+1] bool, tc: [B, C] int32, brk: [B, V+1] int32 — the
    break cache, each variable's count of clauses it is the sole true
    literal of (:func:`break_counts_ref` recomputes it). The pick reads
    the cache at the picked clause's Lmax literals. Returns (v_flip [B] —
    var 0 (the dummy) for already-solved chains, new_val [B], key')."""
    with jax.named_scope("walk.pick.clause"):
        unsat = tc == 0                       # [B, C]
        any_unsat = jnp.any(unsat, axis=-1)   # [B]
        key, k1, k2 = jax.random.split(key, 3)
        # pick a random unsat clause per chain
        logits = jnp.where(unsat, 0.0, -1e30)
        cidx = jax.random.categorical(k1, logits, axis=-1)  # [B]
    with jax.named_scope("walk.pick.break"):
        vs = cvars[cidx]                      # [B, Lmax]
        vmask = vs > 0
        # the cache at the clause's literals, as a one-hot read over the
        # variables (a TPU gathers index by index)
        ids = jnp.arange(brk.shape[-1], dtype=jnp.int32)
        brk = jnp.sum(jnp.where(vs[..., None] == ids, brk[:, None, :], 0),
                      axis=-1)                # [B, Lmax]
        # probSAT polynomial heuristic: p ∝ (1 + brk)^-cb
        w = jnp.where(vmask, -cb * jnp.log1p(brk.astype(jnp.float32)),
                      -1e30)
        pick = jax.random.categorical(k2, w, axis=-1)       # [B]
        v_flip = jnp.take_along_axis(vs, pick[:, None], axis=-1)[:, 0]
        v_flip = jnp.where(any_unsat, v_flip, 0)  # flip dummy var 0 if solved
        new_val = ~jnp.take_along_axis(assign, v_flip[:, None], axis=-1)[:, 0]
    return v_flip, new_val, key


def _apply_flip_one(ovars, osign, assign, tc, v_flip, new_val):
    """Apply the flip + incremental true-count update via occurrence lists
    (pure-jnp reference for the fused ``kernels/flip_update`` kernel)."""
    assign = assign.at[jnp.arange(assign.shape[0]), v_flip].set(new_val)
    occ_cf = ovars[v_flip]                    # [B, Omax]
    occ_sf = osign[v_flip]
    validf = occ_cf >= 0
    delta = jnp.where(occ_sf == new_val[:, None], 1, -1)
    delta = jnp.where(validf, delta, 0)
    tc = tc + jnp.zeros_like(tc).at[
        jnp.arange(tc.shape[0])[:, None], jnp.where(validf, occ_cf, 0)
    ].add(delta)
    return assign, tc


def _break_update_one(brk, tsum, tc, occ, osg, v, new_val):
    """Break counts of one chain after a flip of variable ``v``.

    brk [V+1]; tsum [C], tc [C] — the sums and true counts before the
    flip; occ/osg [O] — v's occurrence row (clause ids, -1 = padding;
    literal signs). Each distinct clause of the row moves by its net
    change of true literals (a clause that holds v twice moves by the sum
    of both): its old critical variable, if it had one, loses the clause
    and its new one gains it. Padding slots, and the dummy variable 0 that
    solved chains flip, touch nothing. The 2·O changes land by a one-hot
    sum over the variables, not a scatter. Returns brk'."""
    valid = occ >= 0
    oc = jnp.where(valid, occ, 0)
    delta = jnp.where(valid, jnp.where(osg == new_val, 1, -1), 0)
    same = (oc[:, None] == oc[None, :]) & valid[:, None] & valid[None, :]
    d = jnp.sum(jnp.where(same, delta[None, :], 0), axis=-1)   # net change
    o = jnp.arange(oc.shape[0])
    first = valid & ~jnp.any(same & (o[None, :] < o[:, None]), axis=-1)
    t0, s0 = tc[oc], tsum[oc]
    idx = jnp.concatenate([s0, s0 + d * v])   # old, new critical variable
    upd = jnp.concatenate([jnp.where(first & (t0 == 1), -1, 0),
                           jnp.where(first & (t0 + d == 1), 1, 0)])
    ids = jnp.arange(brk.shape[0], dtype=jnp.int32)
    return brk + jnp.sum(jnp.where(idx[:, None] == ids, upd[:, None], 0),
                         axis=0, dtype=jnp.int32)


def _window_chunk(cvars, csign, ovars, osign, assign, tc, tsum, brk, keys,
                  n_steps, cb, kernels: Optional[str], mesh=None):
    """Walk all K CNFs for ``n_steps`` probSAT steps (n_steps may be a
    traced scalar — both engines share this one implementation, so they
    consume the PRNG stream identically and stay bit-compatible).

    assign: [K, B, V+1] bool; tc, tsum: [K, B, C] int32; brk: [K, B, V+1]
    int32; keys: [K, 2]. Returns (assign, tc, tsum, brk, keys, picks):
    ``picks`` counts the steps whose pick read the break cache.
    """
    del csign  # only the pick/update tensors are read here

    def body(_, carry):
        assign, tc, tsum, brk, keys, picks = carry
        v_flip, new_val, keys = jax.vmap(
            lambda cv, b, a, t, k: _pick_flip_one(cv, b, a, t, k, cb)
        )(cvars, brk, assign, tc, keys)
        with jax.named_scope("walk.flip"):
            kk = jnp.arange(assign.shape[0])[:, None]
            occ_c = ovars[kk, v_flip]          # [K, B, O]
            occ_s = osign[kk, v_flip]
            with jax.named_scope("walk.pick.break"):
                brk = jax.vmap(jax.vmap(_break_update_one))(
                    brk, tsum, tc, occ_c, occ_s, v_flip, new_val)
            tc_old = tc
            if kernels is not None:
                from ...kernels.flip_update import flip_update
                interpret = True if kernels == "interpret" else None
                assign, tc = _batch_sharded(
                    lambda *a: flip_update(*a, interpret=interpret),
                    mesh, 0, 6, 2)(assign, tc, v_flip, occ_c, occ_s,
                                   new_val)
            else:
                assign, tc = jax.vmap(_apply_flip_one)(
                    ovars, osign, assign, tc, v_flip, new_val)
            with jax.named_scope("walk.pick.break"):
                # a clause's sum moves by v for each true literal of v it
                # gains or loses: one dense pass, no scatter
                tsum = tsum + v_flip[..., None] * (tc - tc_old)
        return assign, tc, tsum, brk, keys, picks + 1

    return jax.lax.fori_loop(0, n_steps, body,
                             (assign, tc, tsum, brk, keys, jnp.int32(0)))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _walk_start(cvars, csign, ovars, osign, assign0, kernels: Optional[str],
                mesh=None):
    """The walk state a start assignment implies: (tc [K,B,C], tsum
    [K,B,C], brk [K,B,V+1]). The true counts are scoped ``walk.init``;
    the break cache's build is ``walk.pick.break``, the pick's cost."""
    with jax.named_scope("walk.init"):
        tc0 = _window_tc(cvars, csign, assign0, kernels, mesh)
    with jax.named_scope("walk.pick.break"):
        tsum0 = _true_var_sums(ovars, osign, assign0, cvars.shape[1])
        brk0 = jax.vmap(break_counts_ref)(ovars, osign, assign0, tc0)
    return tc0, tsum0, brk0


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 9, 10))
def _run_chains_window(cvars: jnp.ndarray, csign: jnp.ndarray,
                       ovars: jnp.ndarray, osign: jnp.ndarray,
                       n_vars: int, steps: int, cb: float,
                       assign0: jnp.ndarray, keys: jnp.ndarray,
                       kernels: Optional[str] = None, mesh=None,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fixed-length chunk of probSAT over a *window* of K CNFs (the
    host engine's unit of work; one jit entry per chunk length).

    cvars/csign: [K, C, Lmax]; ovars/osign: [K, V+1, Omax];
    assign0: [K, B, V+1]; keys: [K, 2]. Returns (solved [K, B], assign,
    per-clause true counts [K, B, C] — the near-miss signal —, the steps
    whose pick read the break cache).
    """
    del n_vars
    tc0, tsum0, brk0 = _walk_start(cvars, csign, ovars, osign, assign0,
                                   kernels, mesh)
    assign, tc, _, _, _, picks = _window_chunk(
        cvars, csign, ovars, osign, assign0, tc0, tsum0, brk0, keys, steps,
        cb, kernels, mesh)
    with jax.named_scope("walk.chunk_end"):
        solved = ~jnp.any(tc == 0, axis=-1)
    return solved, assign, tc, picks


# -------------------------------------------------------- chunk scheduling

def _bucket(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def _chunk_plan(steps: int, n_clauses: int) -> Tuple[int, int]:
    """(cap, first_chunk) of the progressive chunk schedule, shared by both
    walksat entry points: the per-chunk step count is bounded by the caller
    budget AND by formula size (stop/skip are only polled between chunks,
    and a cancelled racer must drain fast — fewer steps for big formulas),
    and the first chunk never exceeds the cap, so a small ``steps`` budget
    is honoured instead of being rounded up to 256."""
    cap = max(64, min(steps, 2048, 2_000_000 // max(n_clauses, 1)))
    return cap, min(256, cap)


def _next_chunk(prev: int, cap: int, remaining: int) -> int:
    """Progressive chunk schedule: double from the first chunk up to
    ``cap``, then shrink back down (halving only, so the handful of jit
    entries the host engine needs is shared) to land on the step budget
    without overshooting by more than one minimal chunk."""
    c = min(prev * 2, cap)
    while c > 256 and c > remaining:
        c //= 2
    return c


def _next_chunk_jnp(prev, cap, remaining):
    """Traced twin of :func:`_next_chunk` for the device engine's
    while_loop (cap <= 2048, so 5 unrolled halvings always suffice)."""
    c = jnp.minimum(prev * 2, cap)
    for _ in range(5):
        c = jnp.where((c > 256) & (c > remaining), c // 2, c)
    return c


def _init_assign(key: jnp.ndarray, batch: int, n_vars_padded: int,
                 init: Optional[List[bool]]) -> jnp.ndarray:
    """Initial chain assignments [B, V+1]. Without ``init``: uniform
    random. With ``init`` (a warm start, e.g. the previous II's best
    near-miss under the shared variable numbering): chain 0 starts from it
    exactly and chain b flips a growing fraction (up to half) of the
    variables, so the batch explores a widening neighbourhood of the hint
    while keeping full random restarts in the tail.

    The hint is truncated/padded defensively: a sweep window can *shrink*
    (e.g. the previous window's II bucketed to a larger padded var count),
    so ``init`` may be longer or shorter than this window's variable
    space — extra entries are dropped, missing ones default to False."""
    if init is None:
        return jax.random.bernoulli(key, 0.5, (batch, n_vars_padded + 1))
    base = np.zeros(n_vars_padded + 1, bool)
    hint = np.asarray(init, bool)[:n_vars_padded]
    base[1:len(hint) + 1] = hint
    ps = jnp.linspace(0.0, 0.5, batch)[:, None]
    flips = jax.random.bernoulli(key, ps, (batch, n_vars_padded + 1))
    return jnp.asarray(base)[None, :] ^ flips


def pack_cnf_window(cnfs: List[CNF],
                    packs: Optional[List[Optional[HostPack]]] = None,
                    ) -> PackedCNF:
    """Pack K CNFs into one stacked PackedCNF padded to common shapes.

    Shorter clause lists are padded with the tautology clause (v1 ∨ ¬v1) —
    always exactly one true literal, so padded rows are never selected as
    unsat and never reach a solved flag. Padding rows are *excluded* from
    the occurrence lists, so break counts and incremental true-count
    updates are unaffected. Variable counts are padded to the max; extra
    vars occur in no clause and are never flipped.

    All dims are rounded up to coarse buckets so different windows (other
    kernels, other CGRA sizes) reuse the same jitted computation instead of
    paying a fresh XLA compile per instance shape.

    ``packs``, when given, supplies a precomputed :func:`pack_cnf_np` per
    CNF (``None`` entries are packed here) — the session-level cache path
    that makes warm window solves skip per-CNF packing entirely.
    """
    with spans.span("walk.pack"):
        cvars, csign, ovars, osign, V, C = _stack_packs(cnfs, packs)
    # the span times the enqueue of the copies, not their arrival
    with spans.span("walk.upload"):
        return PackedCNF(jnp.asarray(cvars), jnp.asarray(csign),
                         jnp.asarray(ovars), jnp.asarray(osign), V, C)


def _stack_packs(cnfs: List[CNF],
                 packs: Optional[List[Optional[HostPack]]]):
    """The numpy half of :func:`pack_cnf_window`: (cvars, csign, ovars,
    osign, V, C) padded to the window's common bucketed shapes."""
    host: List[HostPack] = []
    for k, c in enumerate(cnfs):
        p = packs[k] if packs is not None else None
        host.append(p if p is not None else pack_cnf_np(c))
    K = len(host)
    V = _bucket(max(p.n_vars for p in host), 128)
    C = _bucket(max(p.n_clauses for p in host), 1024)
    L = max(p.cvars.shape[1] for p in host)
    O = max(p.ovars.shape[1] for p in host)
    L = _bucket(max(L, 2), 4)  # room for the (v1, ¬v1) padding tautology
    O = _bucket(O, 8)
    cvars = np.zeros((K, C, L), np.int32)
    csign = np.zeros((K, C, L), bool)
    ovars = np.full((K, V + 1, O), -1, np.int32)
    osign = np.zeros((K, V + 1, O), bool)
    for k, p in enumerate(host):
        c, l = p.cvars.shape
        cvars[k, :c, :l] = p.cvars
        csign[k, :c, :l] = p.csign
        # tautology padding for clause rows [c, C)
        cvars[k, c:, 0] = 1
        cvars[k, c:, 1] = 1
        csign[k, c:, 0] = True
        csign[k, c:, 1] = False
        v, o = p.ovars.shape
        ovars[k, :v, :o] = p.ovars
        osign[k, :v, :o] = p.osign
    return cvars, csign, ovars, osign, V, C


def _maybe_shard_window(assign0: jnp.ndarray):
    """Shard the (II-window x restart-batch) grid over the device mesh.

    On multi-device hosts the restart batch is split across devices (each
    device walks an independent slice of chains; the clause tensors are
    small and replicated) and GSPMD propagates the layout through the
    jitted engines — the per-candidate solved/near-miss reductions become
    cross-device all-reduces, and the Pallas kernels run per device on
    their batch slice (:func:`_batch_sharded`). Single-device hosts pass
    through untouched. Returns (assign0, mesh or None)."""
    n_dev = jax.device_count()
    if n_dev <= 1 or assign0.shape[1] % n_dev != 0:
        return assign0, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()), ("dev",))
    return (jax.device_put(assign0,
                           NamedSharding(mesh, P(None, "dev", None))), mesh)


# ---------------------------------------------------------- device engine

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _device_segment(poll_chunks: int, cb: float, kernels: Optional[str],
                    mesh, cvars, csign, ovars, osign, steps, cap, state):
    """Run up to ``poll_chunks`` chunks of the progressive schedule wholly
    on device, early-exiting when every live candidate has a solved chain.

    ``state`` carries the full walk: (assign [K,B,V+1], tc [K,B,C], key,
    done, chunk, solved [K], solved_assign [K,V+1] — the assignment of the
    first chain observed solved, snapshotted in the chunk it solved so a
    late poll returns the same model the per-chunk host engine would have,
    skip [K], best_unsat [K], best_assign [K,V+1] — best-over-all-chunks
    near-miss state, tracked only while a candidate is still pending —,
    tsum [K,B,C] and brk [K,B,V+1] — the break cache, see
    ``_break_update_one`` —, picks — the steps whose pick read the cache).
    Only ``solved``/``done``/``picks`` need to reach the host between
    segments; the big buffers stay device-resident for the next segment.
    """
    K = state[0].shape[0]

    # the chunk's bookkeeping (loop test, key split, solved flags,
    # snapshots, schedule) is scoped walk.chunk_end; the steps carry
    # their own walk.pick.* / walk.flip scopes
    def cond(st):
        _, _, _, done, _, solved, _, skip, _, _, _, _, _, polls = st
        with jax.named_scope("walk.chunk_end"):
            return ((done < steps) & jnp.any(~(solved | skip))
                    & (polls < poll_chunks))

    def body(st):
        (assign, tc, key, done, chunk, solved, solved_assign, skip,
         best_unsat, best_assign, tsum, brk, picks, polls) = st
        with jax.named_scope("walk.chunk_end"):
            key, kc = jax.random.split(key)
            keys = jax.random.split(kc, K)
        assign, tc, tsum, brk, _, n = _window_chunk(
            cvars, csign, ovars, osign, assign, tc, tsum, brk, keys, chunk,
            cb, kernels, mesh)
        with jax.named_scope("walk.chunk_end"):
            chain_ok = ~jnp.any(tc == 0, axis=-1)       # [K, B]
            cand_ok = jnp.any(chain_ok, axis=-1)        # [K]
            fresh = cand_ok & ~solved
            row = jnp.argmax(chain_ok, axis=-1)         # first solved chain
            snap = assign[jnp.arange(K), row]
            solved_assign = jnp.where(fresh[:, None], snap, solved_assign)
            solved = solved | fresh
            # near-miss: best assignment over all chunks, per still-pending
            # candidate (solved/skipped candidates stop accumulating)
            n_unsat = jnp.sum(tc == 0, axis=-1)         # [K, B]
            bu = jnp.min(n_unsat, axis=-1)
            brow = jnp.argmin(n_unsat, axis=-1)
            improve = ~solved & ~skip & (bu < best_unsat)
            best_unsat = jnp.where(improve, bu, best_unsat)
            best_assign = jnp.where(improve[:, None],
                                    assign[jnp.arange(K), brow], best_assign)
            done = done + chunk
            chunk = _next_chunk_jnp(chunk, cap, steps - done)
        return (assign, tc, key, done, chunk, solved, solved_assign, skip,
                best_unsat, best_assign, tsum, brk, picks + n, polls + 1)

    out = jax.lax.while_loop(cond, body, state + (jnp.int32(0),))
    return out[:-1]


def _solve_window_device(cnfs, live, packed, results, *, seed, steps, batch,
                         cb, stop, should_skip, on_sat, inits, near_miss,
                         on_near_miss, count):
    from . import SAT
    K = len(live)
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    init_keys = jax.random.split(k0, K)
    # the start assignments are small eager programs that carry no walk.*
    # scope; the true counts and the break cache they imply are one jitted
    # program, scoped as the host engine's chunk scopes them
    assign0 = jnp.stack([
        _init_assign(init_keys[j], batch, packed.n_vars,
                     inits[live[j]] if inits is not None else None)
        for j in range(K)])
    assign0, mesh = _maybe_shard_window(assign0)
    kernels = _sat_kernels_mode()
    cap, chunk0 = _chunk_plan(steps, packed.n_clauses)
    tc0, tsum0, brk0 = _walk_start(packed.cvars, packed.csign, packed.ovars,
                                   packed.osign, assign0, kernels, mesh)
    v1 = packed.n_vars + 1
    state = (assign0, tc0, key,
             jnp.int32(0), jnp.int32(chunk0),
             jnp.zeros(K, bool), jnp.zeros((K, v1), bool),
             jnp.zeros(K, bool),
             jnp.full(K, _INT32_MAX, jnp.int32), jnp.zeros((K, v1), bool),
             tsum0, brk0, jnp.int32(0))
    skip_host = np.zeros(K, bool)
    pending = set(range(K))
    nm_emitted = np.full(K, _INT32_MAX, np.int64)   # last streamed quality
    done = segments = 0
    while done < steps and pending:
        if stop is not None and stop():
            break
        if should_skip is not None:
            newly = [j for j in sorted(pending) if should_skip(live[j])]
            if newly:
                for j in newly:
                    pending.discard(j)
                    skip_host[j] = True
                if not pending:
                    break
                state = state[:7] + (jnp.asarray(skip_host),) + state[8:]
        with spans.span("walk.segment"):
            state = _device_segment(_POLL_CHUNKS, cb, kernels, mesh,
                                    packed.cvars, packed.csign,
                                    packed.ovars, packed.osign,
                                    jnp.int32(steps), jnp.int32(cap), state)
            # the host blocks only on the tiny status; the walk state
            # (assignments, true counts, break cache, near-miss buffers)
            # stays on device
            solved_dev, done_dev, picks_dev = jax.block_until_ready(
                (state[5], state[3], state[12]))
        solved_np = np.asarray(solved_dev)
        done = int(done_dev)
        segments += 1
        count(pending, done, segments, int(picks_dev))
        with spans.span("walk.extract"):
            for j in sorted(pending):
                if not solved_np[j]:
                    continue
                i = live[j]
                model = [bool(b) for b in
                         np.asarray(state[6][j])[1:cnfs[i].n_vars + 1]]
                _validate_model(cnfs[i], model,
                                f"device engine, candidate {i}")
                results[i] = (SAT, model)
                pending.discard(j)
                if on_sat is not None:
                    on_sat(i, model)
            if on_near_miss is not None and pending:
                # stream near-miss improvements at each poll — the
                # caller's feedback channel (e.g. CDCL phase hints) sees
                # them while the walk is still running, not only at
                # budget exhaustion
                bu = np.asarray(state[8])
                for j in sorted(pending):
                    if bu[j] < nm_emitted[j]:
                        nm_emitted[j] = bu[j]
                        i = live[j]
                        on_near_miss(
                            i, int(bu[j]),
                            [bool(b) for b in
                             np.asarray(state[9][j])[1:cnfs[i].n_vars + 1]])
    if near_miss is not None and pending:
        with spans.span("walk.extract"):
            bu = np.asarray(state[8])
            ba = np.asarray(state[9])
            for j in sorted(pending):
                if bu[j] >= _INT32_MAX:
                    continue
                i = live[j]
                near_miss[i] = (int(bu[j]), [bool(b) for b in
                                             ba[j][1:cnfs[i].n_vars + 1]])
    return results


# ------------------------------------------------------------ host engine

def _solve_window_host(cnfs, live, packed, results, *, seed, steps, batch,
                       cb, stop, should_skip, on_sat, inits, near_miss,
                       on_near_miss, count):
    """The per-chunk host loop (PR 1/2 reference engine): identical chunk
    schedule, PRNG stream, and near-miss bookkeeping as the device engine,
    with flags polled after every chunk."""
    from . import SAT
    K = len(live)
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    init_keys = jax.random.split(k0, K)
    assign0 = jnp.stack([
        _init_assign(init_keys[j], batch, packed.n_vars,
                     inits[live[j]] if inits is not None else None)
        for j in range(K)])
    assign0, mesh = _maybe_shard_window(assign0)
    kernels = _sat_kernels_mode()
    cap, chunk = _chunk_plan(steps, packed.n_clauses)
    done = segments = picks = 0
    pending = set(range(K))
    # best-over-all-chunks near-miss per candidate (not final-chunk-only)
    nm_best = {j: (_INT32_MAX, None) for j in range(K)}
    while done < steps and pending:
        if stop is not None and stop():
            break
        key, kc = jax.random.split(key)
        keys = jax.random.split(kc, K)
        with spans.span("walk.segment"):
            solved, assign, tc, n = _run_chains_window(
                packed.cvars, packed.csign, packed.ovars, packed.osign,
                packed.n_vars, chunk, cb, assign0, keys, kernels, mesh)
            solved_np = np.asarray(solved)
        segments += 1
        picks += int(n)
        count(pending, done + chunk, segments, picks)
        with spans.span("walk.extract"):
            for j in sorted(pending):
                i = live[j]
                if should_skip is not None and should_skip(i):
                    pending.discard(j)
                    continue
                if not solved_np[j].any():
                    continue
                row = int(np.argmax(solved_np[j]))
                model = [bool(b) for b in
                         np.asarray(assign[j, row])[1:cnfs[i].n_vars + 1]]
                _validate_model(cnfs[i], model,
                                f"host engine, candidate {i}")
                results[i] = (SAT, model)
                pending.discard(j)
                if on_sat is not None:
                    on_sat(i, model)
            if (near_miss is not None or on_near_miss is not None) \
                    and pending:
                n_unsat = np.asarray(jnp.sum(tc == 0, axis=-1))  # [K, B]
                assign_np = None
                for j in sorted(pending):
                    row = int(np.argmin(n_unsat[j]))
                    if int(n_unsat[j, row]) < nm_best[j][0]:
                        if assign_np is None:
                            assign_np = np.asarray(assign)
                        nm_best[j] = (int(n_unsat[j, row]),
                                      assign_np[j, row].copy())
                        if on_near_miss is not None:
                            i = live[j]
                            on_near_miss(
                                i, nm_best[j][0],
                                [bool(b) for b in
                                 nm_best[j][1][1:cnfs[i].n_vars + 1]])
        assign0 = assign
        done += chunk
        chunk = _next_chunk(chunk, cap, steps - done)
    if near_miss is not None:
        for j in sorted(pending):
            nu, arr = nm_best[j]
            if arr is None:
                continue
            i = live[j]
            near_miss[i] = (nu, [bool(b) for b in arr[1:cnfs[i].n_vars + 1]])
    return results


# -------------------------------------------------------------- front door

def solve_walksat_window(cnfs: List[CNF], *, seed: int = 0,
                         steps: int = 8192, batch: int = 24, cb: float = 2.3,
                         stop=None, should_skip=None, on_sat=None,
                         inits: Optional[List[Optional[List[bool]]]] = None,
                         near_miss: Optional[dict] = None,
                         on_near_miss=None,
                         engine: Optional[str] = None,
                         packed: Optional[PackedCNF] = None,
                         packs: Optional[List[Optional[HostPack]]] = None,
                         walk_counts: Optional[dict] = None,
                         ) -> List[Tuple[str, Optional[List[bool]]]]:
    """Batched probSAT across a window of candidate-II CNFs.

    All K formulas walk concurrently inside one jitted computation (vmapped
    restarts over the stacked clause tensors). Incomplete: per-CNF result is
    SAT or UNKNOWN, never UNSAT (structurally-empty-clause CNFs excepted).

    ``stop()`` aborts the whole window; ``should_skip(i)`` marks candidate i
    as no longer interesting (e.g. its complete solver already finished);
    ``on_sat(i, model)`` fires as soon as candidate i is certified, so the
    caller can early-cancel other work while remaining candidates keep
    walking.

    ``inits[i]`` warm-starts candidate i's chains from a prior assignment
    (see ``_init_assign``); ``near_miss``, when given a dict, receives
    ``{i: (n_unsat, assignment)}`` — the best assignment each *still
    pending* candidate reached over the whole walk (solved and skipped
    candidates are excluded, so the session's warm-start dict is never
    polluted with stale or irrelevant assignments). ``on_near_miss(i,
    n_unsat, assignment)`` streams improvements *during* the walk (per
    host poll on the device engine, per chunk on the host engine) — the
    asynchronous feedback channel the solver portfolio uses to seed CDCL
    phase hints while the racer is still walking.

    ``engine`` selects the chunk driver: ``"device"`` (default) keeps the
    whole schedule in one jitted while_loop with the host polling a tiny
    status array every few chunks; ``"host"`` is the per-chunk reference
    loop. Both are bit-compatible for a fixed seed;
    ``REPRO_WALKSAT_ENGINE`` overrides the default.

    ``packed`` supplies a ready stacked window pack (used only when every
    candidate turns out live, i.e. it covers exactly the CNFs walked);
    ``packs`` supplies per-CNF host packs for the stacker. Both come from
    the ``SolverSession`` pack cache — a warm sweep leg re-solving an
    unchanged window skips packing entirely.

    ``walk_counts``, when given a dict, receives ``{i: WalkCounts}`` for
    every candidate that walked, updated after each device segment while
    the candidate is pending, so a caller reading it from another thread
    sees the steps walked so far.
    """
    from . import SAT, UNKNOWN, UNSAT
    K = len(cnfs)
    results: List[Tuple[str, Optional[List[bool]]]] = [(UNKNOWN, None)] * K
    live = []
    for i, cnf in enumerate(cnfs):
        arena = getattr(cnf, "arena", None)
        if arena is not None:
            has_empty = bool((np.diff(arena.offs_view()) == 0).any())
        else:
            has_empty = any(len(c) == 0 for c in cnf.clauses)
        if getattr(cnf, "trivially_unsat", False) or has_empty:
            results[i] = (UNSAT, None)
        elif cnf.n_clauses == 0 or cnf.n_vars == 0:
            results[i] = (SAT, [False] * cnf.n_vars)
            if on_sat is not None:
                on_sat(i, results[i][1])
        else:
            live.append(i)
    if not live:
        return results
    from ..device import require_attached_backend
    require_attached_backend()
    if engine is None:
        engine = os.environ.get("REPRO_WALKSAT_ENGINE", "device")
    if engine not in ("device", "host"):
        raise ValueError(f"unknown walksat engine {engine!r}")
    if packed is None or len(live) != K:
        packed = pack_cnf_window(
            [cnfs[i] for i in live],
            [packs[i] for i in live] if packs is not None else None)

    def count(pending, steps_walked: int, segments: int,
              picks: int) -> None:
        if walk_counts is not None:
            for j in pending:
                i = live[j]
                walk_counts[i] = WalkCounts(steps_walked, segments,
                                            cnfs[i].n_clauses,
                                            packed.n_clauses, picks)

    run = _solve_window_device if engine == "device" else _solve_window_host
    return run(cnfs, live, packed, results, seed=seed, steps=steps,
               batch=batch, cb=cb, stop=stop, should_skip=should_skip,
               on_sat=on_sat, inits=inits, near_miss=near_miss,
               on_near_miss=on_near_miss, count=count)


def solve_walksat(cnf: CNF, *, seed: int = 0, steps: int = 20000,
                  batch: int = 64, cb: float = 2.3, stop=None,
                  init: Optional[List[bool]] = None,
                  near_miss: Optional[dict] = None,
                  engine: Optional[str] = None,
                  pack: Optional[HostPack] = None,
                  walk_counts: Optional[dict] = None,
                  ) -> Tuple[str, Optional[List[bool]]]:
    """Single-CNF probSAT: the K=1 window. Shares the window engines, the
    bucketed padded pack (consecutive IIs of a sweep — and the incremental
    projections, whose handful of selector variables would otherwise change
    the tensor shapes — reuse one XLA compile), and the budget/formula-size
    chunk schedule, so a caller-provided ``steps`` is honoured exactly the
    same way in both entry points. ``near_miss`` receives ``{0: (n_unsat,
    assignment)}`` when the instance stays unsolved; ``pack`` supplies a
    cached :func:`pack_cnf_np` of the CNF; ``walk_counts`` receives
    ``{0: WalkCounts}`` when the CNF walked."""
    res = solve_walksat_window(
        [cnf], seed=seed, steps=steps, batch=batch, cb=cb, stop=stop,
        inits=[init] if init is not None else None,
        near_miss=near_miss, engine=engine,
        packs=[pack] if pack is not None else None,
        walk_counts=walk_counts)
    return res[0]
