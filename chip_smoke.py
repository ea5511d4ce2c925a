"""Chip smoke test: the mapper's main path on one TPU, end to end.

    python3 chip_smoke.py              # one chip: phases a-e below
    python3 chip_smoke.py --chips 4    # the batch-sharded walk on four chips
                                       # against one chip, and nothing else

Everything runs in this one process, which owns the chip. Inputs are the
suite kernels of ``repro.core.suite`` on the paper's largest fabric (5x5)
and data drawn from fixed seeds; nothing is read from outside the checkout.

  a. device: platform, kind and count; anything but a TPU exits non-zero.
  b. kernels: compiled ``true_counts_window`` and ``flip_update`` against
     their jnp references on real 5x5 window packs, bit for bit.
  c. device walk: ``solve_walksat_window`` with the device and the host
     engine, same seeds: identical statuses and models; the walk's state
     lives on the TPU and its compiled segment holds the Pallas kernel.
  d. main path: ``compile(MapRequest(..., arch="5x5", sweep_width=4,
     service="default"))`` for all 11 suite kernels against the sequential
     reference; one hard window with the racer started at once (and the
     CDCL process pool forked, reset and forked again while this process
     holds the chip).
  e. served path: about 20 requests (repeats, near shapes, new) through
     ``CompileFrontDoor`` -> ``WorkerPool``; every result equals a direct
     ``compile()``, and every walk ran in this process on the TPU.

Per-phase wall and compile times are printed for information. The last
line of standard output is one JSON object naming the device; it is
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PLATFORM = "tpu"
WINDOW_KERNELS = ("sha2", "sha", "basicmath", "nw")
FABRIC = "5x5"
BATCH = 24          # solve_window's restart batch
WALK_STEPS = 512    # phase c budget per engine


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- timing
class Phase:
    """Wall time of a phase and the XLA compile time spent inside it
    (JAX's own monitoring events; a persistent-cache hit is counted as a
    retrieval, not a compile)."""
    events = {"compile_s": 0.0, "compiles": 0, "retrieve_s": 0.0,
              "cache_hits": 0}
    _installed = False

    def __init__(self, name: str):
        self.name = name
        if not Phase._installed:
            import jax.monitoring as mon

            def on_duration(event, duration, **_):
                if event.endswith("/backend_compile_duration"):
                    Phase.events["compile_s"] += duration
                    Phase.events["compiles"] += 1
                elif event.endswith("/cache_retrieval_time_sec"):
                    Phase.events["retrieve_s"] += duration

            def on_event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    Phase.events["cache_hits"] += 1

            mon.register_event_duration_secs_listener(on_duration)
            mon.register_event_listener(on_event)
            Phase._installed = True

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.ev0 = dict(Phase.events)
        return self

    def __exit__(self, *exc):
        d = {k: Phase.events[k] - self.ev0[k] for k in Phase.events}
        say(f"[{self.name}] wall_s={time.perf_counter() - self.t0:.3f} "
            f"compile_s={d['compile_s']:.3f} compiles={d['compiles']} "
            f"cache_hits={d['cache_hits']} "
            f"cache_retrieve_s={d['retrieve_s']:.3f}")


class WalkSpy:
    """Records every device-walk segment: the process it ran in, the
    devices its state lives on, and its arguments (for the HLO check)."""

    def __init__(self):
        from repro.core.sat import walksat_jax
        self.orig = walksat_jax._device_segment
        self.calls = []
        walksat_jax._device_segment = self

    def __call__(self, *args):
        out = self.orig(*args)
        devs = out[0].sharding.device_set
        self.calls.append({"pid": os.getpid(),
                           "platforms": {d.platform for d in devs},
                           "n_devices": len(devs), "args": args})
        return out

    def hlo(self, call) -> str:
        return self.orig.lower(*call["args"]).compile().as_text()


# ---------------------------------------------------------------- inputs
def build_windows():
    """Cold CNFs of IIs MII..MII+3 on 5x5 and their stacked window pack."""
    from repro.core import suite
    from repro.core.arch import arch
    from repro.core.encode import EncoderSession
    from repro.core.sat.walksat_jax import pack_cnf_window
    from repro.core.schedule import min_ii
    fab = arch(FABRIC)
    out = {}
    for name in WINDOW_KERNELS:
        g = suite.get(name)
        mii = min_ii(g, fab)
        sess = EncoderSession(g, fab)
        iis = list(range(mii, mii + 4))
        cnfs = [sess.encode(ii).cnf for ii in iis]
        packed = pack_cnf_window(cnfs)
        K, C, L = packed.cvars.shape
        O = packed.ovars.shape[2]
        say(f"  window {name}: IIs {iis[0]}..{iis[-1]} K={K} B={BATCH} "
            f"V={packed.n_vars} C={C} L={L} O={O}")
        out[name] = (iis, cnfs, packed)
    return out


# ---------------------------------------------------------------- phases
def phase_kernels(windows) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.clause_eval import true_counts_window
    from repro.kernels.clause_eval.ref import true_counts_window_ref
    from repro.kernels.flip_update import flip_update
    from repro.kernels.flip_update.ref import flip_update_ref
    ref_tc = jax.jit(true_counts_window_ref)
    ref_flip = jax.jit(flip_update_ref)
    for seed, (name, (_, _, p)) in enumerate(windows.items()):
        K = p.cvars.shape[0]
        key = jax.random.PRNGKey(seed)
        assign = jax.random.bernoulli(key, 0.5, (K, BATCH, p.n_vars + 1))
        got = true_counts_window(p.cvars, p.csign, assign, interpret=False)
        tc = ref_tc(p.cvars, p.csign, assign)
        check(np.array_equal(np.asarray(got), np.asarray(tc)),
              f"clause_eval != reference on {name}")
        rng = np.random.default_rng(seed)
        kk = jnp.arange(K)[:, None]
        for step in range(4):
            v_flip = jnp.asarray(rng.integers(0, p.n_vars + 1, (K, BATCH)),
                                 jnp.int32)
            new_val = ~jnp.take_along_axis(assign, v_flip[..., None],
                                           axis=-1)[..., 0]
            occ_c, occ_s = p.ovars[kk, v_flip], p.osign[kk, v_flip]
            a1, t1 = flip_update(assign, tc, v_flip, occ_c, occ_s, new_val,
                                 interpret=False)
            a2, t2 = ref_flip(assign, tc, v_flip, occ_c, occ_s, new_val)
            check(np.array_equal(np.asarray(a1), np.asarray(a2))
                  and np.array_equal(np.asarray(t1), np.asarray(t2)),
                  f"flip_update != reference on {name}, step {step}")
            assign, tc = a1, t1
        recount = true_counts_window(p.cvars, p.csign, assign,
                                     interpret=False)
        check(np.array_equal(np.asarray(recount), np.asarray(tc)),
              f"carried true counts drifted from a recount on {name}")
        say(f"  {name}: clause_eval and 4 flip_update steps bit-identical")


def walk_window(windows, name, seed, engine):
    from repro.core.sat.walksat_jax import solve_walksat_window
    _, cnfs, packed = windows[name]
    return solve_walksat_window(cnfs, seed=seed, steps=WALK_STEPS,
                                batch=BATCH, engine=engine, packed=packed)


def phase_device_walk(windows, spy) -> None:
    n0 = len(spy.calls)
    for seed, name in enumerate(windows):
        dev = walk_window(windows, name, seed, "device")
        host = walk_window(windows, name, seed, "host")
        check(dev == host, f"device engine != host engine on {name}")
        say(f"  {name} seed {seed}: {[s for s, _ in dev]} (engines agree)")
    calls = spy.calls[n0:]
    check(bool(calls), "the device engine ran no segment")
    check(all(c["platforms"] == {PLATFORM} for c in calls),
          "walk state is not on the TPU")
    check("tpu_custom_call" in spy.hlo(calls[0]),
          "compiled _device_segment holds no Pallas kernel")
    say(f"  {len(calls)} device segments, state on TPU, "
        f"tpu_custom_call in the compiled segment")


def phase_main_path(windows) -> dict:
    from repro.core import suite
    from repro.core.api import MapRequest, compile as compile_request
    from repro.core.arch import arch
    from repro.core.mapper import MapperConfig, map_loop
    from repro.core.sat import SAT, UNSAT, portfolio, walksat_jax
    from repro.core.simulator import verify_mapping
    fab = arch(FABRIC)
    refs = {}
    for name in suite.names():
        g = suite.get(name)
        r = compile_request(MapRequest(dfg=g, arch=FABRIC, sweep_width=4,
                                       service="default"))
        ref = map_loop(g, fab, MapperConfig(), sweep_width=1)
        check(r.success == ref.success and r.ii == ref.ii,
              f"{name}: sweep II {r.ii} != sequential II {ref.ii}")
        if r.success:
            chk = verify_mapping(r.dfg, fab, r.placement, r.ii)
            check(chk.ok, f"{name}: simulator rejects II={r.ii}")
        check(r.service.racer_errors == 0,
              f"{name}: racer error {r.service.racer_error}")
        refs[name] = r
        say(f"  {name}: II={r.ii} (sequential {ref.ii}) "
            f"via={r.service.via} attempts={len(r.attempts)}")

    # hard windows with the racer started at once; the first forks the
    # CDCL pool while this process holds the chip, the second forks it
    # again after a reset (the deadline-kill path)
    started = []
    walk = walksat_jax.solve_walksat_window

    def counted(*args, **kwargs):
        started.append(1)
        return walk(*args, **kwargs)

    walksat_jax.solve_walksat_window = counted
    pids = []
    try:
        for name in ("sha2", "sha"):
            iis, cnfs, _ = windows[name]
            res = portfolio.solve_window(cnfs, method="auto", seed=0,
                                         walksat_delay=0.0,
                                         walksat_batch=BATCH)
            for t in threading.enumerate():
                if "run_walksat" in t.name:
                    t.join(timeout=300)
            pool = portfolio._PROC_POOL
            check(pool is not None and not portfolio._PROC_POOL_BROKEN,
                  "CDCL process pool did not start after the chip was held")
            pids.append({p.pid for p in pool._processes.values()})
            for ii, cnf, w in zip(iis, cnfs, res):
                check(w.status in (SAT, UNSAT), f"{name} II={ii}: {w.status}")
                if w.status == SAT:
                    check(cnf.check(w.model), f"{name} II={ii}: bad model")
                elif refs[name].ii is not None:
                    check(ii < refs[name].ii, f"{name} II={ii} UNSAT "
                          f"but the reference maps it")
            say(f"  forced racer window {name} IIs {iis[0]}..{iis[-1]}: "
                f"{[(w.status, w.via) for w in res]}")
            portfolio._reset_pool()
            time.sleep(2.2)      # the pool's post-reset cooldown
    finally:
        walksat_jax.solve_walksat_window = walk
    check(len(started) == 2, f"racer started {len(started)}/2 times")
    check(not (pids[0] & pids[1]), "CDCL pool was not forked anew")
    say(f"  CDCL pool forked twice while holding the chip "
        f"({len(pids[0])} + {len(pids[1])} workers)")
    return refs


def phase_served(refs, spy) -> None:
    sys.path.insert(0, str(ROOT / "benchmarks"))
    from serve_load import near_variant
    from repro.core import suite
    from repro.core.api import MapRequest, compile as compile_request
    from repro.core.arch import arch
    from repro.core.mapper import MapperConfig
    from repro.core.simulator import verify_mapping
    from repro.core.workers import WorkerPool
    from repro.launch.serve import CompileFrontDoor
    fab = arch(FABRIC)
    sweep_cfg = MapperConfig()
    # portfolio at sweep_width=1 walks every II on the device first, so the
    # served path is sure to drive the chip; these kernels map at their
    # MII on 5x5, so no walk spends its whole budget on an infeasible II
    walk_cfg = MapperConfig(solver="portfolio")
    srand = suite.get("srand")
    new = [(suite.get(k), sweep_cfg, 4)
           for k in ("sha", "gsm", "bitcount", "hotspot")]
    walked = [(g, walk_cfg, 1) for g in
              (srand, near_variant(srand, 0), near_variant(srand, 1),
               near_variant(suite.get("stringsearch"), 1)) if g is not None]
    unique = new + walked
    # repeats of the walked requests and of the sweeps, interleaved
    mix = unique + [unique[i % len(unique)] for i in (0, 4, 5, 6, 1, 4,
                                                      7, 2, 5, 4, 3, 6)]
    n0 = len(spy.calls)

    async def drive():
        # near_delta=0: no warm transfer between shapes, so every served
        # solve starts as cold as the direct compile() it is compared with
        with WorkerPool(workers=2, near_delta=0) as pool:
            check(pool.inline, "WorkerPool forked shards on a chip host")
            async with CompileFrontDoor(pool, window_ms=20) as door:
                res = await asyncio.gather(*[
                    door.compile(g, fab, cfg, sweep_width=w,
                                 deadline_s=900) for g, cfg, w in mix])
                stats = door.stats.snapshot()
            return res, stats, pool.stats()

    res, door_stats, pool_stats = asyncio.run(drive())
    check(door_stats["served"] == len(mix) and door_stats["failed"] == 0,
          f"front door: {door_stats}")
    check(pool_stats.get("racer_errors", 0) == 0,
          f"served racer errors: {pool_stats.get('racer_error')}")
    direct = {}
    for (g, cfg, w), r in zip(mix, res):
        if cfg is sweep_cfg:
            # racing legs may pick another model at the same II
            d = refs[g.name]
            same = (r.success, r.ii, r.mii) == (d.success, d.ii, d.mii)
        else:
            if id(g) not in direct:
                direct[id(g)] = compile_request(MapRequest(
                    dfg=g, arch=fab, config=cfg, sweep_width=w))
            d = direct[id(g)]
            same = ((r.success, r.ii, r.mii, r.placement)
                    == (d.success, d.ii, d.mii, d.placement))
        check(same, f"served {g.name} != direct compile(): "
                    f"II {r.ii} vs {d.ii}")
        if r.success:
            check(verify_mapping(r.dfg, fab, r.placement, r.ii).ok,
                  f"served {g.name}: simulator rejects II={r.ii}")
    by_walk = sum(1 for r in res for a in r.attempts if a.via == "walksat")
    calls = spy.calls[n0:]
    check(bool(calls), "no device walk ran on the served path")
    check(all(c["pid"] == os.getpid() and c["platforms"] == {PLATFORM}
              for c in calls), "a served walk ran off this process's TPU")
    say(f"  {len(mix)} requests ({len(unique)} unique) served == direct; "
        f"coalesced={door_stats['coalesced']} "
        f"cache_hits={pool_stats.get('cache_hits', 0)} "
        f"device segments={len(calls)} all in pid {os.getpid()} on TPU; "
        f"II attempts decided by the walk={by_walk}")


def phase_four_chips(windows, spy) -> None:
    """The restart batch sharded over four chips against one chip."""
    import re

    import numpy as np
    from repro.core.sat import walksat_jax
    for seed, name in enumerate(windows):
        n0 = len(spy.calls)
        sharded = walk_window(windows, name, seed, "device")
        calls = spy.calls[n0:]
        shard = walksat_jax._maybe_shard_window
        walksat_jax._maybe_shard_window = lambda a: (a, None)
        try:
            single = walk_window(windows, name, seed, "device")
        finally:
            walksat_jax._maybe_shard_window = shard
        check(sharded == single, f"4-chip walk != 1-chip walk on {name}")
        check(calls and all(c["n_devices"] == 4 for c in calls),
              f"{name}: walk state not spread over 4 chips")
        hlo = spy.hlo(calls[0])
        check("tpu_custom_call" in hlo, "no Pallas kernel in the segment")
        K, C = windows[name][2].cvars.shape[:2]
        gathers = re.findall(r"= \w+\[([\d,]*)\]\S* all-gather(?:-start)?\(",
                             hlo)
        sizes = [int(np.prod([int(d) for d in g.split(",") if d]))
                 for g in gathers]
        check(all(n < K * BATCH * C // 4 for n in sizes),
              f"{name}: all-gather of the true counts: {gathers}")
        say(f"  {name} seed {seed}: {[s for s, _ in sharded]} "
            f"(4 chips == 1 chip; all-gather sizes {sorted(set(sizes))})")


# ---------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded walk against one chip")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    kernels = os.environ.get("REPRO_SAT_KERNELS", "").strip().lower()
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if kernels not in ("", "1", "true", "on", "compiled") \
            or interp in ("1", "true", "on"):
        print("chip_smoke: REPRO_SAT_KERNELS/REPRO_PALLAS_INTERPRET route "
              "away from the compiled kernels; refusing", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.device import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    with Phase("a/device"):
        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        say(f"[a/device] platform={dev['platform']} kind={dev['kind']} "
            f"count={dev['count']} compile_cache={cache}")
    if dev["platform"] != PLATFORM:
        print(f"chip_smoke: no TPU (JAX platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    if dev["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 1

    from repro.core.sat import portfolio
    spy = WalkSpy()
    t0 = time.perf_counter()
    try:
        with Phase("inputs"):
            windows = build_windows()
        if args.chips == 4:
            with Phase("c4/sharded-walk"):
                phase_four_chips(windows, spy)
        else:
            with Phase("b/kernels"):
                phase_kernels(windows)
            with Phase("c/device-walk"):
                phase_device_walk(windows, spy)
            with Phase("d/main-path"):
                refs = phase_main_path(windows)
            with Phase("e/served"):
                phase_served(refs, spy)
        n_err, first = portfolio.racer_errors()
        check(n_err == 0, f"{n_err} racer error(s): {first}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        portfolio._reset_pool()
    say(f"[total] wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={Phase.events['compile_s']:.3f} "
        f"compiles={Phase.events['compiles']} "
        f"cache_hits={Phase.events['cache_hits']}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
