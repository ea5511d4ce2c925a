"""Chip benchmark of the mapping service: one cell, one run.

    python3 benchmarks/chip/run.py --workload suite5x5-sweep.fresh \
        --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``: fabric, ``MapperConfig`` fields, sweep width)
and a traffic mix (``traffic/<name>.json``). A run

1. set-up: draws the requests from ``--seed``, starts the served path
   (``CompileFrontDoor`` -> ``WorkerPool`` -> ``MappingService``, all at
   their defaults, no disk store), serves the mix's set-up requests and
   warms the device-walk shapes this traffic uses;
2. window: the mix's arrival process sends its requests for ``--seconds``
   seconds: closed-loop clients, each sending its next request when its
   verdict arrives, or open-loop bursts at times drawn from the seed;
   then nothing more is sent, the requests in flight are waited for, and
   the clock is read after that wait: all of that work counts, over all
   of that time;
3. checks every verdict against the plain reference (``chipbench/
   reference.py``), then prints counters on earlier lines, each checked
   number beside its limit as the last lines of standard error, and one
   JSON object as the last line of standard output.

``--trace 1`` records a profiler trace of the window, with the program's
host spans (``repro.core.spans``) switched on for it, and reports the
cell's per-layer metrics (``metrics/<name>.py``) instead of the
end-to-end ones. The run owns every chip of the host and exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import argparse
import asyncio
import faulthandler
import gc
import importlib.util
import json
import math
import multiprocessing
import os
import random
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path.insert(0, str(HERE))

from chipbench import faults, graphs, reference, traffic  # noqa: E402

DRAIN_S = 60.0          # how long verdicts in flight at the close may take
REF_SAMPLE = 32         # verdicts whose lower-II claims the ILP re-decides
BREAKDOWN_NAME = 240    # characters kept of a device op's name (its HLO text)


def process_start() -> float:
    """time.time() at which this process started (Linux), else now."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_PROCESS = process_start()


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class RunError(Exception):
    """A run that cannot produce a result (no chip, bad cell, ...)."""


# ----------------------------------------------------------------- cell
def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str) -> dict:
    bench = load_bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"unknown workload {name!r}")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / confs[cell["config"]]["file"]).read_text())
    if config.get("name") != cell["config"]:
        raise RunError(f"{confs[cell['config']]['file']} is not "
                       f"{cell['config']!r}")

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config,
            "mix": traffic.load_mix(cell["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def to_program(g: graphs.Graph, name: str):
    from repro.core.dfg import DFG, Node
    dfg = DFG(name)
    for i, (op, ins, imm) in enumerate(g):
        dfg.nodes[i] = Node(i, op, ins, imm, "")
    dfg.touch()
    dfg.validate()
    return dfg


def verdict(name: str, g, res) -> dict:
    """What the reference judges of one served result."""
    ra = getattr(res, "regalloc", None)
    return {"name": name, "graph": g, "success": bool(res.success),
            "ii": res.ii if res.success else None,
            "placement": dict(res.placement) if res.success else {},
            "regs": dict(ra.regs) if res.success and ra is not None else {},
            "attempts": [(a.ii, a.status, a.regalloc_ok)
                         for a in res.attempts],
            "via": next((a.via for a in res.attempts
                         if a.ii == res.ii and a.status == "SAT"), None)}


# -------------------------------------------------------------- warm-up
def warm_walks(items, fabrics, cfg) -> int:
    """Compile (or load from the cache) every device-walk program the
    portfolio's first II of these requests uses: one short walk per
    distinct padded window shape, cold and warm-started, as
    ``SolverSession.solve_ii`` runs it."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.encode import EncoderSession
    from repro.core.sat.portfolio import SolverSession
    from repro.core.sat.walksat_jax import pack_cnf_window, solve_walksat
    from repro.core.schedule import min_ii
    seen = set()
    for req in items:
        dfg = to_program(req.graph, req.name)
        fabric = fabrics[req.fabric][0]
        sess = SolverSession(EncoderSession(dfg, fabric, cfg.amo),
                             method=cfg.solver, seed=cfg.seed,
                             max_learnt=cfg.max_learnt)
        ii = min_ii(dfg, fabric)
        sess.ensure_ii(ii)
        cnf = sess.project(ii)
        pack, _ = sess.host_pack(ii)
        packed = pack_cnf_window([cnf], [pack])
        key = (packed.cvars.shape, packed.ovars.shape)
        if key in seen:
            continue
        seen.add(key)
        for init in (None, [False] * cnf.n_vars):
            solve_walksat(cnf, seed=cfg.seed, steps=64,
                          batch=sess.walksat_batch, init=init, pack=pack,
                          near_miss={})
        # the solved-model read-back indexes the walk's [1, V+1] state
        np.asarray(jnp.zeros(packed.ovars.shape[1:2], bool)[None][0])
    return len(seen)


# ---------------------------------------------------------------- window
async def send(door, fabrics, cfg, width, req, rec, deadline_s=None):
    """One request through the front door; its record gets the result, or
    the error, and the time the verdict came."""
    from repro.launch.serve import DeadlineExceeded
    dfg = to_program(req.graph, req.name)
    rec["dfg"] = id(dfg)
    try:
        rec["res"] = await door.compile(dfg, fabrics[req.fabric][0], cfg,
                                        sweep_width=width,
                                        deadline_s=deadline_s)
    except DeadlineExceeded:
        rec["late"] = True
    except Exception as exc:            # a failed request is a verdict
        rec["error"] = f"{type(exc).__name__}: {exc}"
    rec["t1"] = time.perf_counter()


async def drive(door, fabrics, cfg, width, items, arrival, times, seconds,
                probes):
    """Sends ``items`` as the mix's arrival process says until the window
    closes: closed-loop clients, or an open loop at ``times`` (offsets
    from the window's start; a request's latency counts from its time
    even when the sender runs late)."""
    queue = iter(items)
    records: List[dict] = []
    t_start = time.perf_counter()
    t_end = t_start + seconds

    def take():
        try:
            return next(queue)
        except StopIteration:
            raise RunError("the traffic ran out inside the window; "
                           "raise the mix's 'requests'") from None

    async def client():
        while time.perf_counter() < t_end:
            req = take()
            rec = {"req": req, "t0": time.perf_counter()}
            records.append(rec)
            await send(door, fabrics, cfg, width, req, rec)

    async def open_loop():
        sent = []
        for t in times:
            req = take()
            delay = t_start + t - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = {"req": req, "t0": t_start + t}
            records.append(rec)
            sent.append(asyncio.ensure_future(send(
                door, fabrics, cfg, width, req, rec,
                deadline_s=arrival.get("deadline_s"))))
        if sent:
            await asyncio.wait(sent)

    # the span marks the measured window for the trace reduction; it ends
    # at the close, before the drain
    faults.WINDOW.set()
    span = probes.span("benchmark.window")
    span.__enter__()
    asyncio.get_running_loop().call_later(seconds, span.__exit__,
                                          None, None, None)
    if arrival["kind"] == "closed":
        tasks = [asyncio.ensure_future(client())
                 for _ in range(int(arrival["clients"]))]
    else:
        tasks = [asyncio.ensure_future(open_loop())]
    done, pending = await asyncio.wait(tasks, timeout=seconds + DRAIN_S)
    t_close = time.perf_counter()
    faults.WINDOW.clear()
    for t in pending:
        t.cancel()
    for t in done:
        if t.exception() is not None:
            raise t.exception()
    return records, t_start, t_end, t_close


# ------------------------------------------------------------- metrics
def p95(xs: List[float]) -> float:
    s = sorted(xs)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def end_to_end(records, t_start, t_close, setup_s) -> Dict[str, float]:
    """Every request sent in the window counts, and the rate runs from the
    window's start to ``t_close``, when the last of them was answered."""
    lat = [r["t1"] - r["t0"] for r in records if "res" in r]
    out = {"setup_s": setup_s,
           "verdicts_per_s": len(lat) / (t_close - t_start)}
    if lat:
        out["verdict_p50_s"] = statistics.median(lat)
        out["verdict_p95_s"] = p95(lat)
    return out


def load_file(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of this directory (a metric reader
    or a kernel's work function), found by its name."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}", HERE / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------- reference
def judge(records, fabrics, seed: int, pool) -> dict:
    """The checked numbers of one run (each must be 0) and counters."""
    lost = [r for r in records if "res" not in r and not r.get("late")]
    served = [(r["req"], verdict(r["req"].name, r["req"].graph, r["res"]))
              for r in records if "res" in r]
    bad, sim, off = [], [], []
    for req, v in served:
        fab = fabrics[req.fabric][1]
        if req.ii is not None and v["ii"] != req.ii:
            off.append((v["name"], v["ii"], req.ii))
        if not v["success"]:
            continue
        errs = reference.placement_faults(v["graph"], fab, v["ii"],
                                          v["placement"], v["regs"])
        if errs:
            bad.append((v["name"], errs[:2]))
        elif not reference.sim_matches(v["graph"], fab, v["ii"],
                                       v["placement"]):
            sim.append(v["name"])
    sample = sorted(random.Random(seed).sample(range(len(served)),
                                               min(REF_SAMPLE, len(served))))
    args = [(served[i][1]["graph"], fabrics[served[i][0].fabric][1],
             served[i][1]["ii"], served[i][1]["attempts"]) for i in sample]
    outs = pool.map(reference.ii_faults, args) if pool is not None \
        else list(map(reference.ii_faults, args))
    unproven = [(served[i][1]["name"], f[:2])
                for i, (f, _) in zip(sample, outs) if f]
    vs = [v for _, v in served]
    return {"checks": {"lost": len(lost), "bad_placement": len(bad),
                       "sim_mismatch": len(sim), "unproven_ii": len(unproven),
                       "ii_mismatch": len(off)},
            "detail": {"lost": [r.get("error", "no verdict")
                                for r in lost][:3],
                       "bad_placement": bad[:3], "sim_mismatch": sim[:3],
                       "unproven_ii": unproven[:3], "ii_mismatch": off[:3]},
            "counters": {
                "verdicts": len(vs),
                "mapped": sum(v["success"] for v in vs),
                "late": sum(1 for r in records if r.get("late")),
                "ii_listed": sum(1 for req, _ in served
                                 if req.ii is not None),
                "ii_checked": len(sample),
                "ilp_timeouts": sum(u for _, u in outs),
                "regalloc_claims": sum(
                    list(reference.claims(v["attempts"]).values())
                    .count("regalloc") for v in vs),
                "walk_decided": sum(v["via"] == "walksat" for v in vs)}}


def ref_pool(workers: int):
    if workers <= 0:
        return None
    return multiprocessing.get_context("spawn").Pool(workers)


# ------------------------------------------------------------------ run
def run_cell(args, require_chip: bool = True,
             ref_workers: Optional[int] = None) -> dict:
    spec = load_cell(args.workload)
    if ref_workers is None:
        ref_workers = min(8, max(1, (os.cpu_count() or 2) - 2))
    pool = ref_pool(ref_workers)
    try:
        return _run(args, spec, pool, require_chip)
    finally:
        if pool is not None:
            pool.close()
            pool.join()


def _run(args, spec, pool, require_chip):
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    if require_chip:
        # the cache lives at a fixed path inside the checkout, whatever the
        # host sets; off the chip (tests) JAX's configuration is left alone
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        from repro.core.device import enable_compile_cache
        enable_compile_cache()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if require_chip and device["platform"] != "tpu":
        raise RunError(f"no TPU: JAX platform {device['platform']!r}")
    chips = int(spec["cell"]["chips"])
    if device["count"] < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees "
                       f"{device['count']}")

    from chipbench.probes import Probes
    probes = Probes(trace=bool(args.trace))
    probes.listen()
    try:
        return _measure(args, spec, pool, require_chip, jax, device, probes)
    finally:
        probes.unlisten()


def fabrics_of(config: dict, mix: dict) -> dict:
    """{request fabric key: (program fabric, reference fabric)}; None is
    the configuration's. The program builds each fabric from its name,
    the reference from the spec, and the two must agree."""
    from repro.core.arch import arch
    specs = {None: config["fabric"], **mix.get("fabrics", {})}
    out = {}
    for key, spec in specs.items():
        prog = arch(spec["name"] if key is None else key)
        ref = reference.fabric_from_config(spec)
        if (prog.rows, prog.cols, prog.n_pes) != (ref.rows, ref.cols,
                                                  ref.n_pes) \
                or any(prog.regs(p) != ref.regs[p]
                       for p in range(ref.n_pes)):
            raise RunError(f"the program's fabric {spec.get('name', key)!r} "
                           f"differs from its spec")
        out[key] = (prog, ref)
    return out


def _measure(args, spec, pool, require_chip, jax, device, probes):
    from repro.core import spans
    from repro.core.mapper import MapperConfig
    from repro.core.sat import portfolio
    from repro.core.workers import WorkerPool
    from repro.launch.serve import CompileFrontDoor

    # ---- set-up: traffic, program, warm-up
    config, mix = spec["config"], spec["mix"]
    items = traffic.requests(mix, args.seed)
    warm_items = traffic.warm_requests(mix, args.seed)
    times = None
    if mix["arrival"]["kind"] == "open":
        times = traffic.arrival_times(mix, args.seed, args.seconds)
    fabrics = fabrics_of(config, mix)
    cfg = MapperConfig(**config["mapper"])
    width = int(config["sweep_width"])
    shapes = 0
    if cfg.solver == "portfolio":
        shapes = warm_walks(warm_items + items, fabrics, cfg)
    say(f"[setup] requests={len(items)} walk_shapes={shapes} "
        f"warm_requests={len(warm_items)}")

    async def serve():
        # a chip host runs the pool's shards as threads of this process;
        # off the chip (tests) the same is asked for explicitly
        with WorkerPool(inline=not require_chip) as wpool:
            probes.install(pool=wpool)
            try:
                async with CompileFrontDoor(wpool) as door:
                    await asyncio.gather(*[
                        door.compile(to_program(r.graph, r.name),
                                     fabrics[r.fabric][0], cfg,
                                     sweep_width=width)
                        for r in warm_items])
                    # set-up leaves a large heap (the warm walks' traces):
                    # collect it once and freeze what stays, so that a full
                    # collection in the window does not rescan it (it stalled
                    # the chip for 2-3 s in some runs)
                    gc.collect()
                    gc.freeze()
                    setup_s = time.time() - T_PROCESS
                    trace_dir = None
                    if args.trace:
                        trace_dir = tempfile.mkdtemp(prefix="chipbench-")
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0
                        jax.profiler.start_trace(trace_dir,
                                                 profiler_options=opts)
                        spans.enable()      # empties its buffer
                    try:
                        out = await drive(door, fabrics, cfg, width, items,
                                          mix["arrival"], times,
                                          args.seconds, probes)
                    finally:
                        gc.unfreeze()
                        if args.trace:
                            jax.profiler.stop_trace()
                            spans.enable(False)
                    recorded = spans.drain()
                    peak = max((d.memory_stats() or {}).get(
                        "peak_bytes_in_use", 0) for d in jax.devices())
                    return out, setup_s, trace_dir, peak, wpool.stats(), \
                        recorded
            finally:
                probes.uninstall()
                portfolio._reset_pool()     # the CDCL workers, if it forked

    (records, t_start, t_end, t_close), setup_s, trace_dir, peak, stats, \
        recorded = asyncio.run(serve())
    device["memory_peak_bytes"] = int(peak)

    # ---- after the window: trace, metrics, reference
    red = None
    if trace_dir is not None:
        from chipbench.trace import read_xplane
        try:
            red = read_xplane(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        say(f"[trace] planes={json.dumps(red['planes'])}")
        say(f"[spans] recorded={len(recorded)} dropped={spans.dropped()}")
    window = [r for r in records if r["t0"] <= t_end]
    result = judge(window, fabrics, args.seed, pool)
    say(f"[counters] {json.dumps(result['counters'])} "
        f"solve_windows={probes.solve_windows} "
        f"walk_calls={probes.walk_calls} walked={probes.walked_windows} "
        f"segments={len(probes.segments_between(t_start, t_end))} "
        f"compiles_in_window={probes.compiles_between(t_start, t_end)} "
        f"racer_errors={stats.get('racer_errors', 0)}")
    lat = [round(r["t1"] - r["t0"], 4) for r in window if "t1" in r]
    say(f"[latency_s] {json.dumps(lat)}")
    gcs = probes.collections_between(t_start, t_close)
    say(f"[gc] collections in the window: {len(gcs)}, of the oldest "
        f"generation {sum(g == 2 for g, _ in gcs)}, longest "
        f"{max((d for _, d in gcs), default=0.0):.4f} s")
    if probes.thread_errors:
        say(f"[thread_errors] {json.dumps(probes.thread_errors)} first: "
            f"{json.dumps(probes.thread_error_first)}")
    for k, v in result["detail"].items():
        if v:
            say(f"[detail] {k}: {v}")

    if args.trace:
        ctx = Context(window, t_start, t_end, probes, red, device, spec,
                      recorded)
        metrics = {}
        for m in spec["per_layer"]:
            val = load_file("metrics", m["name"]).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = end_to_end(window, t_start, t_close, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] in e2e}
    failed = sum(1 for r in window if "res" not in r)   # late ones too
    out = {"correct": all(v == 0 for v in result["checks"].values()),
           "attempted": len(window), "failed": failed, "metrics": metrics,
           "device": device}
    if red is not None:
        # a while loop's time holds its body's ops: list the ops inside it
        ops = sorted(((n, s) for n, s in red["op_s"].items()
                      if not n.startswith("%while")), key=lambda kv: -kv[1])
        out["breakdown"] = {"device_ops": [[n[:BREAKDOWN_NAME], s]
                                           for n, s in ops[:10]],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": 0}
                     for k, v in result["checks"].items()}
    return out


class Context:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, records, t_start, t_end, probes, trace, device, spec,
                 spans):
        self.records = records
        self.served = [r for r in records if "res" in r]
        self.t_start, self.t_end = t_start, t_end
        self.window_s = t_end - t_start
        self.probes = probes
        self.segments = probes.segments_between(t_start, t_end)
        self.trace = trace
        self.spans = list(spans)    # the program's, recorded in the window
        self.device = device
        self.spec = spec

    def peaks(self) -> dict:
        table = json.loads((HERE / "peaks.json").read_text())
        if self.device["kind"] not in table["devices"]:
            raise RunError(f"no peaks for device kind {self.device['kind']!r}")
        return table["devices"][self.device["kind"]]

    def work(self, kernel: str):
        return load_file("work", kernel)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, require_chip: bool = True,
         ref_workers: Optional[int] = None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        say("run.py: the program (src/repro) is not in this checkout")
        return 2
    # a run that hangs shows where before the 360 s limit ends it
    faulthandler.dump_traceback_later(330, exit=False, file=sys.__stderr__)
    try:
        out = run_cell(args, require_chip=require_chip,
                       ref_workers=ref_workers)
    except RunError as exc:
        say(f"run.py: {exc}")
        return 1
    finally:
        faulthandler.cancel_dump_traceback_later()
    checks = out["checks"]
    for k, v in checks.items():
        say(f"check {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
