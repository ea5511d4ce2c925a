"""What the benchmark observes of the program, from outside it: wrappers
around the calls into each layer (counts, wall times and, in a traced run,
``jax.profiler.TraceAnnotation`` spans on the device trace's clock), JAX's
compile events, garbage collections, and exceptions that escape the
program's threads.

Every wrapper forwards its call unchanged. The one that times a device
segment waits on the segment's ``done`` output, which the walk itself does
right after the call.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Dict, List


class Probes:
    def __init__(self, trace: bool):
        self.trace = trace
        self.lock = threading.Lock()
        self.segments: List[dict] = []        # one per _device_segment call
        self.solve_windows = 0                # sweep windows (solve_window)
        self.walk_calls = 0                   # solve_walksat_window calls
        self.walked_windows = 0               # ... that ran >= 1 segment
        self.submits: Dict[int, float] = {}   # id(dfg) -> WorkerPool.submit
        self.compiles: List[float] = []       # perf_counter of each compile
        self.collections: List[tuple] = []    # (start, generation, seconds)
        self.thread_errors: Dict[str, int] = {}
        self.thread_error_first: Dict[str, str] = {}
        self._local = threading.local()
        self._undo: List = []

    # ------------------------------------------------------------ spans
    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _patch(self, owner, attr: str, wrapper) -> None:
        orig = getattr(owner, attr)
        setattr(owner, attr, wrapper(orig))
        self._undo.append((owner, attr, orig))

    def install(self, pool=None) -> None:
        """Wrap the program's layer entry points (and ``pool.submit``)."""
        import jax
        from repro.core import mapper, service, sweep
        from repro.core.sat import walksat_jax

        def segment(orig):
            def call(*args):
                state_in = args[-1]
                with self.span("_device_segment"):
                    t0 = time.perf_counter()
                    out = orig(*args)
                    done = int(jax.block_until_ready(out[3]))
                    wall = time.perf_counter() - t0
                rec = {"t0": t0, "wall": wall,
                       "steps": done - int(state_in[3]),
                       "K": int(state_in[0].shape[0]),
                       "B": int(state_in[0].shape[1]),
                       "O": int(args[6].shape[2]),
                       "C": int(args[4].shape[1])}
                with self.lock:
                    self.segments.append(rec)
                self._local.segments = getattr(self._local, "segments", 0) + 1
                return out
            return call

        def walk(orig):
            def call(*args, **kwargs):
                self._local.segments = 0
                with self.span("solve_walksat_window"):
                    try:
                        return orig(*args, **kwargs)
                    finally:
                        with self.lock:
                            self.walk_calls += 1
                            if self._local.segments:
                                self.walked_windows += 1
            return call

        def window(orig):
            def call(*args, **kwargs):
                with self.lock:
                    self.solve_windows += 1
                with self.span("solve_window"):
                    return orig(*args, **kwargs)
            return call

        def spanned(name):
            def wrap(orig):
                def call(*args, **kwargs):
                    with self.span(name):
                        return orig(*args, **kwargs)
                return call
            return wrap

        self._patch(walksat_jax, "_device_segment", segment)
        self._patch(walksat_jax, "solve_walksat_window", walk)
        self._patch(sweep, "solve_window", window)
        self._patch(service.MappingService, "map",
                    spanned("MappingService.map"))
        self._patch(sweep, "verify_mapping", spanned("verify_mapping"))
        self._patch(mapper, "verify_mapping", spanned("verify_mapping"))
        if pool is not None:
            def submit(orig):
                def call(dfg, *args, **kwargs):
                    with self.lock:
                        self.submits[id(dfg)] = time.perf_counter()
                    with self.span("WorkerPool.submit"):
                        return orig(dfg, *args, **kwargs)
                return call
            self._patch(pool, "submit", submit)

    def uninstall(self) -> None:
        while self._undo:
            owner, attr, orig = self._undo.pop()
            setattr(owner, attr, orig)

    # ------------------------------------------------- compiles, threads
    def listen(self) -> None:
        """Count XLA backend compiles (a persistent-cache hit is not one)
        and uncaught exceptions of any thread."""
        import jax.monitoring as mon

        def on_duration(event, duration, **_):
            if event.endswith("/backend_compile_duration"):
                with self.lock:
                    self.compiles.append(time.perf_counter())
        mon.register_event_duration_secs_listener(on_duration)
        self._listeners = [(mon.unregister_event_duration_listener,
                            on_duration)]

        def on_gc(phase, info):
            if phase == "start":
                self._gc_t0 = time.perf_counter()
            elif hasattr(self, "_gc_t0"):
                self.collections.append(
                    (self._gc_t0, info["generation"],
                     time.perf_counter() - self._gc_t0))
        gc.callbacks.append(on_gc)
        self._on_gc = on_gc

        prior = threading.excepthook

        def hook(args):
            key = args.exc_type.__name__ if args.exc_type else "?"
            with self.lock:
                self.thread_errors[key] = self.thread_errors.get(key, 0) + 1
                self.thread_error_first.setdefault(
                    key, f"{args.thread.name if args.thread else '?'}: "
                         f"{args.exc_value}")
            if prior is not threading.__excepthook__:
                prior(args)
        threading.excepthook = hook
        self._prior_hook = prior

    def unlisten(self) -> None:
        for unregister, fn in getattr(self, "_listeners", []):
            unregister(fn)
        self._listeners = []
        if getattr(self, "_on_gc", None) in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if hasattr(self, "_prior_hook"):
            threading.excepthook = self._prior_hook

    def compiles_between(self, t0: float, t1: float) -> int:
        with self.lock:
            return sum(1 for t in self.compiles if t0 <= t <= t1)

    def collections_between(self, t0: float, t1: float) -> List[tuple]:
        """(generation, seconds) of each garbage collection begun between
        ``t0`` and ``t1``."""
        return [(g, d) for t, g, d in list(self.collections)
                if t0 <= t <= t1]

    def segments_between(self, t0: float, t1: float) -> List[dict]:
        with self.lock:
            return [s for s in self.segments if t0 <= s["t0"] <= t1]
