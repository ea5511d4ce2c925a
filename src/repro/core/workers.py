"""Multi-process solve workers: the parallelism layer of the serving tier.

:class:`WorkerPool` fans ``map()`` requests out over N solver *shards*.
Each shard is a single-worker forked process running its own
:class:`~repro.core.service.MappingService` over the **shared**
:class:`~repro.core.store.MappingStore` directory — so every shard sees
every other shard's persisted mappings and proven-UNSAT cores, while its
in-memory warm state (pooled solver sessions, learnt clauses, near-shape
lattice) stays process-local and lock-free.

Requests are routed by **affinity**: the shard index is a stable hash of
(topology signature, near-shape lattice bucket), so every request in one
kernel *family* lands on the same shard and keeps hitting that shard's
warm sessions — the near-shape admission of
:func:`repro.core.service.near_shape_key` only pays off if family members
actually meet. Different families ride different shards and solve in true
parallel (separate processes, no GIL).

Fork-safety: this module's import chain is deliberately jax-free (see the
note in ``core/sat/portfolio.py``) — shards fork *clean* and only a
shard's own walksat racer ever initialises XLA, inside the child.

One process per chip: an accelerator belongs to one process at a time, so
forked shards cannot each drive it (the second one would find the chip
taken and walk on the CPU, or fail). On a host with an accelerator
(:func:`repro.core.device.attached_platform`) the pool therefore runs its
shards as *threads* of the calling process, which then owns the chip —
the same inline mode used where fork is unavailable (or ``inline=True``):
single-worker thread shards over one shared thread-safe service, same API,
same affinity serialisation, no process isolation.
"""
from __future__ import annotations

import logging
import multiprocessing
import os
import struct
from concurrent.futures import Future, ProcessPoolExecutor, ThreadPoolExecutor
from typing import Dict, List, Optional

from .cgra import CGRA
from .device import attached_platform
from .dfg import DFG
from .mapper import MapperConfig, MappingResult
from .service import (MappingService, near_shape_key, shape_signature,
                      topology_signature)
from .store import MappingStore, key_hash

_log = logging.getLogger(__name__)

# ------------------------------------------------- worker-process globals

_WORKER_SVC: Optional[MappingService] = None


def _worker_init(store_path: Optional[str], near_delta: int,
                 max_sessions: int, cache_size: int) -> None:
    global _WORKER_SVC
    store = MappingStore(store_path) if store_path else None
    _WORKER_SVC = MappingService(max_sessions=max_sessions,
                                 cache_size=cache_size, store=store,
                                 near_delta=near_delta)


def _svc() -> MappingService:
    # real raise, not assert: shard entrypoints must guard under -O too
    if _WORKER_SVC is None:
        raise RuntimeError("worker not initialised: _worker_init() did not "
                           "run in this process")
    return _WORKER_SVC


def _worker_map(dfg: DFG, cgra: CGRA, cfg: MapperConfig, sweep_width: int,
                use_cache: bool) -> MappingResult:
    return _svc().map(dfg, cgra, cfg, sweep_width=sweep_width,
                      use_cache=use_cache)


def _worker_stats() -> Dict:
    return _svc().describe()


# ------------------------------------------------------------------ pool


class WorkerPool:
    """N affinity-routed solver shards over one shared store directory.

    ``submit()`` returns a ``concurrent.futures.Future`` resolving to the
    shard's :class:`MappingResult`; ``map()`` is the blocking convenience.
    ``workers=0``, an accelerator on this host, or fork unavailable runs
    inline thread shards over one shared service — identical semantics
    minus process isolation.
    """

    def __init__(self, workers: Optional[int] = None,
                 store_path: Optional[str] = None, near_delta: int = 1,
                 max_sessions: int = 64, cache_size: int = 512,
                 inline: bool = False):
        if workers is None:
            workers = max(1, min(4, (os.cpu_count() or 2) - 1))
        self.n_workers = max(1, workers)
        self.store_path = store_path
        self.near_delta = near_delta
        # on an accelerator host the chip must stay with this process
        self.inline = (inline or workers == 0
                       or attached_platform() is not None)
        self._shards: List = []
        self._inline_svc: Optional[MappingService] = None
        if not self.inline:
            try:
                ctx = multiprocessing.get_context("fork")
                for _ in range(self.n_workers):
                    ex = ProcessPoolExecutor(
                        max_workers=1, mp_context=ctx,
                        initializer=_worker_init,
                        initargs=(store_path, near_delta, max_sessions,
                                  cache_size))
                    self._shards.append(ex)
                # fork every worker now, before the caller does anything
                # XLA-ish in this process
                for f in [ex.submit(os.getpid) for ex in self._shards]:
                    f.result(timeout=60)
            except Exception:
                _log.warning("WorkerPool: could not fork %d shard "
                             "process(es); running them as threads of "
                             "this process", self.n_workers, exc_info=True)
                for ex in self._shards:
                    ex.shutdown(wait=False, cancel_futures=True)
                self._shards = []
                self.inline = True
        if self.inline:
            store = MappingStore(store_path) if store_path else None
            self._inline_svc = MappingService(
                max_sessions=max_sessions, cache_size=cache_size,
                store=store, near_delta=near_delta)
            self._shards = [ThreadPoolExecutor(max_workers=1)
                            for _ in range(self.n_workers)]

    # ---------------------------------------------------------- routing
    def shard_of(self, dfg: DFG, cgra: CGRA,
                 cfg: Optional[MapperConfig] = None) -> int:
        """Affinity shard for a request: one kernel family (same topology
        + near-shape bucket + solver knobs), one shard, forever."""
        cfg = cfg or MapperConfig()
        shape = shape_signature(dfg, cgra)
        fam = (topology_signature(cgra),
               near_shape_key(shape, max(1, self.near_delta)),
               cfg.amo, cfg.solver, cfg.seed)
        h = key_hash(fam)
        return struct.unpack("<Q", h[:8])[0] % self.n_workers

    # -------------------------------------------------------------- API
    def submit(self, dfg: DFG, cgra: CGRA,
               cfg: Optional[MapperConfig] = None, sweep_width: int = 1,
               use_cache: bool = True) -> Future:
        cfg = cfg or MapperConfig()
        shard = self._shards[self.shard_of(dfg, cgra, cfg)]
        if self.inline:
            svc = self._inline_svc
            return shard.submit(svc.map, dfg, cgra, cfg,
                                sweep_width=sweep_width,
                                use_cache=use_cache)
        return shard.submit(_worker_map, dfg, cgra, cfg, sweep_width,
                            use_cache)

    def map(self, dfg: DFG, cgra: CGRA, cfg: Optional[MapperConfig] = None,
            sweep_width: int = 1, use_cache: bool = True,
            timeout: Optional[float] = None) -> MappingResult:
        return self.submit(dfg, cgra, cfg, sweep_width,
                           use_cache).result(timeout=timeout)

    # -------------------------------------------------------- inspection
    def stats(self) -> Dict:
        """Aggregated per-shard service counters (sum across shards, plus
        the per-shard breakdown under ``"shards"``)."""
        if self.inline:
            per = [self._inline_svc.describe()]
        else:
            per = []
            for ex in self._shards:
                try:
                    per.append(ex.submit(_worker_stats).result(timeout=30))
                except Exception:
                    per.append({})
        total: Dict = {}
        for d in per:
            for k, v in d.items():
                if isinstance(v, (int, float)):
                    total[k] = total.get(k, 0) + v
        total["shards"] = per
        total["n_workers"] = self.n_workers
        total["inline"] = self.inline
        return total

    def shutdown(self, wait: bool = True) -> None:
        for ex in self._shards:
            ex.shutdown(wait=wait, cancel_futures=not wait)
        self._shards = []

    def __enter__(self) -> "WorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
