"""Faults planted under the timed path, to show that the reference's
checks fail a run that breaks what the configuration guarantees.

* ``control`` -- the guarantee broken: the program's II search starts one
  above the true MII, so it answers without refuting the MII.
* ``alter`` -- an answer altered where it is produced: the first mapped
  verdict the service returns in the window has one node moved a whole
  II later.
* ``lose`` -- an answer that never comes: the service raises for the
  first request of the window.

``plant(name)`` patches the program in this process and returns a
function that undoes it. ``alter`` and ``lose`` fire only once
``WINDOW`` is set, which the harness does when its window opens, so the
requests served in set-up go untouched.
"""
from __future__ import annotations

import copy
import threading

FAULTS = ("control", "alter", "lose")
WINDOW = threading.Event()


def plant(name: str):
    from repro.core import mapper, service, sweep
    undo = []

    def patch(owner, attr, value):
        undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, value)

    if name == "control":
        for mod in (mapper, sweep):
            orig = mod.min_ii
            patch(mod, "min_ii", lambda dfg, cgra, _f=orig: _f(dfg, cgra) + 1)
    elif name in ("alter", "lose"):
        orig = service.MappingService.map
        once = threading.Lock()
        fired = []

        def faulty(self, dfg, *args, **kwargs):
            res = orig(self, dfg, *args, **kwargs)
            if not WINDOW.is_set():             # set-up, not the window
                return res
            with once:
                first = not fired and (name == "lose" or res.success)
                if first:
                    fired.append(1)
            if not first:
                return res
            if name == "lose":
                raise RuntimeError("planted fault: the verdict is lost")
            res = copy.copy(res)
            res.placement = dict(res.placement)
            n = next(s for s, _, _ in dfg.edges())
            p, c, it = res.placement[n]
            res.placement[n] = (p, c, it + 1)
            return res
        patch(service.MappingService, "map", faulty)
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")

    def restore():
        while undo:
            owner, attr, value = undo.pop()
            setattr(owner, attr, value)
    return restore
