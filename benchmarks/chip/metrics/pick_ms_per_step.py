"""Racer (``core/sat/walksat_jax.py``): device time of the walk step's
flip choice, the leaf operations under the ``walk.pick.*`` named scopes
(``walk.pick.clause``, the unsatisfied clause drawn; ``walk.pick.break``,
its literals' break counts), over the probSAT steps the window walked."""


def read(ctx):
    steps = sum(s["steps"] for s in ctx.segments)
    if ctx.trace is None or not steps:
        return None
    t = sum(s for scope, s in ctx.trace["scope_s"].items()
            if scope.startswith("walk.pick."))
    return 1e3 * t / steps if t > 0 else None
