"""Racer (``core/sat/walksat_jax.py``): share of the window's sweep
windows (``solve_window`` calls) in which a probSAT racer ran at least
one device segment. A racer thread that starts and dies before its first
segment does not count."""


def read(ctx):
    n = ctx.probes.solve_windows
    if not n:
        return None
    return 100.0 * ctx.probes.walked_windows / n
