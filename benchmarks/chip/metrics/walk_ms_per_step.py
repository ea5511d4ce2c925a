"""Racer (``core/sat/walksat_jax.py``): wall time of the window's
``_device_segment`` calls, each timed through ``block_until_ready`` of
its ``done`` output, over the probSAT steps they walked."""


def read(ctx):
    steps = sum(s["steps"] for s in ctx.segments)
    if not steps:
        return None
    return 1e3 * sum(s["wall"] for s in ctx.segments) / steps
