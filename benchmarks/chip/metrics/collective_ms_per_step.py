"""Chips (four-chip mesh): device time of the all-gather and all-reduce
operations in the traced window, over the probSAT steps walked."""


def read(ctx):
    steps = sum(s["steps"] for s in ctx.segments)
    if ctx.trace is None or not steps:
        return None
    t = sum(s for name, s in ctx.trace["op_s"].items()
            if "all-gather" in name or "all-reduce" in name)
    return 1e3 * t / steps if t > 0 else None
