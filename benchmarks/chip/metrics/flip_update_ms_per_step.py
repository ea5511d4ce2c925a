"""Kernels (``kernels/flip_update``): device time of the flip_update
kernel's operations in the traced window, over the probSAT steps the
window walked (one kernel call per step)."""


def kernel_s(trace):
    return sum(s for name, s in trace["op_s"].items() if "flip_update" in name)


def read(ctx):
    steps = sum(s["steps"] for s in ctx.segments)
    if ctx.trace is None or not steps:
        return None
    t = kernel_s(ctx.trace)
    return 1e3 * t / steps if t > 0 else None
