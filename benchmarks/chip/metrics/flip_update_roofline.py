"""Kernels (``kernels/flip_update``): least time of the window's flips
(``work/flip_update.py``, against the chip's HBM bandwidth and int8 peak
from ``peaks.json``) over the kernel's device time in the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = sum(s for name, s in ctx.trace["op_s"].items()
            if "flip_update" in name)
    if t <= 0:
        return None
    peaks = ctx.peaks()
    work = ctx.work("flip_update").work
    least = 0.0
    for s in ctx.segments:
        ops, nbytes = work(s["K"], s["B"], s["O"])
        least += s["steps"] * max(nbytes / peaks["hbm_bytes_per_s"],
                                  ops / peaks["int8_op_per_s"])
    return 100.0 * least / t if least > 0 else None
