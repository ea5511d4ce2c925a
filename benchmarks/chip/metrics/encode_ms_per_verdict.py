"""Sweep / encoder (``core/encode.py`` via ``core/sweep.py``): encode time
the program records per II attempt (``IIAttempt.encode_time``), summed
over the window's verdicts and divided by their number."""


def read(ctx):
    if not ctx.served:
        return None
    total = sum(a.encode_time for r in ctx.served for a in r["res"].attempts)
    return total * 1e3 / len(ctx.served)
