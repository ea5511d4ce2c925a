"""CDCL leg (``core/sat/cdcl.py``): conflicts the program records per II
attempt (``IIAttempt.conflicts``), summed over the window's verdicts and
divided by their number -- a count of proof work (no sound CDCL timer
exists: ``IIAttempt.solve_time`` includes queueing)."""


def read(ctx):
    if not ctx.served:
        return None
    total = sum(a.conflicts or 0 for r in ctx.served
                for a in r["res"].attempts)
    return total / len(ctx.served)
