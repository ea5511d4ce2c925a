"""Racer (``core/sat/walksat_jax.py``): share of the window's verdicts
whose winning II was solved by the device walk (``IIAttempt.via`` of
``walksat``) rather than by the CDCL fallback. The walk cell exists for
verdicts the walk decides; this says how far it holds."""


def read(ctx):
    if not ctx.served:
        return None
    walked = sum(1 for r in ctx.served
                 if any(a.via == "walksat" and a.status == "SAT"
                        and a.ii == r["res"].ii for a in r["res"].attempts))
    return 100.0 * walked / len(ctx.served)
