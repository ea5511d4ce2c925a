"""Racer (``core/sat/walksat_jax.py``): share of the clause rows the
device walk evaluated that are padding, weighting each walked II attempt
of the window's verdicts by its steps: 100 x (1 - sum steps x
``IIAttempt.walk_rows`` / sum steps x ``IIAttempt.walk_rows_padded``)."""


def read(ctx):
    real = padded = 0
    for r in ctx.served:
        for a in r["res"].attempts:
            steps = getattr(a, "walk_steps", None)
            rows = getattr(a, "walk_rows", None)
            rows_padded = getattr(a, "walk_rows_padded", None)
            if steps and rows is not None and rows_padded:
                real += steps * rows
                padded += steps * rows_padded
    if not padded:
        return None
    return 100.0 * (1.0 - real / padded)
