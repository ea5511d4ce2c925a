"""Device: XLA backend compiles inside the measured window (JAX's
``backend_compile_duration`` events; a persistent-cache hit is not a
compile). Set-up is meant to leave none."""


def read(ctx):
    return float(ctx.probes.compiles_between(ctx.t_start, ctx.t_end))
