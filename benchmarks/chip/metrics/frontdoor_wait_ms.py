"""Front door (``launch/serve.py``): median time from a client's submit
to ``WorkerPool.submit`` of its request -- queueing and micro-batching
before any solver sees it. Benchmark-side clock."""
import statistics


def read(ctx):
    waits = [(ctx.probes.submits[r["dfg"]] - r["t0"]) * 1e3
             for r in ctx.records if r["dfg"] in ctx.probes.submits]
    return statistics.median(waits) if waits else None
