"""A percentile of one of the benchmark's stamped quantities."""
from benchmarks.harness import reduce


def read(ctx, quantity: str, q: float):
    vals = reduce.quantity(ctx.requests, quantity, ctx.t0, ctx.t1)
    return reduce.percentile(vals, q) if vals else None
