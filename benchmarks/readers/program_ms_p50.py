"""Median device time of one compiled program's executions (trace)."""
from benchmarks.harness import reduce, trace


def read(ctx, match: str):
    if ctx.events is None:
        return None
    times = trace.program_times(ctx.programs, match)
    return reduce.percentile(times, 50) / 1e6 if times else None
