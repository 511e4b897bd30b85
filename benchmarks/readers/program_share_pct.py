"""Device time of one program as a share of the traced window."""
from benchmarks.harness import trace


def read(ctx, match: str):
    if ctx.events is None:
        return None
    times = trace.program_times(ctx.programs, match)
    if not times:
        return None
    t0, t1 = trace.window_of(ctx.events)
    return 100.0 * sum(times) / (t1 - t0)
