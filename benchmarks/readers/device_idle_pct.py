"""Share of the traced window in which no op ran on the device."""
from benchmarks.harness import trace


def read(ctx):
    if ctx.events is None:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(ctx.events)
                    / (ctx.trace_span[1] - ctx.trace_span[0]))
