"""Share of the traced window in which the device is idle while the
program is inside one of ``spans`` (innermost ring span; names in
``_ring.idle_split``): the ring laid on the trace's clock by the
measured offset. ``None`` without a trace, where the program keeps no
ring records, where a ring lost records inside the window, or where the
clock offset's residual is above ``_ring.MAX_RESIDUAL_NS``."""
from benchmarks.readers import _ring


def read(ctx, spans: list):
    if ctx.events is None:
        return None
    v = _ring.view(ctx)
    if not _ring.trusted(v):
        return None
    split = _ring.idle_split(ctx, v)
    window = (ctx.trace_span[1] - ctx.trace_span[0]) * 1e9
    return 100.0 * sum(split.get(s, 0) for s in spans) / window
