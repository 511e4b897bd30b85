"""Median, over the executions of one compiled program, of the device
time of its ops under one of ``scopes`` (``jax.named_scope`` names in the
program) or named ``ops...`` (a kernel's own name; trace). ``None``
without a trace or where the trace has no such op."""
from benchmarks.readers import _route


def read(ctx, match: str, scopes: list, ops: list = ()):
    if ctx.events is None:
        return None
    times = _route.scope_times(ctx, match, scopes, ops)
    return _route.p50_ms(times) if any(times) else None
