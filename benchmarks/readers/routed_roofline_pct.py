"""A part of the decode tick against its roofline, where what the part
needs depends on what the tick's tokens did: the least time the chip
could take for the bytes the family's ``COSTS[cost]`` gives for the
traced ticks' own sizes (held experts touched, from the program's
``ENG_ROUTE`` records; live positions, and those inside the window,
from the benchmark's stamps), over the median device time of the ops
under ``scopes`` or named ``ops...`` in an execution of the program (of
the whole program where ``scopes`` is null)."""
from benchmarks.harness import peaks, reduce, trace
from benchmarks.readers import _route


def read(ctx, match: str, cost: str, scopes: list = None, ops: list = ()):
    if ctx.events is None or ctx.rehearsal:  # a CPU has no roofline
        return None
    times = trace.program_times(ctx.programs, match) if scopes is None \
        else _route.scope_times(ctx, match, scopes, ops)
    routes = _route.decode_routes(ctx, traced=True)
    if not any(times) or routes is None or not len(routes):
        return None
    sizes = {"experts_touched": float(
        routes[:, _route.FIELDS["touched"]].mean())}
    sizes.update(_route.live_sizes(
        ctx, int(ctx.config.get("sliding_window") or 0)) or {})
    need = ctx.family.COSTS[cost](ctx.config, sizes)
    if need is None:
        return None
    least = need["bytes"] / peaks.peaks_of(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (reduce.percentile(times, 50) / 1e9)
