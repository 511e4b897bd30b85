"""A program's share of its roofline: the least time the chip could take
for the operations and bytes the program needs (from shapes: the
``COSTS`` table of the configuration's family, ``benchmarks/families/``)
over its median device time (trace). The least time is the larger of
operations over peak FLOP/s and bytes over peak bytes/s, of whichever
the cost function gives."""
from benchmarks.harness import peaks, reduce, trace


def read(ctx, match: str, cost: str):
    if ctx.events is None or ctx.rehearsal:  # a CPU has no roofline
        return None
    times = trace.program_times(ctx.programs, match)
    if not times:
        return None
    span = ctx.trace_span
    live = [t[3] for t in ctx.ticks if span[0] <= t[0] < span[1]]
    sizes = {"live_positions": sum(live) / len(live) if live else None}
    need = ctx.family.COSTS[cost](ctx.config, sizes)
    if need is None:
        return None
    peak = peaks.peaks_of(ctx.device_kind)
    least = max(need.get("flops", 0.0) / peak["flops_per_s"],
                need.get("bytes", 0.0) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (reduce.percentile(times, 50) / 1e9)
