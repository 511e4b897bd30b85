"""A program's share of its roofline: the least time the chip could take
for the operations or bytes the program needs (from shapes,
``benchmarks/harness/costs.py``) over its median device time (trace)."""
from benchmarks.harness import costs, peaks, reduce, trace


def read(ctx, match: str, cost: str):
    if ctx.events is None or ctx.rehearsal:  # a CPU has no roofline
        return None
    times = trace.program_times(ctx.programs, match)
    if not times:
        return None
    peak, c = peaks.peaks_of(ctx.device_kind), ctx.config
    if cost == "train_step":
        tr = c["train"]
        least = costs.train_step_flops(
            c, tr["num_hidden_layers"], tr["batch"],
            tr["seq"]) / peak["flops_per_s"]
    elif cost == "decode_tick":
        sv = c["serve"]
        span = ctx.trace_span
        live = [t[3] for t in ctx.ticks if span[0] <= t[0] < span[1]]
        if not live:
            return None
        least = costs.decode_tick_bytes(
            c, sv["num_hidden_layers"], sv["slots"],
            sum(live) / len(live)) / peak["hbm_bytes_per_s"]
    else:
        raise KeyError(cost)
    return 100.0 * least / (reduce.percentile(times, 50) / 1e9)
