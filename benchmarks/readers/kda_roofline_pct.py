"""A delta-rule layer's part of a program against its roofline, where
what the part needs depends on how many lanes were busy or how long the
prompts were: the least time the chip could take for what the family's
``COSTS[cost]`` gives for the traced executions' own sizes, over the
median device time of the ops under ``scopes`` in an execution of the
program whose name holds ``match`` (of the whole program where
``scopes`` is null).

The sizes: ``busy_lanes``, the mean busy slots of the traced ticks (the
benchmark's stamps); and, for the whole tick, what
``routed_roofline_pct`` hands over: held experts touched (``ENG_ROUTE``)
and live positions. With ``"rung": "bucket"`` the executions are the
prompt forwards that ran at the bucket's rows alone
(``bucket_prefill_ms.forwards``: one shape, so that the median is of
one thing) and ``prompt_tokens`` is the mean real prompt length of
those same forwards. The least time is the larger of operations over
peak FLOP/s and bytes over peak bytes/s, of whichever the cost gives.
``None`` without a trace, on a CPU, or where the program keeps no such
records or names no such scope.
"""
from benchmarks.harness import peaks, reduce, trace
from benchmarks.readers import _route, bucket_prefill_ms


def read(ctx, match: str, cost: str, scopes: list = None,
         rung: str = None):
    if ctx.events is None or ctx.rehearsal:  # a CPU has no roofline
        return None
    sizes = {}
    if rung == "bucket":
        found = bucket_prefill_ms.forwards(ctx, match, scopes)
        times = [ns for ns, _ in found]
        if found:
            sizes["prompt_tokens"] = sum(n for _, n in found) / len(found)
    else:
        times = trace.program_times(ctx.programs, match) if scopes is None \
            else _route.scope_times(ctx, match, scopes)
    if not any(times):
        return None
    lo, hi = ctx.trace_span
    busy = [t[2] for t in ctx.ticks if lo <= t[0] < hi]
    sizes["busy_lanes"] = sum(busy) / len(busy) if busy else None
    routes = _route.decode_routes(ctx, traced=True)
    if routes is not None and len(routes):
        sizes["experts_touched"] = float(
            routes[:, _route.FIELDS["touched"]].mean())
    sizes.update(_route.live_sizes(ctx, 0) or {})
    need = ctx.family.COSTS[cost](ctx.config, sizes)
    if need is None:
        return None
    peak = peaks.peaks_of(ctx.device_kind)
    least = max(need.get("flops", 0.0) / peak["flops_per_s"],
                need.get("bytes", 0.0) / peak["hbm_bytes_per_s"])
    return 100.0 * least / (reduce.percentile(times, 50) / 1e9)
