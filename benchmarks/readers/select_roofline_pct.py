"""A selecting latent layer's part of a program against its roofline,
where what the part needs depends on how many positions the traced
ticks' lanes saw and chose among, or on how long the prompts were: the
least time the chip could take for what the family's ``COSTS[cost]``
gives for the traced executions' own sizes, over the median device time
of the ops under ``scopes`` in an execution of the program whose name
holds ``match`` (of the whole program where ``scopes`` is null).

The sizes of a decode tick: ``live_positions`` and ``chosen_positions``,
the means over the traced ticks of the program's ``ENG_SELECT`` records
(positions a busy lane's query sees, and ``min`` of that and ``topk``,
each summed over the busy lanes: one layer's), and ``experts_touched``
from its ``ENG_ROUTE`` records. With ``"rung": "bucket"`` the
executions are the prompt forwards that ran at the bucket's rows alone
(``bucket_prefill_ms.forwards``), each held against what its own
prompt's length needs, and the share is the median over them. The least
time is the larger of operations over peak FLOP/s and bytes over peak
bytes/s, of whichever the cost gives. ``None`` without a trace, on a
CPU, or where the program keeps no such records or names no such scope.
"""
import numpy as np

from benchmarks.harness import peaks, reduce, trace
from benchmarks.readers import _ring, _route, bucket_prefill_ms

#: ``ENG_SELECT``'s arguments in a ring record (ts, event, six of them).
FIELDS = {"rows": 3, "live": 4, "chosen": 5, "topk": 6}


def decode_selects(ctx, traced: bool = False):
    """``(n, 8)`` ``ENG_SELECT`` records of the window's decode ticks
    (stamped like their ``ENG_DECODE``; a prefill's carries its
    ``ENG_PREFILL``'s stamp), or of the traced part; ``None`` where the
    program keeps none or a ring lost records inside the window."""
    v = _ring.view(ctx)
    if v is None or not v.ok or not hasattr(v.Ev, "ENG_SELECT"):
        return None
    recs = np.concatenate(v.rows(v.Ev.ENG_SELECT) or [np.empty((0, 8))])
    decodes = {int(r[0]) for rows in v.rows(v.Ev.ENG_DECODE) for r in rows}
    recs = recs[np.array([int(r[0]) in decodes for r in recs], bool)]
    if traced:
        lo, hi = (int(t * 1e9) for t in ctx.trace_span)
        recs = recs[(recs[:, 0] >= lo) & (recs[:, 0] < hi)]
    return recs


def _least_s(ctx, need: dict) -> float:
    peak = peaks.peaks_of(ctx.device_kind)
    return max(need.get("flops", 0.0) / peak["flops_per_s"],
               need.get("bytes", 0.0) / peak["hbm_bytes_per_s"])


def read(ctx, match: str, cost: str, scopes: list = None,
         rung: str = None):
    if ctx.events is None or ctx.rehearsal:  # a CPU has no roofline
        return None
    count = ctx.family.COSTS[cost]
    if rung == "bucket":
        shares = []
        for ns, tokens in bucket_prefill_ms.forwards(ctx, match, scopes):
            need = count(ctx.config, {"prompt_tokens": float(tokens)})
            if need is not None:
                shares.append(100.0 * _least_s(ctx, need) / (ns / 1e9))
        return reduce.percentile(shares, 50) if shares else None
    times = trace.program_times(ctx.programs, match) if scopes is None \
        else _route.scope_times(ctx, match, scopes)
    selects = decode_selects(ctx, traced=True)
    if not any(times) or selects is None or not len(selects):
        return None
    sizes = {"live_positions": float(selects[:, FIELDS["live"]].mean()),
             "chosen_positions": float(selects[:, FIELDS["chosen"]].mean())}
    routes = _route.decode_routes(ctx, traced=True)
    if routes is not None and len(routes):
        sizes["experts_touched"] = float(
            routes[:, _route.FIELDS["touched"]].mean())
    need = count(ctx.config, sizes)
    if need is None:
        return None
    return 100.0 * _least_s(ctx, need) / (reduce.percentile(times, 50) / 1e9)
