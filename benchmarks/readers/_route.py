"""What the new readers share: the ``ENG_ROUTE`` records of a run's
decode ticks (``pbs_tpu/obs/trace.py``: tick, tokens routed,
assignments to held and to absent experts, held experts touched,
largest load of one expert) and the device time a program's executions
spend under ``jax.named_scope`` names (``load_xplane``'s ``"scope"``).

A program that writes no ``ENG_ROUTE`` and names no scope (any before
the PR that added them) gives ``None`` / nothing to read; no reader
raises for it.
"""

from __future__ import annotations

import bisect

import numpy as np

from benchmarks.harness import reduce, trace
from benchmarks.readers import _ring

FIELDS = {"tokens": 3, "held": 4, "absent": 5, "touched": 6, "max_load": 7}


def decode_routes(ctx, traced: bool = False):
    """``(n, 8)`` ``ENG_ROUTE`` records of the window's decode ticks
    (stamped like their ``ENG_DECODE``; a prefill's carries its
    ``ENG_PREFILL``'s stamp), or of the traced part; ``None`` where the
    program keeps none or a ring lost records inside the window."""
    v = _ring.view(ctx)
    if v is None or not v.ok or not hasattr(v.Ev, "ENG_ROUTE"):
        return None
    routes = np.concatenate(v.rows(v.Ev.ENG_ROUTE) or [np.empty((0, 8))])
    decodes = {int(r[0]) for rows in v.rows(v.Ev.ENG_DECODE) for r in rows}
    keep = np.array([int(r[0]) in decodes for r in routes], bool)
    if "_route_line" not in vars(ctx):
        vars(ctx)["_route_line"] = True
        print(_line(routes[keep], routes[~keep]), flush=True)
    routes = routes[keep]
    if traced:
        lo, hi = (int(t * 1e9) for t in ctx.trace_span)
        routes = routes[(routes[:, 0] >= lo) & (routes[:, 0] < hi)]
    return routes


def _line(dec, pre) -> str:
    def part(name, recs):
        if not len(recs):
            return f"{name}: none"
        f = {k: recs[:, i] for k, i in FIELDS.items()}
        both = f["held"].sum() + f["absent"].sum()
        return (f"{name}: {len(recs)}, tokens routed p50 "
                f"{np.median(f['tokens']):.0f}, to absent experts "
                f"{100 * f['absent'].sum() / max(both, 1):.2f}%, held "
                f"experts touched p50 {np.median(f['touched']):.0f}, "
                f"largest load of one expert p50 "
                f"{np.median(f['max_load']):.0f} max "
                f"{f['max_load'].max():.0f}")
    return ("ring: routing, " + part("decode ticks", dec) + "; "
            + part("prefills", pre))


def scope_times(ctx, match: str, scopes, ops=()) -> list[int]:
    """Per execution of a program whose name holds ``match``: the
    device nanoseconds of the ops inside it whose scope holds one of
    ``scopes`` or whose name starts with one of ``ops`` (XLA:TPU's
    grouped product, ``ragged-dot-*``, comes out of an expansion that
    keeps no scope; containers are left out: their bodies' ops are
    events themselves). Empty where the trace has no such op."""
    if not ctx.events:
        return []
    progs = [p for p in ctx.programs if match in p["name"]]
    per_dev = trace.device_ops(ctx.events)
    if not progs or not per_dev:
        return []
    ops = sorted(
        (e for e in sorted(per_dev.items())[0][1]
         if (any(s in e.get("scope", "") for s in scopes)
             or e["name"].startswith(tuple(ops)))
         and e["name"].split(".")[0].split(" ")[0] not in trace.CONTAINERS),
        key=lambda e: e["start"])
    if "_scope_line" not in vars(ctx):
        vars(ctx)["_scope_line"] = True
        _scope_line(ctx, match, progs, sorted(per_dev.items())[0][1])
    if not ops:
        return []
    starts = np.array([e["start"] for e in ops])
    durs = np.concatenate([[0], np.cumsum([e["dur"] for e in ops])])
    lo = np.searchsorted(starts, [p["start"] for p in progs])
    hi = np.searchsorted(starts, [p["start"] + p["dur"] for p in progs])
    return [int(durs[j] - durs[i]) for i, j in zip(lo, hi)]


def _scope_line(ctx, match: str, progs, ops) -> None:
    """Once a run: the median device time an execution of the program
    spends under each scope its ops name (the last part of the path
    that holds a dot: ``attn.full``, ``moe.experts``, ...)."""
    total: dict[str, list[int]] = {}
    starts = [p["start"] for p in progs]
    for e in ops:
        name = next((part for part in e.get("scope", "").split("/")[1:]
                     if "." in part), None)
        if name is None and e["name"].startswith("ragged-dot"):
            name = "ragged-dot-*"
        i = bisect.bisect_right(starts, e["start"]) - 1
        if name is None or i < 0 or \
                e["start"] >= progs[i]["start"] + progs[i]["dur"] or \
                e["name"].split(".")[0].split(" ")[0] in trace.CONTAINERS:
            continue
        per = total.setdefault(name, [0] * len(progs))
        per[i] += e["dur"]
    if total:
        print(f"trace: device ms an execution of {match} under each scope, "
              f"p50 over {len(progs)}: " + ", ".join(
                  f"{k} {reduce.percentile(v, 50) / 1e6:.3f}"
                  for k, v in sorted(total.items())), flush=True)


def live_sizes(ctx, window: int) -> dict | None:
    """Positions held by the requests in a slot, summed over slots and
    averaged over the traced ticks: as they are, and each slot's count
    clipped to ``window`` (what a window layer has to read)."""
    lo, hi = ctx.trace_span
    ticks = [t for t in ctx.ticks if lo <= t[0] < hi]
    if not ticks:
        return None
    reqs = [(r, [t for t, _ in r["stamps"]]) for r in ctx.requests
            if r["admit"] is not None and r["admit"] < hi
            and (r["done"] is None or r["done"] > lo)]
    live = clipped = 0
    for t_in, t_out, _busy, _live in ticks:
        for r, times in reqs:
            if r["admit"] > t_in or (
                    r["done"] is not None and r["done"] <= t_out):
                continue
            k = bisect.bisect_right(times, t_out)
            held = len(r["prompt"]) + (r["stamps"][k - 1][1] if k else 0)
            live += held
            clipped += min(held, window)
    return {"live_positions": live / len(ticks),
            "live_window_positions": clipped / len(ticks)}


def p50_ms(times) -> float | None:
    return reduce.percentile(times, 50) / 1e6 if times else None
