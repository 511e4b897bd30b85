"""Median device time that a prompt forward padded to the bucket (the
configuration's ``serve.prompt_bucket`` rows, the widest rung of the
engine's ladder) spends under one of ``scopes`` (trace).

The engine pads a prompt to the narrowest rung that holds it, so a
median over every prompt forward falls on whichever rung the traced
part happened to sample more (a factor of two between two runs of one
program); this reads one shape. A forward is known by its
``ENG_PREFILL`` record (``rows``) and its request's ``ENG_ADMIT``
(``prompt_len``), laid on the trace's clock by the ring's measured
offset: it is the execution whose midpoint lies inside the record's
dispatch and wait.

``None`` without a trace, where the program keeps no such records or
names no such scope, where the two clocks cannot be joined, or where no
forward at the bucket ran in the traced part.
"""
import bisect

from benchmarks.readers import _ring, _route


def forwards(ctx, match: str, scopes) -> list[tuple[int, int]]:
    """``(device ns under scopes, real prompt tokens)`` of each traced
    execution of the program that ran at the bucket's rows."""
    v = _ring.view(ctx)
    if not ctx.events or not _ring.trusted(v):
        return []
    bucket = int(ctx.config["serve"]["prompt_bucket"])
    off = int(round(v.offset["offset_ns"]))
    spans = []   # start, end on the trace's clock; prompt tokens
    for whole in v.whole.values():
        plen = {r[3]: r[5] for r in
                whole[whole[:, 1] == int(v.Ev.ENG_ADMIT)].tolist()}
        spans += [(r[0] + off, r[0] + r[5] + r[6] + off, plen[r[3]])
                  for r in whole[whole[:, 1] == int(v.Ev.ENG_PREFILL)].tolist()
                  if r[7] == bucket and r[3] in plen]
    spans.sort()
    starts = [s[0] for s in spans]
    progs = [p for p in ctx.programs if match in p["name"]]
    found = []
    for p, ns in zip(progs, _route.scope_times(ctx, match, scopes)):
        mid = p["start"] + p["dur"] // 2
        i = bisect.bisect_right(starts, mid) - 1
        if ns and i >= 0 and mid < spans[i][1]:
            found.append((ns, spans[i][2]))
    return found


def read(ctx, match: str, scopes: list):
    if ctx.events is None:
        return None
    return _route.p50_ms([ns for ns, _ in forwards(ctx, match, scopes)])
