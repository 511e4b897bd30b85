"""Median host share of one ``bench.*`` span: its wall time minus the
device time of the programs that ran inside it (trace). A program is
inside the span that holds its midpoint: the device's clock runs some
tenths of a millisecond off the host's, so a start can fall outside."""
from benchmarks.harness import reduce, trace


def mid(p: dict) -> float:
    return p["start"] + p["dur"] / 2


def read(ctx, span: str):
    if ctx.events is None:
        return None
    spans = [e for e in trace.annotations(ctx.events) if e["name"] == span]
    progs = ctx.programs
    if not spans or not progs:
        return None
    host, i = [], 0
    for sp in spans:
        end = sp["start"] + sp["dur"]
        while i < len(progs) and mid(progs[i]) < sp["start"]:
            i += 1
        inside, j = 0, i
        while j < len(progs) and mid(progs[j]) < end:
            inside += progs[j]["dur"]
            j += 1
        if j > i:  # a tick that ran nothing on the device is not a tick
            host.append(sp["dur"] - inside)
    return reduce.percentile(host, 50) / 1e6 if host else None
