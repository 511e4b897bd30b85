"""The program's own records, read where they lie.

The program keeps flight-recorder rings (``pbs_tpu/obs/trace.py``): the
partition's (``SCHED_PICK``/``EXEC_STEP``/``SCHED_DESCHED``), the
engine's (``ENG_*``), the gateway's (``GW_*``/``SPAN_*``) and the
host's (``HOST_GC``). ``view(ctx)`` peeks at every live one (never
consumes), cuts to the measured window and, once a run, prints what it
found. The rings stamp ``time.monotonic_ns()``, the clock of the
benchmark's own stamps (``ctx.t0``/``t1``/``trace_span`` are
``time.monotonic()`` seconds), so the window needs no alignment. The
device trace runs on the profiler's clock: ``clock_offset`` measures
the distance from paired events (``ENG_TICK`` start against
``bench.serve_step`` start, ``SCHED_PICK`` against ``bench.quantum``)
and ``idle_split`` lays the ring's spans over the trace with it.

A program without these records (the parent of the PR that added them)
gives ``view() is None`` and every reader returns ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import reduce, trace

#: A reader trusts the laid-over ring only below this spread of the
#: paired differences (median absolute deviation).
MAX_RESIDUAL_NS = 50_000
#: Ring span names in ``idle_split`` (innermost wins).
ENGINE_HOST = ("eng.admit", "eng.keysplit", "eng.pre", "eng.post",
               "eng.tick")
ENGINE_ALL = ENGINE_HOST + ("eng.sync",)
IN_QUANTUM = ENGINE_ALL + ("exec.dispatch", "exec.wait", "quantum")
OUTSIDE = "outside"
#: bench.* spans that wrap what ENG_TICK wraps.
BENCH_TICK = ("bench.serve_step", "bench.decode", "bench.prefill")
_PREFIX = trace.ANNOTATION_PREFIX


def _program():
    try:
        from pbs_tpu.obs import trace as pt
    except ImportError:
        return None
    if not hasattr(pt, "live_rings") or not hasattr(pt.Ev, "ENG_TICK"):
        return None
    return pt


class View:
    """Ring records of one run: ``rings`` maps owner name to the
    ``(n, 8)`` records of the measured window (ts, event, six
    arguments), ``whole`` to all the ring still holds."""

    def __init__(self, pt, t0_ns: int, t1_ns: int, rings=None):
        self.pt, self.Ev = pt, pt.Ev
        self.t0_ns, self.t1_ns = t0_ns, t1_ns
        self.rings: dict[str, np.ndarray] = {}
        self.whole: dict[str, np.ndarray] = {}
        self.lost = 0          # overwritten, all rings, whole run
        self.lost_inside = 0   # rings whose loss reaches into the window
        for name, ring in (pt.live_rings() if rings is None else rings):
            recs = ring.peek(ring.capacity).astype(np.int64)
            self.whole[name] = recs
            ts = recs[:, 0]
            self.rings[name] = recs[(ts >= t0_ns) & (ts < t1_ns)]
            lost = int(ring.lost)
            self.lost += lost
            if lost and (not len(recs) or ts[0] > t0_ns):
                self.lost_inside += 1
        self.offset: dict | None = None

    @property
    def ok(self) -> bool:
        return self.lost_inside == 0

    def rows(self, event, whole: bool = False) -> list[np.ndarray]:
        """Per ring, the records of ``event`` in ring order."""
        src = self.whole if whole else self.rings
        return [r[r[:, 1] == int(event)] for r in src.values()]

    def merged(self) -> np.ndarray:
        return self.pt.merge_records(list(self.rings.values()))

    def describe(self, rec) -> str:
        try:
            name = self.Ev(int(rec[1])).name
        except ValueError:
            name = hex(int(rec[1]))
        return f"{name}@{rec[0] / 1e9:.6f}({','.join(map(str, rec[2:]))})"


def view(ctx) -> View | None:
    """The run's view, made (and its one log line printed) once."""
    if "_ring_view" in vars(ctx):
        return vars(ctx)["_ring_view"]
    pt = _program()
    v = None
    if pt is not None:
        v = View(pt, int(ctx.t0 * 1e9), int(ctx.t1 * 1e9))
        if ctx.events:
            v.offset = clock_offset(v, ctx.events, ctx.trace_span)
        print(_log_line(v), flush=True)
        print(_pieces_line(v), flush=True)
    vars(ctx)["_ring_view"] = v
    return v


def _log_line(v: View) -> str:
    recs = v.merged()
    parts = [f"rings {sorted(v.rings)}: {len(recs)} records in the window",
             f"lost {v.lost} ({v.lost_inside} ring(s) inside the window)"]
    off = v.offset
    if off is not None:
        parts.append(
            f"trace clock - ring clock {off['offset_ns'] / 1e3:.1f} us, "
            f"residual {off['residual_ns'] / 1e3:.1f} us over "
            f"{off['pairs']} {off['pair']} pairs"
            + "".join(f" ({k}: {o / 1e3:.1f} us, residual {r / 1e3:.1f})"
                      for k, (o, r, _n) in off["others"].items()))
    if len(recs) > 1:
        gaps = np.diff(recs[:, 0])
        i = int(np.argmax(gaps))
        parts.append(f"longest gap between records {gaps[i] / 1e6:.3f} ms, "
                     f"{v.describe(recs[i])} -> {v.describe(recs[i + 1])}")
    gcs = [int(r[2]) for rows in v.rows(v.Ev.HOST_GC) for r in rows]
    parts.append(f"full GCs in the window {len(gcs)}"
                 + (f", longest {max(gcs) / 1e6:.3f} ms" if gcs else ""))
    return "ring: " + "; ".join(parts)


def _pieces_line(v: View) -> str:
    """Median of every duration the records carry, over the window: the
    inside breakdown of the tick and of the quantum."""
    Ev = v.Ev
    pieces: dict[str, list[int]] = {}
    jobs = {v.pt.job_tag(n): n for n in ("train", "serve")}

    def add(name, value):
        pieces.setdefault(name, []).append(value)

    for recs in v.rings.values():
        pick = None
        for ts, ev, a0, a1, a2, a3, a4, a5 in recs.tolist():
            if ev == Ev.ENG_TICK:
                add("tick", a0)
            elif ev == Ev.ENG_KEYSPLIT:
                add("keysplit", a1)
            elif ev == Ev.ENG_DECODE:
                add("pre", a1), add("sync", a2), add("post", a3)
            elif ev == Ev.ENG_ADMIT:
                add("admit", a5), add("admit.wait", a4)
            elif ev == Ev.ENG_PREFILL:
                add("prefill.dispatch", a3), add("prefill.sync", a4)
            elif ev == Ev.EXEC_STEP:
                job = jobs.get(a4, hex(a4))
                add(f"exec[{job}].dispatch", a1)
                add(f"exec[{job}].wait", a2)
            elif ev == Ev.SCHED_PICK:
                pick = ts
            elif ev == Ev.SCHED_DESCHED and pick is not None:
                add("quantum", ts - pick)
                pick = None
    pieces["between_quanta"] = quantity(v, "between_quanta")
    return "ring: p50 of each piece, ms (n): " + ", ".join(
        f"{k} {reduce.percentile(vals, 50) / 1e6:.3f} ({len(vals)})"
        for k, vals in pieces.items() if vals)


# -- the shared clock --------------------------------------------------------


def _pair(ring_ts, ann_ts, max_shift: int = 3):
    """Differences ``ann - ring`` of two event sequences that are the
    same events seen on two clocks, one of which may have a few more at
    either end: the alignment whose differences spread least."""
    best = None
    for k in range(-max_shift, max_shift + 1):
        r = ring_ts[max(k, 0):]
        a = ann_ts[max(-k, 0):]
        n = min(len(r), len(a))
        if n < 2:
            continue
        d = a[:n] - r[:n]
        mad = float(np.median(np.abs(d - np.median(d))))
        if best is None or mad < best[0]:
            best = (mad, d)
    return best


def clock_offset(v: View, events, span) -> dict | None:
    """``{"offset_ns", "residual_ns", "pairs", "pair", "others"}``: what
    to add to a ring stamp to get the trace's clock, as the median
    difference of paired starts over the traced part, and how far the
    differences spread around it (median absolute deviation)."""
    lo, hi = int(span[0] * 1e9), int(span[1] * 1e9)
    anns = trace.annotations(events)
    found = {}
    for label, event in (("bench.serve_step", v.Ev.ENG_TICK),
                         ("bench.quantum", v.Ev.SCHED_PICK)):
        a = np.array([e["start"] for e in anns if e["name"] == label],
                     dtype=np.int64)
        r = np.sort(np.concatenate(
            [rows[:, 0] for rows in v.rows(event, whole=True)]
            or [np.empty(0, np.int64)]))
        r = r[(r >= lo) & (r < hi)]
        best = _pair(r, a) if len(a) and len(r) else None
        if best is not None:
            mad, d = best
            found[f"{event.name}~{label}"] = (
                float(np.median(d)), mad, len(d))
    if not found:
        return None
    pair = min(found, key=lambda k: found[k][1])
    off, res, n = found.pop(pair)
    return {"offset_ns": off, "residual_ns": res, "pairs": n, "pair": pair,
            "others": found}


def trusted(v: View | None) -> bool:
    """Nothing lost inside the window, and a clock offset that holds."""
    return (v is not None and v.ok and v.offset is not None
            and v.offset["residual_ns"] <= MAX_RESIDUAL_NS)


# -- the ring's spans, laid over the trace -----------------------------------


def program_spans(v: View) -> list[dict]:
    """``{"name", "start", "dur"}`` on the ring's clock, properly
    nested: quanta and the gaps between them, executed steps split into
    dispatch and wait, engine ticks split into admission (its prefill
    wait taken out as ``eng.sync``), key splits, ``pre``/``sync``/
    ``post``."""
    Ev, out = v.Ev, []

    def add(name, start, dur):
        if dur > 0:
            out.append({"name": name, "start": int(start), "dur": int(dur)})

    for recs in v.whole.values():
        pick = None
        last_desched = None
        for ts, ev, a0, a1, a2, a3, a4, a5 in recs.tolist():
            if ev == Ev.SCHED_PICK:
                if last_desched is not None:
                    add("between_quanta", last_desched, ts - last_desched)
                pick = ts
            elif ev == Ev.SCHED_DESCHED and pick is not None:
                add("quantum", pick, ts - pick)
                pick, last_desched = None, ts
            elif ev == Ev.EXEC_STEP:   # slot, dispatch, wait, compile
                add("exec.dispatch", ts, a1 + a3)
                add("exec.wait", ts + a1 + a3, a2)
            elif ev == Ev.ENG_TICK:
                add("eng.tick", ts, a0)
            elif ev == Ev.ENG_ADMIT:   # tick, rid, slot, plen, wait, dur
                add("eng.admit", ts, a5)
            elif ev == Ev.ENG_PREFILL:  # tick, rid, slot, dispatch, sync
                add("eng.sync", ts + a3, a4)
            elif ev == Ev.ENG_KEYSPLIT:
                add("eng.keysplit", ts, a1)
            elif ev == Ev.ENG_DECODE:  # tick, pre, sync, post
                add("eng.pre", ts, a1)
                add("eng.sync", ts + a1, a2)
                add("eng.post", ts + a1 + a2, a3)
    return out


def idle_split(ctx, v: View) -> dict[str, int]:
    """Device-idle nanoseconds of the traced part by innermost program
    span: the benchmark's own ``idle_by_annotation`` over the same
    device events, with the ring's spans (moved to the trace's clock)
    in the place of the ``bench.*`` annotations, so that inside and
    outside attribute the same gaps the same way."""
    if "_ring_idle" in vars(ctx):
        return vars(ctx)["_ring_idle"]
    off = int(round(v.offset["offset_ns"]))
    events = [e for e in ctx.events if not e["name"].startswith(_PREFIX)]
    lo, hi = trace.window_of(ctx.events)
    for sp in program_spans(v):
        start = sp["start"] + off
        if start < hi and start + sp["dur"] > lo:
            events.append({"plane": "/host:ring", "line": "ring",
                           "name": _PREFIX + sp["name"], "start": start,
                           "dur": sp["dur"]})
    split = {(k[len(_PREFIX):] if k.startswith(_PREFIX) else OUTSIDE): ns
             for k, ns in trace.idle_by_annotation(events).items()}
    vars(ctx)["_ring_idle"] = split
    print(_split_lines(ctx, split), flush=True)
    return split


def _split_lines(ctx, split: dict[str, int]) -> str:
    """The split, and the proof that the clocks are shared: what the
    ring puts inside ENG_TICK (and inside PICK..DESCHED) against what
    the benchmark's annotations put inside the spans that wrap the same
    code from outside, in points of the traced window."""
    window = (ctx.trace_span[1] - ctx.trace_span[0]) * 1e9
    outside = trace.idle_by_annotation(ctx.events)
    lines = ["ring: device idle by innermost program span (s): "
             + ", ".join(f"{k} {ns / 1e9:.4f}" for k, ns in
                         sorted(split.items(), key=lambda kv: -kv[1]))]
    levels = [("inside ENG_TICK", ENGINE_ALL, BENCH_TICK)]
    if "bench.quantum" in outside:
        levels.append(("inside PICK..DESCHED", IN_QUANTUM, tuple(
            k for k in outside if k.startswith(_PREFIX))))
    for label, ring_names, bench_names in levels:
        mine = sum(split.get(k, 0) for k in ring_names)
        theirs = sum(outside.get(k, 0) for k in bench_names)
        if mine or theirs:
            lines.append(
                f"ring: idle {label}: ring {mine / 1e9:.4f} s = "
                f"{100 * mine / window:.2f}% of the traced window, "
                f"bench.* from outside {theirs / 1e9:.4f} s = "
                f"{100 * theirs / window:.2f}%, apart "
                f"{100 * abs(mine - theirs) / window:.2f} points"
                + (f" (PICK..DESCHED is wider than bench.quantum by the "
                   f"executor's accounting: {split.get('quantum', 0) / 1e9:.4f}"
                   f" s of the ring's is outside any EXEC_STEP)"
                   if "quantum" in ring_names else ""))
    return "\n".join(lines)


# -- quantities of the whole window ------------------------------------------


def _in_window(v: View, t_ns) -> bool:
    return v.t0_ns <= t_ns < v.t1_ns


def quantity(v: View, name: str, job: str | None = None) -> list[float]:
    """Nanosecond values of one quantity over the measured window."""
    Ev = v.Ev
    if name == "decode_post":
        return [r[5] for rows in v.rows(Ev.ENG_DECODE) for r in rows.tolist()]
    if name == "decode_pre_no_admission":
        out = []
        for recs in v.rings.values():
            quiet = {r[3] for r in recs[recs[:, 1] == int(Ev.ENG_TICK)]
                     .tolist() if r[5] == 0}
            out += [r[3] for r in recs[recs[:, 1] == int(Ev.ENG_DECODE)]
                    .tolist() if r[2] in quiet]
        return out
    if name == "tick_host_inside":
        return [t for recs in v.rings.values() for t in _tick_host(Ev, recs)]
    if name == "admit_wait":  # requests that reached the engine in the window
        return [r[6] for rows in v.rows(Ev.ENG_ADMIT, whole=True)
                for r in rows.tolist() if _in_window(v, r[0] - r[6])]
    if name == "gateway_qdelay":  # requests submitted in the window
        return [r[4] for rows in v.rows(Ev.SPAN_DISPATCH, whole=True)
                for r in rows.tolist() if _in_window(v, r[0] - r[4])]
    if name == "between_quanta":
        out = []
        for recs in v.rings.values():
            last = None
            for ts, ev, *_ in recs.tolist():
                if ev == Ev.SCHED_DESCHED:
                    last = ts
                elif ev == Ev.SCHED_PICK and last is not None:
                    out.append(ts - last)
                    last = None
        return out
    if name == "step_dispatch":
        tag = v.pt.job_tag(job)
        return [r[3] for rows in v.rows(Ev.EXEC_STEP) for r in rows.tolist()
                if r[6] == tag]
    raise KeyError(name)


def _tick_host(Ev, recs) -> list[int]:
    """Per tick that dispatched a decode: the host time the engine spent
    in its own code: admissions without their prefill waits, key
    splits, ``pre`` and ``post``."""
    host: dict[int, int] = {}
    last_split: dict[int, int] = {}
    decoded = []
    for _ts, ev, a0, a1, _a2, a3, a4, a5 in recs.tolist():
        if ev == Ev.ENG_ADMIT:          # its key split is in its duration
            host[a0] = host.get(a0, 0) + a5
        elif ev == Ev.ENG_PREFILL:      # its wait is not host work
            host[a0] = host.get(a0, 0) - a4
        elif ev == Ev.ENG_KEYSPLIT:     # the last of a tick is the decode's
            last_split[a0] = a1
        elif ev == Ev.ENG_DECODE:
            host[a0] = host.get(a0, 0) + a1 + a3
            decoded.append(a0)
    return [host[t] + last_split.get(t, 0) for t in decoded]
