"""Set-up from the inside: where the time from the process's start to
the window's ``t0`` went, read from the program's ``host`` ring
(``HOST_START``, ``HOST_COMPILE``, ``HOST_PHASE``; docs/TRACING.md
"Where a start-up goes") and the first tick or quantum of the warm-up.

Seven parts, six of them durations that are disjoint and sum to ``t0``
less the process's start:

- ``import``: process start -> the entry point's imports done;
- ``runtime_init``: -> the JAX backend answered;
- ``programs``: the union of every outermost compile event (trace,
  lower, compile or load) from there to ``t0``;
- ``construct``: the union of the constructors' spans, less the
  compiles inside them: what the constructors themselves ran;
- ``warmup``: the first ``ENG_TICK`` or ``SCHED_PICK`` after the last
  constructor's span -> ``t0``, less any compile that fell there (which
  is in ``programs``, and named in the log: a program first built by a
  request is a finding);
- ``unaccounted``: what no record names;
- ``cache_hit_pct``: backend events before ``t0`` that the persistent
  cache served, of all.

Once a run it prints the split, the programs by wall and **every
program built inside the window** (``ring: compiled in the window: 0``
is the sound reading). ``None`` on a program without the records, where
any ring lost a record, and for a part the run does not have.
"""

from __future__ import annotations

from benchmarks.harness import trace
from benchmarks.readers import _ring

DURATIONS = ("import", "runtime_init", "programs", "construct", "warmup",
             "unaccounted")
#: Programs named in the log, largest first.
TOP = 12


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``intervals`` inside ``[lo, hi)``."""
    return trace.merge((max(s, lo), min(e, hi)) for s, e in intervals
                       if min(e, hi) > max(s, lo))


def _minus(a, b) -> list[tuple[int, int]]:
    """What the disjoint sorted ``a`` covers and the disjoint sorted
    ``b`` does not."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def split(v: _ring.View) -> dict | None:
    """The parts in nanoseconds (``cache_hit_pct`` in percent) with what
    the log lines print, or ``None``."""
    Ev, pt = v.Ev, v.pt
    host = v.whole.get("host")
    if host is None or v.lost or not hasattr(Ev, "HOST_START"):
        return None
    start = host[host[:, 1] == int(Ev.HOST_START)].tolist()
    if len(start) != 1:
        return None
    origin, _ev, _pkg, import_ns, backend_ns, ask_ns, devices, flags = start[0]
    ready, t0, t1 = origin + backend_ns, v.t0_ns, v.t1_ns
    if not origin + import_ns <= ready <= t0:
        return None
    compiles = host[host[:, 1] == int(Ev.HOST_COMPILE)].tolist()
    phases = host[host[:, 1] == int(Ev.HOST_PHASE)].tolist()

    built = _clip(((r[0], r[0] + r[3]) for r in compiles), ready, t0)
    spans = _clip(((r[0], r[0] + r[3]) for r in phases), ready, t0)
    # The warm-up: from the first tick or quantum that follows the last
    # constructor's span (a co-located trainer's first steps run before
    # the server is built; they are not the warm-up).
    built_by = max((r[0] + r[3] for r in phases if r[0] + r[3] <= t0),
                   default=ready)
    firsts = [int(rows[i, 0]) for ev in (Ev.ENG_TICK, Ev.SCHED_PICK)
              for rows in v.rows(ev, whole=True)
              for i in (rows[:, 0] >= built_by).nonzero()[0][:1]]
    warm_from = min((t for t in firsts if t < t0), default=None)
    warm = [] if warm_from is None else \
        _minus(_minus([(warm_from, t0)], built), spans)

    parts = {"import": import_ns, "runtime_init": backend_ns - import_ns,
             "programs": _length(built),
             "construct": _length(_minus(spans, built)) if phases else None,
             "warmup": _length(warm) if warm else None}
    parts["unaccounted"] = (t0 - origin) - sum(
        x for x in parts.values() if x is not None)
    backend = [r for r in compiles if r[2] == 2 and r[0] < t0]
    parts["cache_hit_pct"] = 100.0 * sum(
        1 for r in backend if r[6] == pt.CACHE_HIT) / len(backend) \
        if backend else None

    in_warm = [r for r in compiles
               if warm_from is not None and warm_from <= r[0] < t0]
    return {
        "parts": parts, "total_ns": t0 - origin,
        "records": len(compiles) + len(phases) + 1,
        "compiles": [r for r in compiles if r[0] < t0],
        "before_ready": sum(1 for r in compiles if r[0] < ready),
        "in_warmup": (len(in_warm), sum(r[3] for r in in_warm)),
        "phases": phases,
        "in_window": [r for r in compiles if r[0] + r[3] > t0 and r[0] < t1],
        "origin": "pbs_tpu's first import" if flags & pt.START_FROM_IMPORT
        else "/proc/self/stat",
        "ask_ns": ask_ns, "devices": devices}


def _where(v: _ring.View, ts: int) -> str:
    """The tick or executed step whose span holds ``ts``."""
    Ev = v.Ev
    for rows in v.rows(Ev.ENG_TICK, whole=True):
        for r in rows.tolist():
            if r[0] <= ts < r[0] + r[2]:
                return f"tick {r[3]}"
    for rows in v.rows(Ev.EXEC_STEP, whole=True):
        for r in rows.tolist():
            if r[0] <= ts < r[0] + r[3] + r[4] + r[5]:
                return f"a step of job {v.pt.tag_name(r[6])}"
    return "between ticks"


def _lines(v: _ring.View, s: dict) -> str:
    pt, sec = v.pt, lambda ns: f"{ns / 1e9:.3f}"
    p = s["parts"]
    by_phase: dict[str, int] = {}
    for r in s["phases"]:
        name = pt.tag_name(r[2])
        by_phase[name] = by_phase.get(name, 0) + r[3] - r[4]
    n_warm, ns_warm = s["in_warmup"]
    notes = {
        "programs": f"{len(s['compiles'])} records, {n_warm} of them "
                    f"{sec(ns_warm)} s inside the warm-up"
                    + (f", {s['before_ready']} before the backend answered"
                       if s["before_ready"] else ""),
        "construct": ", ".join(f"{k} {sec(ns)}"
                               for k, ns in by_phase.items())}
    lines = ["ring: set-up by phase (s): " + ", ".join(
        f"{k} {sec(p[k])}" + (f" ({notes[k]})" if notes.get(k) else "")
        for k in DURATIONS if p[k] is not None)
        + f"; sum = t0 - process start = {sec(s['total_ns'])} (origin "
        f"{s['origin']}; {s['devices']} device(s), asked in "
        f"{sec(s['ask_ns'])}; cache hits "
        + ("none asked" if p["cache_hit_pct"] is None
           else f"{p['cache_hit_pct']:.1f}%")
        + f"; {s['records']} records)"]

    # One line a program: the three kinds of one build share the
    # function's name (JAX says ``f`` when it traces and ``jit(f)`` when
    # it lowers and compiles) and the scope.
    progs: dict[tuple, dict] = {}
    for r in s["compiles"]:
        name = pt.tag_name(r[4])
        name = name[4:-1] if name.startswith("jit(") else name
        scope = pt.tag_name(r[5])
        e = progs.setdefault((name, scope), {"ns": [0, 0, 0], "cache": 0})
        e["ns"][r[2]] += r[3]
        e["cache"] = max(e["cache"], r[6])
    verdict = ("not asked", "hit", "compiled and written")
    top = sorted(progs.items(), key=lambda kv: -sum(kv[1]["ns"]))[:TOP]
    lines.append(
        f"ring: programs by wall, the largest {len(top)} of {len(progs)} "
        "(s = trace + lower + backend, cache): " + "; ".join(
            f"{name}" + (f" [{scope}]" if scope != "-" else "")
            + f" {sec(sum(e['ns']))} = " + " + ".join(map(sec, e["ns"]))
            + f", {verdict[e['cache']]}" for (name, scope), e in top))

    inside = s["in_window"]
    lines.append("ring: compiled in the window: " + (
        "0" if not inside else f"{len(inside)}: " + "; ".join(
            f"{pt.tag_name(r[4])} {pt.COMPILE_KINDS[r[2]]} {sec(r[3])} s "
            f"in {_where(v, r[0])}" for r in inside)))
    return "\n".join(lines)


def phases(ctx) -> dict | None:
    """The run's split, made (and its lines printed) once."""
    if "_setup_phases" not in vars(ctx):
        v = _ring.view(ctx)
        s = split(v) if v is not None else None
        if s is not None:
            print(_lines(v, s), flush=True)
        vars(ctx)["_setup_phases"] = s
    return vars(ctx)["_setup_phases"]


def read(ctx, part: str):
    s = phases(ctx)
    if s is None or s["parts"][part] is None:
        return None
    return s["parts"][part] / (1.0 if part == "cache_hit_pct" else 1e9)
