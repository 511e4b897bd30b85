"""From a profiler trace to device times. The yardstick's own reduction.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
flat list of event dicts (what ``benchmarks/data/*.trace.json`` records);
everything else is a pure function of that list, checked on a recorded
trace in ``benchmarks/tests``.

An event: ``{"plane", "line", "name", "start", "dur"}`` in nanoseconds
on the trace's clock, plus ``"module"`` / ``"run"`` where the profiler
attached the HLO module and run id, and on a device op ``"scope"``: the
op's ``tf_op`` metadata (``jit(_decode)/while/body/attn/dot_general:``),
which is where a ``jax.named_scope`` or a Pallas kernel's name ends up.

- **device ops**: events of a device plane's ``XLA Ops`` line. Where the
  trace has no device plane (a CPU rehearsal) events that carry an
  ``hlo_op`` stat stand in.
- **programs**: one record per execution of a compiled program: the
  ``XLA Modules`` line of a device plane where there is one, else device
  ops grouped by (module, run). ``busy`` is the union of the ops inside.
- **annotations**: the benchmark's own ``bench.*`` host spans
  (``jax.profiler.TraceAnnotation``), flattened so that each instant
  carries its innermost span.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

ANNOTATION_PREFIX = "bench."
NO_ANNOTATION = "_no_bench_annotation_"
#: Ops whose event spans their body's ops, which are events themselves.
CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_op(name: str) -> str:
    """On a TPU an op event is named by its whole HLO instruction
    (``%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)``): keep the op
    and its result type, without layouts."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    rest = _LAYOUT.sub("", rest)
    result = rest[:rest.index(")") + 1] if rest.startswith("(") \
        else rest.split(" ", 1)[0]
    return f"{head.lstrip('%')} {result}"[:96]


def _xplane_path(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def _profile(logdir: str):
    import jax.profiler

    return jax.profiler.ProfileData.from_file(_xplane_path(logdir))


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes for a length-delimited field. ``ProfileData`` shows an
    event's own stats but not its metadata's, so the few fields
    :func:`op_scopes` needs are read from the wire (``xplane.proto``;
    fixed-width fields are skipped)."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        val = shift = 0
        while True:
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return val

    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            yield num, varint()
        elif wire == 2:
            size = varint()
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def op_scopes(path: str, stat: str = "tf_op") -> dict[str, dict[str, str]]:
    """Per device plane of an ``.xplane.pb``, event name -> the string
    stat ``stat`` of that event's metadata. Field numbers: ``XSpace``
    planes 1; ``XPlane`` name 2, event_metadata 4, stat_metadata 5 (map
    entries: value 2); ``XEventMetadata`` name 2, stats 5;
    ``XStatMetadata`` id 1, name 2; ``XStat`` metadata_id 1, str_value 5,
    ref_value 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())  # slices of it copy nothing
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                name = str(v, "utf-8")
            elif k == 4:
                metas.append(dict(_fields(v)).get(2, b""))
            elif k == 5:
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1, 0)] = str(sm.get(2, b""), "utf-8")
        if not name.startswith("/device:"):
            continue
        scopes = out.setdefault(name, {})
        for meta in metas:
            ev_name, found = "", None
            for k, v in _fields(meta):
                if k == 2:
                    ev_name = str(v, "utf-8")
                elif k == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    found = str(st[5], "utf-8") if 5 in st \
                        else stat_names.get(st.get(7), "")
            if found:
                scopes[ev_name] = found
    return out


def describe(logdir: str) -> list[str]:
    """Planes, lines and the first events of each: what to read before
    writing code against a trace."""
    out = []
    for plane in _profile(logdir).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name} ({len(evs)} events)")
            for ev in evs[:4]:
                out.append(f"    {ev.name} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {dict(ev.stats)}")
    return out


def load_xplane(logdir: str) -> list[dict]:
    data = _profile(logdir)
    scopes = op_scopes(_xplane_path(logdir))
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        scope_of = scopes.get(plane.name, {})
        for line in plane.lines:
            on_device = device and line.name in ("XLA Ops", "XLA Modules")
            for ev in line.events:
                name = ev.name
                stats = {}
                if name.startswith(ANNOTATION_PREFIX) or on_device:
                    pass
                elif device:
                    continue
                else:
                    stats = dict(ev.stats)
                    if "hlo_op" not in stats:
                        continue
                scope = None
                if on_device and line.name == "XLA Ops":
                    scope = scope_of.get(name)
                    name = short_op(name)
                rec = {"plane": plane.name, "line": line.name, "name": name,
                       "start": int(ev.start_ns), "dur": int(ev.duration_ns)}
                if scope:
                    rec["scope"] = scope
                if "hlo_module" in stats:
                    rec["module"] = str(stats["hlo_module"])
                    rec["run"] = int(stats.get("run_id", 0))
                out.append(rec)
    return out


def unpack(doc: dict) -> list[dict]:
    """Events of a recorded trace file (``benchmarks/data``), which keeps
    planes, lines and names in tables to stay small."""
    return [{"plane": doc["planes"][p], "line": doc["lines"][l],
             "name": doc["names"][n], "start": start, "dur": dur}
            for p, l, n, start, dur in doc["events"]]


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _device_planes(events) -> list[str]:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith("/device:")})


def device_ops(events) -> dict[str, list[dict]]:
    """Per device (plane name), the ops that ran on it."""
    planes = _device_planes(events)
    if planes:
        return {p: [e for e in events
                    if e["plane"] == p and e["line"] == "XLA Ops"]
                for p in planes}
    ops = [e for e in events if "module" in e]
    return {"/host-as-device": ops} if ops else {}


def busy_intervals(ops) -> list[tuple[int, int]]:
    return merge((e["start"], e["start"] + e["dur"]) for e in ops
                 if e["dur"] > 0)


def busy_seconds(events) -> float:
    """Seconds in which an op ran on the device, averaged over devices."""
    per = [sum(e - s for s, e in busy_intervals(ops))
           for ops in device_ops(events).values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def programs(events) -> list[dict]:
    """Executions of compiled programs on the first device:
    ``{"name", "start", "dur", "busy"}`` sorted by start."""
    per_dev = device_ops(events)
    if not per_dev:
        return []
    plane, ops = sorted(per_dev.items())[0]
    mods = [e for e in events
            if e["plane"] == plane and e["line"] == "XLA Modules"]
    out = []
    if mods:
        ops = sorted(ops, key=lambda e: e["start"])
        i = 0
        for m in sorted(mods, key=lambda e: e["start"]):
            end = m["start"] + m["dur"]
            while i < len(ops) and ops[i]["start"] < m["start"]:
                i += 1
            j = i
            while j < len(ops) and ops[j]["start"] < end:
                j += 1
            busy = sum(e - s for s, e in busy_intervals(ops[i:j]))
            out.append({"name": m["name"], "start": m["start"],
                        "dur": m["dur"], "busy": busy or m["dur"]})
            i = j
        return out
    groups: dict[tuple, list[dict]] = {}
    for e in ops:
        groups.setdefault((e["module"], e["run"]), []).append(e)
    for (module, _run), evs in groups.items():
        start = min(e["start"] for e in evs)
        end = max(e["start"] + e["dur"] for e in evs)
        out.append({"name": module, "start": start, "dur": end - start,
                    "busy": sum(e - s for s, e in busy_intervals(evs))})
    return sorted(out, key=lambda p: p["start"])


def program_times(progs, match: str) -> list[int]:
    """Device nanoseconds of each execution (of :func:`programs`) whose
    name holds ``match``."""
    return [p["dur"] for p in progs if match in p["name"]]


def annotations(events) -> list[dict]:
    return sorted((e for e in events
                   if e["name"].startswith(ANNOTATION_PREFIX)),
                  key=lambda e: (e["start"], -e["dur"]))


def flatten(spans) -> list[tuple[int, int, str]]:
    """Properly nested spans -> disjoint ``(start, end, innermost name)``."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []  # (end, name)
    cursor = None

    def emit(upto):
        nonlocal cursor
        if stack and cursor is not None and upto > cursor:
            out.append((cursor, upto, stack[-1][1]))
        cursor = upto

    for sp in sorted(spans, key=lambda e: (e["start"], -e["dur"])):
        while stack and stack[-1][0] <= sp["start"]:
            end = stack[-1][0]
            emit(end)
            stack.pop()
        emit(sp["start"])
        stack.append((sp["start"] + sp["dur"], sp["name"]))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def window_of(events) -> tuple[int, int]:
    """The traced interval: from the first to the last thing recorded
    (device op or benchmark span)."""
    keep = [e for ops in device_ops(events).values() for e in ops]
    keep += annotations(events)
    if not keep:
        raise ValueError("empty trace")
    return (min(e["start"] for e in keep),
            max(e["start"] + e["dur"] for e in keep))


def idle_by_annotation(events) -> dict[str, int]:
    """Idle nanoseconds of the first device, by what the host was in."""
    per_dev = device_ops(events)
    if not per_dev:
        return {}
    t0, t1 = window_of(events)
    busy = busy_intervals(sorted(per_dev.items())[0][1])
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    segs = flatten(annotations(events))
    out: dict[str, int] = {}
    k = 0
    for gs, ge in gaps:
        while k < len(segs) and segs[k][1] <= gs:
            k += 1
        covered, j = 0, k
        while j < len(segs) and segs[j][0] < ge:
            ov = min(ge, segs[j][1]) - max(gs, segs[j][0])
            if ov > 0:
                out[segs[j][2]] = out.get(segs[j][2], 0) + ov
                covered += ov
            j += 1
        if ge - gs > covered:
            out[NO_ANNOTATION] = out.get(NO_ANNOTATION, 0) + ge - gs - covered
    return out


def top_ops(events, progs, n: int = 10) -> list[list]:
    """The device ops that took most time: ``[program/op, seconds]``."""
    per_dev = device_ops(events)
    if not per_dev:
        return []
    ops = sorted(per_dev.items())[0][1]
    starts = [p["start"] for p in progs]
    total: dict[str, int] = {}
    for e in ops:
        if e["name"].split(".")[0].split(" ")[0] in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, e["start"]) - 1
        inside = i >= 0 and e["start"] < progs[i]["start"] + progs[i]["dur"]
        prog = progs[i]["name"].split("(")[0] if inside \
            else e.get("module", "")
        key = f"{prog}/{e['name']}" if prog else e["name"]
        total[key] = total.get(key, 0) + e["dur"]
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
