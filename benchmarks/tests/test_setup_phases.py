"""The set-up's split (``benchmarks/readers/setup_phases.py``): every
part by hand on a hand-made view, the cases in which the reader has to
say ``None``, and one traced rehearsal each of a gateway cell, the
co-located cell and the solo trainer, whose parts sum to the run's own
``setup_s``."""
import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmarks.harness.spec import Spec
from benchmarks.readers import _ring, setup_phases
from pbs_tpu.obs import trace as pt
from pbs_tpu.obs.trace import Ev, TraceBuffer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
S = 1_000_000_000
T0, T1 = 124 * S, 126 * S


def at(seconds: float) -> int:
    return round(seconds * S)


def compile_(ring, start, end, kind, fun, scope=None, cache=pt.CACHE_NONE):
    ring.emit(at(start), Ev.HOST_COMPILE, pt.COMPILE_KINDS.index(kind),
              at(end - start), pt.job_tag(fun),
              pt.job_tag(scope) if scope else 0, cache,
              at(0.01) if cache == pt.CACHE_HIT else 0)


def phase(ring, start, end, name, compile_s, size=0, scope=None):
    ring.emit(at(start), Ev.HOST_PHASE, pt.job_tag(name), at(end - start),
              at(compile_s), size, pt.job_tag(scope) if scope else 0)


def made(start: bool = True, late: bool = False):
    """A process that began at 100 s: imports to 103, the backend at
    110, a server built from 111 to 116, weights' program at 116.5, a
    first tick at 118.2, a program built by a request at 119, the window
    from 124 to 126."""
    host, eng, part = TraceBuffer(64), TraceBuffer(64), TraceBuffer(64)
    if start:
        host.emit(at(100), Ev.HOST_START, at(2.5), at(3), at(10), at(0.001),
                  1, 0)
    phase(host, 111, 112, "serve.place", 0, size=4096)
    compile_(host, 112.1, 112.3, "backend", "jit(broadcast_in_dim)")
    phase(host, 112, 112.5, "eng.cache", 0.2, size=1024)
    compile_(host, 113, 113.5, "trace", "_prefill", "eng.prefill@8")
    compile_(host, 113.5, 114, "lower", "jit(_prefill)", "eng.prefill@8")
    compile_(host, 114, 115.5, "backend", "jit(_prefill)", "eng.prefill@8",
             pt.CACHE_HIT)
    phase(host, 113, 116, "eng.build", 2.5, size=8, scope="eng.prefill@8")
    compile_(host, 116.5, 117, "backend", "jit(weights)",
             cache=pt.CACHE_MISS)
    compile_(host, 119, 119.25, "trace", "_threefry_split")
    # A co-located trainer's quantum before the server was built is not
    # the warm-up's start.
    part.emit(at(112.7), Ev.SCHED_PICK, 0, 1000)
    eng.emit(at(118.2), Ev.ENG_TICK, at(0.02), 0, 1, 1, 0, 0)
    eng.emit(at(124.45), Ev.ENG_TICK, at(0.25), 7, 1, 0, 0, 0)
    if late:
        compile_(host, 124.5, 124.6, "lower", "jit(late)")
    return [("engine", eng), ("host", host), ("partition:p#0", part)]


def view(rings) -> _ring.View:
    return _ring.View(pt, T0, T1, rings=rings)


def test_every_part_by_hand():
    s = setup_phases.split(view(made()))
    parts = s["parts"]
    assert parts == {
        "import": at(3), "runtime_init": at(7),
        # 0.2 in eng.cache + 2.5 in eng.build + 0.5 of weights + 0.25
        # built by a request
        "programs": at(3.45),
        # three spans of 1 + 0.5 + 3 s less the 2.7 s compiled inside
        "construct": at(1.8),
        # 118.2 -> 124 less the 0.25 s compiled there
        "warmup": at(5.55),
        # 110-111, 112.5-113, 116-116.5, 117-118.2
        "unaccounted": at(3.2),
        "cache_hit_pct": pytest.approx(100 / 3)}
    assert sum(parts[k] for k in setup_phases.DURATIONS) == \
        s["total_ns"] == T0 - at(100)
    assert s["in_warmup"] == (1, at(0.25)) and s["in_window"] == []
    assert s["records"] == 10   # 6 compiles, 3 spans, the start


def test_the_readers_value_and_its_lines(capsys):
    rings = made(late=True)
    ctx = types.SimpleNamespace(t0=T0 / S, t1=T1 / S, events=None,
                                trace_span=None)
    vars(ctx)["_ring_view"] = view(rings)
    assert setup_phases.read(ctx, "programs") == 3.45
    assert setup_phases.read(ctx, "cache_hit_pct") == pytest.approx(100 / 3)
    out = capsys.readouterr().out
    assert out.count("ring: set-up by phase") == 1     # printed once
    assert "programs 3.450 (6 records, 1 of them 0.250 s inside the " \
        "warm-up)" in out
    assert "construct 1.800 (serve.place 1.000, eng.cache 0.300, " \
        "eng.build 0.500)" in out
    assert "_prefill [eng.prefill@8] 2.500 = 0.500 + 0.500 + 1.500, hit" \
        in out
    assert "weights 0.500 = 0.000 + 0.000 + 0.500, compiled and written" \
        in out
    # A program built under traffic names itself, its kind, its wall and
    # the tick it fell in.
    assert "ring: compiled in the window: 1: jit(late) lower 0.100 s in " \
        "tick 7" in out


def test_none_without_the_records_and_where_a_ring_lost_any():
    # The parent's program: a host ring with no HOST_START in it.
    assert setup_phases.split(view(made(start=False))) is None
    # No host ring at all.
    assert setup_phases.split(view(made()[:1])) is None
    # A lapped ring has lost the set-up's first records.
    rings = made()
    small = TraceBuffer(2)
    for i in range(3):
        small.emit(at(120 + i), Ev.ENG_TICK, 1, i, 0, 0, 0, 0)
    assert small.lost == 1
    assert setup_phases.split(view(rings + [("engine~2", small)])) is None
    # A part the run does not have is None, not 0: no constructor ran.
    host = TraceBuffer(8)
    host.emit(at(100), Ev.HOST_START, at(2.5), at(3), at(10), 0, 1, 0)
    parts = setup_phases.split(view([("host", host)]))["parts"]
    assert parts["construct"] is None and parts["warmup"] is None
    assert parts["cache_hit_pct"] is None
    assert parts["unaccounted"] == T0 - at(110)


def rehearse(cell: str):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "12", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout, json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["serve-chat-steady", "colo-train-serve",
                                  "train-solo"])
def test_a_traced_rehearsal_reports_the_split_and_it_closes(cell):
    out, result = rehearse(cell)
    listed = [m["name"] for m in Spec().metrics_of(cell, "per_layer")
              if m["moves"] == "setup_s"]
    assert len(listed) == 7
    got = {n: result["metrics"]["rehearsal_" + n]["value"] for n in listed}
    durations = [v for n, v in got.items() if n.endswith("_s")]
    assert len(durations) == 6 and all(v >= 0 for v in durations)
    # The parts sum to t0 less the process's start, and that to the
    # run's own setup_s (measured from run.py's first line to the end of
    # set-up): the two origins agree.
    setup_s = float(re.search(r"^setup ([0-9.]+)s", out, re.M).group(1))
    assert abs(sum(durations) - setup_s) < 0.3, (got, setup_s)
    assert got["setup.unaccounted_s"] < 0.5 * setup_s
    assert "ring: compiled in the window: 0\n" in out
