"""The ring readers (``benchmarks/readers/_ring.py``): a synthetic ring
laid over the trace recorded on the chip (``benchmarks/data``) with a
known clock offset, and the cases in which a reader has to say ``None``
and not a wrong number."""
import json
import os
import types

import pytest

from benchmarks.harness import trace
from benchmarks.harness.spec import Spec
from benchmarks.readers import _ring
from pbs_tpu.obs import trace as pt
from pbs_tpu.obs.trace import Ev, TraceBuffer

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "colo-recorded.trace.json")
OFFSET = -5_000_000_000_123          # trace clock - ring clock, ns
NONE = trace.NO_ANNOTATION


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        return trace.unpack(json.load(f))


def synthetic(events, jitter_ns: int = 0, capacity: int = 4096):
    """Rings that say on their own clock what the recorded ``bench.*``
    annotations say on the trace's: quantum -> PICK..DESCHED,
    train_step -> EXEC_STEP's dispatch, serve_step -> ENG_TICK, decode
    -> ENG_DECODE's ``pre``, prefill -> ENG_KEYSPLIT."""
    part, eng = TraceBuffer(capacity), TraceBuffer(capacity)
    tick = 0
    seen: dict[str, int] = {}
    for e in trace.annotations(events):
        name, d = e["name"], e["dur"]
        # The recorded trace is short (three quanta, two ticks), so the
        # jitter is a fixed pattern: early, on time, late.
        seen[name] = seen.get(name, 0) + 1
        s = e["start"] - OFFSET + (seen[name] % 3 - 1) * jitter_ns
        if name == "bench.quantum":
            part.emit(s, Ev.SCHED_PICK, 0, 1000)
            part.emit(s + d, Ev.SCHED_DESCHED, 0, d)
        elif name == "bench.train_step":
            part.emit(s, Ev.EXEC_STEP, 0, d, 0, 0, pt.job_tag("train"))
        elif name == "bench.serve_step":
            tick += 1
            eng.emit(s, Ev.ENG_TICK, d, tick, 16, 0, 0, 0)
        elif name == "bench.decode":
            eng.emit(s, Ev.ENG_DECODE, tick, d, 0, 0)
        elif name == "bench.prefill":
            eng.emit(s, Ev.ENG_KEYSPLIT, tick, d)
    return [("partition:test#0", part), ("engine", eng)]


def context(events, rings, monkeypatch):
    lo, hi = trace.window_of(events)
    span = ((lo - OFFSET) / 1e9 - 1e-4, (hi - OFFSET) / 1e9 + 1e-4)
    monkeypatch.setattr(pt, "live_rings", lambda: list(rings))
    return types.SimpleNamespace(events=events, trace_span=span,
                                 t0=span[0], t1=span[1])


def test_offset_is_recovered_exactly(events, monkeypatch):
    ctx = context(events, synthetic(events), monkeypatch)
    v = _ring.view(ctx)
    assert v.ok and v.lost == 0
    assert v.offset["offset_ns"] == OFFSET and v.offset["residual_ns"] == 0
    assert v.offset["pair"] in ("ENG_TICK~bench.serve_step",
                                "SCHED_PICK~bench.quantum")
    assert list(v.offset["others"].values())[0][:2] == (OFFSET, 0)
    assert _ring.trusted(v) and _ring.view(ctx) is v      # made once


def test_idle_attribution_equals_the_benchmarks_own(events, monkeypatch):
    ctx = context(events, synthetic(events), monkeypatch)
    split = _ring.idle_split(ctx, _ring.view(ctx))
    outside = trace.idle_by_annotation(events)
    assert split["eng.tick"] == outside["bench.serve_step"]
    assert split.get("eng.pre", 0) == outside.get("bench.decode", 0)
    assert split["exec.dispatch"] == outside["bench.train_step"]
    assert split["quantum"] == outside["bench.quantum"] + outside.get(
        "bench.submit", 0)
    assert split["between_quanta"] + split.get(_ring.OUTSIDE, 0) == \
        outside[NONE]
    assert sum(split.values()) == sum(outside.values())
    # The reader's share is that sum over the traced window.
    read = Spec().reader("ring_idle_pct")
    window = (ctx.trace_span[1] - ctx.trace_span[0]) * 1e9
    assert read(ctx, spans=["eng.tick", "eng.pre"]) == pytest.approx(
        100 * (split["eng.tick"] + split.get("eng.pre", 0)) / window)


def test_small_jitter_is_trusted_large_is_not(events, monkeypatch):
    read = Spec().reader("ring_idle_pct")
    ctx = context(events, synthetic(events, jitter_ns=3_000), monkeypatch)
    v = _ring.view(ctx)
    assert abs(v.offset["offset_ns"] - OFFSET) <= 3_000
    assert 0 < v.offset["residual_ns"] <= 3_000
    assert read(ctx, spans=["eng.tick"]) is not None
    ctx = context(events, synthetic(events, jitter_ns=400_000), monkeypatch)
    assert _ring.view(ctx).offset["residual_ns"] > _ring.MAX_RESIDUAL_NS
    assert read(ctx, spans=["eng.tick"]) is None


def test_records_lost_inside_the_window_give_none(events, monkeypatch):
    """A ring that lapped inside the window holds a torn sample: its
    percentile is a wrong number, so there is none."""
    rings = synthetic(events, capacity=2)
    assert any(r.lost for _, r in rings)
    ctx = context(events, rings, monkeypatch)
    v = _ring.view(ctx)
    assert not v.ok and v.lost_inside >= 1
    pct, idle = Spec().reader("ring_percentile"), Spec().reader(
        "ring_idle_pct")
    assert pct(ctx, quantity="between_quanta", q=50, unit_ns=1e3) is None
    assert idle(ctx, spans=["quantum"]) is None
    # The same rings read whole give numbers.
    ctx = context(events, synthetic(events), monkeypatch)
    assert pct(ctx, quantity="between_quanta", q=50, unit_ns=1e3) > 0
    assert pct(ctx, quantity="step_dispatch", q=50, unit_ns=1e3,
               job="train") > 0
    assert pct(ctx, quantity="step_dispatch", q=50, unit_ns=1e3,
               job="nobody") is None


def test_a_program_without_the_records_gives_none(events, monkeypatch):
    """The parent of the PR that added the rings' accessor: every new
    reader returns None and raises nothing."""
    ctx = context(events, synthetic(events), monkeypatch)
    monkeypatch.delattr(pt, "live_rings")
    assert _ring.view(ctx) is None
    assert Spec().reader("ring_percentile")(
        ctx, quantity="decode_post", q=50, unit_ns=1e6) is None
    assert Spec().reader("ring_idle_pct")(ctx, spans=["eng.tick"]) is None


def test_tick_host_adds_the_pieces_and_leaves_the_waits():
    """Per tick: admissions without their prefill waits, the tick's own
    key split (an admission's is inside its duration), pre and post."""
    eng = TraceBuffer(64)
    # tick 5: one admission of 900 (its key split 100, its prefill wait
    # 500), the decode's key split 70, pre 200, sync 4000, post 30.
    eng.emit(1000, Ev.ENG_KEYSPLIT, 5, 100)
    eng.emit(1200, Ev.ENG_PREFILL, 5, 0, 0, 50, 500, 0)
    eng.emit(990, Ev.ENG_ADMIT, 5, 0, 0, 12, 7777, 900)
    eng.emit(1900, Ev.ENG_KEYSPLIT, 5, 70)
    eng.emit(1970, Ev.ENG_DECODE, 5, 200, 4000, 30)
    eng.emit(980, Ev.ENG_TICK, 5300, 5, 1, 1, 0, 0)
    # tick 6: nothing admitted; tick 7 ran no decode and is no tick.
    eng.emit(7000, Ev.ENG_KEYSPLIT, 6, 60)
    eng.emit(7060, Ev.ENG_DECODE, 6, 150, 4000, 20)
    eng.emit(6990, Ev.ENG_TICK, 4300, 6, 1, 0, 0, 0)
    eng.emit(12000, Ev.ENG_TICK, 10, 7, 0, 0, 0, 0)
    v = _ring.View(pt, 0, 10**9, rings=[("engine", eng)])
    assert _ring.quantity(v, "tick_host_inside") == [
        900 - 500 + 70 + 200 + 30, 60 + 150 + 20]
    assert _ring.quantity(v, "decode_pre_no_admission") == [150]
    assert _ring.quantity(v, "decode_post") == [30, 20]
    assert _ring.quantity(v, "admit_wait") == []   # submitted before t0
