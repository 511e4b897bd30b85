"""The trace reduction: on a hand-made trace with known answers, and on
a small trace recorded on the chip (``benchmarks/data``)."""
import json
import os
import shutil

import pytest

from benchmarks.harness import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur}


# Two programs. train: ops at [100,140) and [150,200) inside a module
# [100,200). decode: one op [300,330) inside [300,330). Host: a
# serve_step span [250,340) holding a decode span [260,300).
HAND = [
    ev(DEV, "XLA Modules", "jit_train_step(1)", 100, 100),
    ev(DEV, "XLA Ops", "fusion.1", 100, 40),
    ev(DEV, "XLA Ops", "fusion.2", 150, 50),
    ev(DEV, "XLA Modules", "jit__decode(2)", 300, 30),
    ev(DEV, "XLA Ops", "fusion.9", 300, 30),
    ev(HOST, "python", "bench.train_step", 90, 120),
    ev(HOST, "python", "bench.serve_step", 250, 90),
    ev(HOST, "python", "bench.decode", 260, 40),
]


def test_merge_and_busy():
    assert trace.merge([(5, 9), (1, 3), (2, 4), (9, 10)]) == \
        [(1, 4), (5, 10)]
    assert trace.busy_seconds(HAND) == pytest.approx(120e-9)


def test_programs_and_their_device_time():
    progs = trace.programs(HAND)
    assert [(p["name"], p["dur"], p["busy"]) for p in progs] == \
        [("jit_train_step(1)", 100, 90), ("jit__decode(2)", 30, 30)]
    assert trace.program_times(progs, "jit__decode") == [30]
    assert trace.program_times(progs, "jit_train") == [100]


def test_flatten_keeps_the_innermost_span():
    assert trace.flatten(trace.annotations(HAND)) == [
        (90, 210, "bench.train_step"), (250, 260, "bench.serve_step"),
        (260, 300, "bench.decode"), (300, 340, "bench.serve_step")]


def test_idle_gaps_by_annotation():
    # window [90, 340); busy [100,140) [150,200) [300,330).
    # idle: [90,100) train, [140,150) train, [200,210) train,
    # [210,250) nothing, [250,260) serve, [260,300) decode, [330,340) serve
    assert trace.window_of(HAND) == (90, 340)
    assert trace.idle_by_annotation(HAND) == {
        "bench.train_step": 30, trace.NO_ANNOTATION: 40,
        "bench.serve_step": 20, "bench.decode": 40}
    assert sum(trace.idle_by_annotation(HAND).values()) + 120 == 250


def test_top_ops_carry_their_program():
    assert trace.top_ops(HAND, trace.programs(HAND), 2) == [["jit_train_step/fusion.2", 50e-9],
                                      ["jit_train_step/fusion.1", 40e-9]]


def test_programs_without_a_modules_line():
    cpu = [dict(ev(HOST, "t", "dot.1", 10, 5), module="jit_f", run=1),
           dict(ev(HOST, "t", "add.2", 20, 5), module="jit_f", run=1),
           dict(ev(HOST, "t", "dot.1", 40, 5), module="jit_f", run=2)]
    assert [(p["name"], p["start"], p["dur"], p["busy"])
            for p in trace.programs(cpu)] == \
        [("jit_f", 10, 15, 10), ("jit_f", 40, 5, 5)]


DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data")


def test_device_ops_keep_their_scope(tmp_path):
    """A profile recorded on the v5e of one jitted ``_decode`` whose
    layer scan holds ``jax.named_scope("attn")`` and whose tail holds
    ``"head"``: each device op comes back with the scope path from its
    event metadata, and the names ``breakdown.device_ops`` prints stay
    as they were."""
    log = tmp_path / "plugins" / "profile" / "once"
    log.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "named-scopes.xplane.pb"), log)
    events = trace.load_xplane(str(tmp_path))
    ops = [e for e in events if e["line"] == "XLA Ops"]
    by_scope = {}
    for e in ops:
        by_scope.setdefault(e.get("scope"), set()).add(e["name"])
    assert by_scope["jit(_decode)/head/dot_general:"] == {
        "fusion.18 bf16[]"}
    assert by_scope["jit(_decode)/while/body/closed_call/attn/"
                    "dot_general:"] == {
        "fusion.33 (bf16[256], bf16[256,1024])"}
    # 3 runs x 4 layers of the scanned matmul; copies and the loop
    # itself carry no scope
    assert sum(1 for e in ops if "/attn/dot_general" in e.get("scope", "")
               ) == 12
    assert None in by_scope
    assert trace.top_ops(events, trace.programs(events), 1)[0][0] == \
        "jit__decode/fusion.33 (bf16[256], bf16[256,1024])"


RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "colo-recorded.trace.json")


def test_recorded_chip_trace():
    """A slice of a traced colo run on the v5e, with the answers worked
    out once by hand from the file (see its ``expect`` block)."""
    with open(RECORDED) as f:
        doc = json.load(f)
    events, want = trace.unpack(doc), doc["expect"]
    assert len(events) > 5000
    assert trace.window_of(events) == tuple(want["window"])
    assert trace.busy_seconds(events) * 1e9 == pytest.approx(want["busy_ns"])
    progs = trace.programs(events)
    for match, times in want["program_times"].items():
        assert trace.program_times(progs, match) == times
    assert trace.idle_by_annotation(events) == want["idle_by_annotation"]
