"""The records of an admission that rides in the pipeline, held to the
benchmark's own readers (``benchmarks/readers``; nothing under
``benchmarks/`` is edited for it).

An admission's prompt forward is enqueued behind the decode in flight
and read once the call's decode is enqueued behind it
(docs/SERVING.md "The pipelined tick"), so its ``ENG_PREFILL.sync``,
its ``ENG_ADMIT.dur`` and the call's ``ENG_DECODE.sync`` end at one
stamp. Three readers stand on those records:

- ``_ring._tick_host``: a tick's host time is ``sum(ENG_ADMIT.dur) -
  sum(ENG_PREFILL.sync) + last key split + pre + post``. It must not be
  negative, and the difference must be the admission's host work;
- ``_ring.program_spans``: the spans laid over the device trace must be
  properly nested (``harness/trace.flatten`` needs that);
- ``bucket_prefill_ms.forwards``: a device execution of the prefill is
  joined to the ``ENG_PREFILL`` record whose dispatch and wait hold its
  midpoint. Every forward must get its own record, rung and prompt.

A small dense engine on the CPU is driven through ticks with no, one
and two admissions, pipelined and settled; the device executions are
synthetic, laid in dispatch order as early and as late as the host's
stamps allow.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import trace
from benchmarks.readers import _ring, bucket_prefill_ms
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.transformer import TransformerConfig, init_params
from pbs_tpu.obs import trace as pt
from pbs_tpu.obs.trace import Ev, TraceBuffer

BUCKET, MAX_LEN, SLOTS = 512, 640, 3
CFG = TransformerConfig(vocab=128, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=64, max_seq=MAX_LEN,
                        dtype=jnp.float32)
#: (call it is submitted before, prompt length, budget): an admission
#: into an idle engine, two in one call behind a decode, one alone
#: behind a decode, one with a budget of one, and calls that admit none.
SCRIPT = ((0, 300, 14), (2, 100, 3), (2, 400, 4), (7, 200, 3), (9, 500, 1),
          (9, 30, 2))
DEVICE = "/device:TPU:0"


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["pipelined", "settled"])
def served(request, params):
    """The engine after the script, its ring's view (the whole run as
    the window, the two clocks one) and each request's prompt length."""
    eng = ContinuousBatcher(CFG, params, n_slots=SLOTS,
                            prompt_bucket=BUCKET, max_len=MAX_LEN)
    assert eng.rungs == (256, 512)
    ring = TraceBuffer(1 << 12)
    eng.bind_trace(ring)
    tick = eng.step if request.param == "pipelined" else eng.step_settled
    rng = np.random.default_rng(5)
    script, plens, calls = list(SCRIPT), {}, 0
    while script or eng.has_work():
        while script and script[0][0] <= calls:
            _, plen, budget = script.pop(0)
            plens[eng.submit(rng.integers(1, 120, plen), budget)] = plen
        tick()
        calls += 1
        assert calls < 100
    view = _ring.View(pt, 0, 1 << 62, rings=[("engine", ring)])
    view.offset = {"offset_ns": 0.0, "residual_ns": 0.0, "pairs": 0,
                   "pair": "", "others": {}}
    assert _ring.trusted(view)
    return eng, view, plens


def rows(view, event) -> list[list[int]]:
    return [r.tolist() for r in view.rows(event)[0]]


def test_the_script_has_ticks_with_no_one_and_two_admissions(served):
    eng, view, plens = served
    admitted = [r[5] for r in rows(view, Ev.ENG_TICK)]
    assert {0, 1, 2} <= set(admitted) and sum(admitted) == len(SCRIPT)
    assert eng.stats()["prefill_count"] == len(plens) == len(SCRIPT)


def test_a_ticks_host_time_is_never_negative_and_is_the_host_work(served):
    """``_tick_host`` against the same sum made from the stamps: an
    admission adds the time from its start until its forward's dispatch
    returned, and (one that was read before the call's last dispatch)
    its booking; never its wait."""
    _eng, view, _ = served
    decodes = rows(view, Ev.ENG_DECODE)
    got = dict(zip((r[2] for r in decodes),
                   _ring._tick_host(Ev, view.whole["engine"])))
    assert len(got) == len(decodes) and min(got.values()) >= 0
    assert got == dict(zip(
        (r[2] for r in decodes),
        _ring.quantity(view, "tick_host_inside")))
    admits = {r[3]: r for r in rows(view, Ev.ENG_ADMIT)}
    prefills = rows(view, Ev.ENG_PREFILL)
    splits: dict[int, list[int]] = {}
    for r in rows(view, Ev.ENG_KEYSPLIT):
        splits.setdefault(r[2], []).append(r[3])
    for _ts, _ev, tick, pre, _sync, post, _flag, _ in decodes:
        work = 0
        for p in (p for p in prefills if p[2] == tick):
            a = admits[p[3]]
            # ENG_ADMIT.dur - ENG_PREFILL.sync: both end at one stamp
            assert a[0] + a[7] == p[0] + p[5] + p[6]
            dispatched = p[0] + p[5] - a[0]
            assert a[7] - p[6] == dispatched > 0
            work += dispatched
        assert got[tick] == work + splits[tick][-1] + pre + post
    # a tick with no admission is its key split, pre and post alone
    quiet = [r[3] for r in rows(view, Ev.ENG_TICK) if r[5] == 0]
    assert any(t in got for t in quiet)


def test_the_spans_are_properly_nested(served):
    """``program_spans`` as ``flatten`` needs them: of two spans that
    overlap one holds the other. The admission that rides is an
    ``eng.admit`` that holds its forward's ``eng.sync``, which holds the
    decode's key split, ``eng.pre`` and ``eng.sync``; ``eng.post``
    starts where all three end."""
    _eng, view, _ = served
    spans = _ring.program_spans(view)
    assert {s["name"] for s in spans} == {
        "eng.tick", "eng.admit", "eng.sync", "eng.keysplit", "eng.pre",
        "eng.post"}
    stack: list[tuple[int, str]] = []
    depth = 0
    for sp in sorted(spans, key=lambda e: (e["start"], -e["dur"])):
        while stack and stack[-1][0] <= sp["start"]:
            stack.pop()
        end = sp["start"] + sp["dur"]
        if stack:
            assert end <= stack[-1][0], (sp, stack[-1])
        else:
            assert sp["name"] == "eng.tick"
        stack.append((end, sp["name"]))
        depth = max(depth, len(stack))
    # tick > admit > the forward's wait > the decode's wait
    assert depth == 4
    # and flatten covers every tick once, with nothing outside a tick
    flat = trace.flatten(spans)
    assert all(a[1] <= b[0] for a, b in zip(flat, flat[1:]))
    ticks = sum(s["dur"] for s in spans if s["name"] == "eng.tick")
    assert sum(e - s for s, e, _ in flat) == ticks
    # a wait is never billed to the host: idle inside a forward's or a
    # decode's wait is ``eng.sync``'s, where it is the innermost span
    waited = sum(e - s for s, e, name in flat if name == "eng.sync")
    assert waited > 0


def executions(view, late: bool) -> list[dict]:
    """One synthetic device execution a dispatched program, in dispatch
    order on one device: as early as it can have run (from the stamp at
    which its dispatch returned) or, a prompt forward, as late (ending
    just before the stamp at which the host had its first token). A
    forward's one op, under ``attn.full``, is as long as 100 ns + the
    request's id, which is how the test knows it again."""
    def ev(line, name, start, dur, scope=None):
        e = {"plane": DEVICE, "line": line, "name": name,
             "start": int(start), "dur": int(dur)}
        if scope:
            e["scope"] = scope
        return e

    calls = [(r[0] + r[3], None, "jit__decode(1)", 0)
             for r in rows(view, Ev.ENG_DECODE)]
    calls += [(r[0] + r[5], r[0] + r[5] + r[6], f"jit__prefill({r[7]})",
               100 + r[3]) for r in rows(view, Ev.ENG_PREFILL)]
    events, free = [], 0
    for dispatched, read, name, op in sorted(calls):
        start = max(dispatched, free)
        if late and read is not None:
            start = max(start, read - 2_000)
        assert read is None or start + 1_000 <= read
        free = start + 1_000
        events.append(ev("XLA Modules", name, start, 1_000))
        if op:
            events.append(ev("XLA Ops", "fusion.1", start, op,
                             "jit(_prefill)/jit(main)/attn.full/dot_general"))
    return events


@pytest.mark.parametrize("late", [False, True], ids=["early", "late"])
def test_every_forward_joins_its_own_record(served, late, capsys):
    """``bucket_prefill_ms.forwards`` over the synthetic executions, once
    a rung: each forward at that rung is found, under its own request's
    prompt length."""
    _eng, view, plens = served
    events = executions(view, late)
    found = {}
    for rung in (256, 512):
        ctx = types.SimpleNamespace(
            events=events, programs=trace.programs(events),
            config={"serve": {"prompt_bucket": rung}}, _ring_view=view)
        for ns, tokens in bucket_prefill_ms.forwards(
                ctx, "jit__prefill", ["attn.full"]):
            assert ns - 100 not in found
            found[ns - 100] = (rung, tokens)
    capsys.readouterr()  # the readers' log lines
    assert found == {rid: (256 if plen <= 256 else 512, plen)
                     for rid, plen in plens.items()}
