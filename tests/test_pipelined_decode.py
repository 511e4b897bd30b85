"""The one-deep pipelined decode of the slot engine: ``step()`` enqueues
tick n+1 before it reads tick n, and books tick n while the device runs
tick n+1 (docs/SERVING.md "The pipelined tick").

What is held here: the pipelined engine serves the tokens the settled
engine serves (``step_settled()``: every tick read before it returns),
for a dense, a sparse, two recurrent and a drafting layer stack, greedy
and sampled, with budgets and an ``eos_id`` that end requests mid-run
and requests that arrive mid-stream; the lane mask is decided ahead
from the budgets; an admission rides in the pipeline (its prompt forward
enqueued behind the decode in flight, its first token read once the
decode behind it is enqueued too); ``has_work()`` / ``settle()`` see the
tick in flight; the scheduler's wrapper and the disaggregated backend
leave nothing in flight; and the ring's records keep what the
benchmark's readers assume of them (``tests/test_admission_records.py``
holds the records of an admission to the readers' own code). Toy sizes,
CPU, float32.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import Spec
from benchmarks.run import overlay
from pbs_tpu.gateway import Gateway, TenantQuota
from pbs_tpu.gateway.backends import BatcherBackend
from pbs_tpu.models import (
    ContinuousBatcher, SpeculativeBatcher, TransformerConfig, init_params,
    make_continuous_serve_step)
from pbs_tpu.models.serving import _LANE_CARRY, _LANE_OFF
from pbs_tpu.obs.trace import Ev

SPEC = Spec()
SLOTS, BUCKET, MAX_LEN = 3, 12, 40
TINY = dict(vocab=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq=MAX_LEN, dtype=jnp.float32)
#: family -> (configuration, its family in the benchmark's spec)
PLANNED = {"moe": ("laguna-s-2.1", "moe-mixed-gqa"),
           "kda": ("solar-open2-250b", "moe-kda-gqa"),
           "mamba": ("ai21-jamba2-3b", "dense-mamba-mqa"),
           "mtp": ("deepseek-v3", "moe-mla-mtp")}
#: the families that sample; a plan that drafts for itself is greedy
SAMPLED = ["dense", "moe", "kda", "mamba"]
FAMILIES = ["dense", *PLANNED]


@functools.lru_cache(maxsize=None)
def model(family: str):
    """A tiny configuration of the family and its seeded weights: the
    dense scan, or the rehearsal preset of a planned stack's
    configuration file (window and full attention over experts; KDA
    state beside a softmax layer, experts too; Mamba state beside
    attention; latent attention over experts with a drafting block,
    whose decode verifies two positions a lane)."""
    if family == "dense":
        cfg = TransformerConfig(**TINY)
        return cfg, init_params(cfg, jax.random.PRNGKey(0))
    name, fam = PLANNED[family]
    full, fam = SPEC.config(name), SPEC.family(fam)
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["compute_dtype"] = c["serve"]["weights_dtype"] = "float32"
    n = c["serve"]["num_hidden_layers"]
    cfg = fam.program_config(c, n, MAX_LEN)
    params = jax.jit(lambda s: fam.reference.init_tree(
        c, s, n, jnp.float32))(fam.reference.seed_word(11))
    return cfg, params


@functools.lru_cache(maxsize=None)
def engine(family: str, temperature: float = 0.0) -> ContinuousBatcher:
    """One engine a (family, temperature), reused by every run: the
    retire condition is the host's (``eos_id`` is set by the run), and
    a run starts from free slots and the seed's key (``fresh``)."""
    cfg, params = model(family)
    return ContinuousBatcher(cfg, params, n_slots=SLOTS,
                             prompt_bucket=BUCKET, max_len=MAX_LEN,
                             temperature=temperature, seed=5)


def fresh(eng: ContinuousBatcher, eos_id=None) -> ContinuousBatcher:
    assert not eng.has_work()
    eng.eos_id = eos_id
    eng._key = jax.random.PRNGKey(5)
    return eng


def prompts(vocab: int, n: int) -> list[list[int]]:
    rng = np.random.default_rng(3)
    return [rng.integers(1, vocab, int(rng.integers(2, BUCKET + 1))).tolist()
            for _ in range(n)]


def run(eng, script, settled: bool):
    """Drive ``eng`` through ``script`` ([(step, prompt, max_new)]: a
    request is submitted just before the ``step``-th call) with the
    pipelined ``step()`` or with ``step_settled()``. Returns the
    completions in submission order and the number of calls made."""
    tick = eng.step_settled if settled else eng.step
    script = sorted(script, key=lambda r: r[0])
    rids, done, calls = [], {}, 0
    while script or eng.has_work():
        while script and script[0][0] <= calls:
            _, prompt, max_new = script.pop(0)
            rids.append(eng.submit(prompt, max_new))
        for comp in tick():
            done[comp.request_id] = comp
        calls += 1
        assert calls < 200
    return [done[r] for r in rids], calls


#: Six requests at once into three slots, and two that arrive
#: mid-stream (one of them prefill-only): every slot is reused, budgets
#: from prefill-only (1) to the longest, two of them equal; admissions
#: land alone and two to a call, onto an idle device and behind a decode.
BUDGETS = (6, 1, 9, 4, 12, 6, 5, 1)
ARRIVALS = (0, 0, 0, 0, 0, 0, 3, 5)


def crowd(vocab: int):
    return list(zip(ARRIVALS, prompts(vocab, len(BUDGETS)), BUDGETS))


@pytest.mark.parametrize("family", FAMILIES)
def test_greedy_tokens_equal_the_settled_engines(family):
    """Admissions into reused slots, budget retirements and an EOS that
    fires mid-run: request for request the tokens of the settled
    engine. (Greedy, a request's tokens do not depend on the tick it is
    admitted at, and the pipelined engine reuses a slot one tick later.)
    """
    eng = engine(family)
    script = crowd(eng.cfg.vocab)
    plain, _ = run(fresh(eng), script, settled=True)
    assert [len(c.tokens) for c in plain] == list(BUDGETS)
    # An EOS that cuts the longest request short, and whatever else
    # emits it: the token the settled engine gave it fifth.
    eos = plain[4].tokens[4]
    want, n_settled = run(fresh(eng, eos), script, settled=True)
    assert len(want[4].tokens) <= 5 < BUDGETS[4]
    assert want[4].tokens[-1] == eos
    before = eng.stats()["admissions_overlapped"]
    got, n_piped = run(fresh(eng, eos), script, settled=False)
    assert [c.tokens for c in got] == [c.tokens for c in want]
    assert [c.prompt_len for c in got] == [c.prompt_len for c in want]
    assert n_piped > n_settled  # the booking is one call behind
    assert eng._inflight is None and not eng.active.any()
    # and prompts were ingested behind a decode in flight
    assert eng.stats()["admissions_overlapped"] > before


@pytest.mark.parametrize("family", SAMPLED)
def test_sampled_tokens_equal_the_settled_engines(family):
    """temperature > 0, a fixed seed: the same draws. Sampled tokens
    depend on the key a dispatch is handed and on the lane they are
    drawn in, so on the order of splits and on the slot: they are the
    settled engine's wherever both admit a request in the same call
    into the same slot, which this script arranges (no request waits
    for a slot: ``steps_waited`` 0; the pipelined engine frees a slot a
    call later, so a slot is asked for two calls after its tenant's
    last token; a lane is busy throughout, so no tick is skipped where
    only an EOS the host has not seen would keep one). The third and
    fourth requests arrive mid-stream in one call: one forward is read
    before the next is dispatched, the last rides behind the decode."""
    eng = engine(family, 0.8)
    p = prompts(eng.cfg.vocab, 6)
    script = [(0, p[0], 24), (0, p[1], 2), (2, p[2], 7), (2, p[5], 3),
              (6, p[3], 1), (8, p[4], 9)]
    plain, _ = run(fresh(eng), script, settled=True)
    # ends the third request mid-run, and never the first (lane 0 draws
    # from the same keys with and without it)
    eos = next(t for t in plain[2].tokens[1:6] if t not in plain[0].tokens)
    want, _ = run(fresh(eng, eos), script, settled=True)
    assert 2 <= len(want[2].tokens) <= 6 < 7
    assert want[2].tokens[-1] == eos and len(want[0].tokens) == 24
    before = eng.stats()["admissions_overlapped"]
    got, _ = run(fresh(eng, eos), script, settled=False)
    assert [c.steps_waited for c in want + got] == [0] * 12
    assert [c.tokens for c in got] == [c.tokens for c in want]
    # behind a decode: the first of the two at call 2 (the second is
    # dispatched once that decode has been read) and the two after
    assert eng.stats()["admissions_overlapped"] - before == 3
    # and they are draws: the greedy engine says something else
    greedy, _ = run(fresh(engine(family), eos), script, settled=False)
    assert [c.tokens for c in greedy] != [c.tokens for c in got]


def dense(n_slots=2, **kw) -> ContinuousBatcher:
    cfg, params = model("dense")
    return ContinuousBatcher(cfg, params, n_slots=n_slots,
                             prompt_bucket=BUCKET, max_len=MAX_LEN, **kw)


def records(eng, event) -> list[list[int]]:
    recs = eng.trace.peek(eng.trace.capacity).astype(np.int64)
    return [r.tolist() for r in recs if r[1] == int(event)]


# -- (b) the tick in flight is work, and settle() books it --------------------


def test_has_work_and_settle_see_the_tick_in_flight():
    eng = dense()
    rid = eng.submit([1, 2, 3], 2)
    assert eng.step() == []  # first token booked, the second enqueued
    assert eng._inflight is not None and eng.has_work()
    assert eng.slot_tokens[0] == eng.slot_tokens[0][:1] and eng.active[0]
    assert eng.tokens_emitted == 1
    done = eng.settle()
    assert [c.request_id for c in done] == [rid]
    assert len(done[0].tokens) == 2 and eng.tokens_emitted == 2
    assert eng._inflight is None and not eng.has_work()
    assert eng.settle() == []  # nothing in flight: nothing to do


def test_a_tick_whose_lanes_are_gone_is_still_work_until_read():
    """An EOS the host sees a tick late: the lane has run one token
    more, in flight when its request retires. ``has_work()`` holds
    until that tick is read, and its token is dropped."""
    (whole,), _ = run(dense(n_slots=1), [(0, [4, 5, 6], 8)], settled=True)
    eos = whole.tokens[2]  # the third token ends it, if none before
    cut = whole.tokens[:whole.tokens.index(eos) + 1]
    assert len(whole.tokens) == 8 and 2 <= len(cut) <= 3
    eng = dense(n_slots=1, eos_id=eos)
    rid = eng.submit([4, 5, 6], 8)
    done = []
    while not done:
        done = eng.step()
    assert [c.request_id for c in done] == [rid]
    assert done[0].tokens == cut
    # The call that booked the EOS had already enqueued one more tick.
    assert eng._inflight is not None and not eng.active.any()
    assert eng.has_work()
    assert eng.step() == [] and not eng.has_work()
    assert eng.tokens_emitted == len(cut)


# -- (c) the mask is the host's, decided ahead --------------------------------


def spied(eng) -> list:
    """Log what ``eng`` enqueues and reads, in order: ``("prefill",
    slot)``, ``("decode", lanes)`` and ``("read", kinds)`` (the kinds of
    what one wait covered: ``_InFlight`` / ``_Admission``)."""
    log = []
    prefill, decode, land = eng._prefill_fn, eng._decode_fn, eng._land

    def spy_prefill(params, cache, prev_tok, slot, *a):
        log.append(("prefill", slot))
        return prefill(params, cache, prev_tok, slot, *a)

    def spy_decode(params, cache, prev_tok, lanes, key):
        log.append(("decode", np.asarray(lanes).tolist()))
        return decode(params, cache, prev_tok, lanes, key)

    def spy_land(unread, done):
        if unread:
            log.append(("read", [type(u).__name__ for u in unread]))
        return land(unread, done)

    eng._prefill_fn, eng._decode_fn, eng._land = (
        spy_prefill, spy_decode, spy_land)
    return log


def test_a_lane_whose_budget_ends_in_flight_is_off_in_the_next_dispatch():
    eng = dense()
    seen = []  # (lanes handed to the program, active, remaining) a dispatch
    decode = eng._decode_fn

    def spy(params, cache, prev_tok, lanes, key):
        seen.append((np.asarray(lanes).tolist(), eng.active.tolist(),
                     eng.slot_remaining.tolist(), eng.last_tok.tolist()))
        return decode(params, cache, prev_tok, lanes, key)

    eng._decode_fn = spy
    eng.submit([1, 2, 3], 3)  # slot 0: first token + two decodes
    eng.submit([4, 5], 6)     # slot 1: first token + five
    done = []
    while eng.has_work():
        done += eng.step()
    lanes = [s[0] for s in seen]
    # Dispatch 0 follows the admissions: the first one's token was read
    # before the second was dispatched, the second's is on the device.
    assert lanes[0] == [seen[0][3][0], _LANE_CARRY] and lanes[0][0] >= 0
    assert lanes[1] == [_LANE_CARRY, _LANE_CARRY]
    # Dispatch 2: slot 0's last token is in flight (dispatch 1). The
    # lane is still active on the host, one token from its budget, and
    # off in the program: nothing runs that would be thrown away.
    assert lanes[2] == [_LANE_OFF, _LANE_CARRY]
    assert seen[2][1] == [True, True] and seen[2][2][0] == 1
    assert lanes[3:] == [[_LANE_OFF, _LANE_CARRY]] * 2 and len(lanes) == 5
    assert sorted(len(c.tokens) for c in done) == [3, 6]
    st = eng.stats()
    # every decode went behind something unread: the first behind the
    # second admission's forward, the others behind the decode before
    assert (st["ticks_overlapped"], st["ticks_settled"]) == (5, 0)
    flags = [r[6] for r in records(eng, Ev.ENG_DECODE)]
    assert flags == [1, 1, 1, 1, 1]
    assert st["tokens_emitted"] == 9 and st["completed"] == 2


def test_an_admission_rides_behind_the_decode_in_flight():
    """A request waiting and a slot free: the prompt forward is enqueued
    behind the decode in flight and this call's decode behind the
    forward, before anything is read; one wait then covers the older
    decode and the first token, and both are booked when the call
    returns. The decode counts as overlapped."""
    eng = dense()
    eng.submit([1, 2, 3], 9)
    eng.step(), eng.step()
    st = eng.stats()
    assert (st["ticks_overlapped"], st["ticks_settled"]) == (2, 0)
    assert st["admissions_overlapped"] == 0  # it found the device idle
    before = len(eng.slot_tokens[0])
    log = spied(eng)
    rid = eng.submit([7, 8], 4)
    assert eng.step() == []
    assert log == [("prefill", 1), ("decode", [_LANE_CARRY, _LANE_CARRY]),
                   ("read", ["_InFlight", "_Admission"])]
    # the older decode's token and the new lane's first are booked
    assert len(eng.slot_tokens[0]) == before + 1
    assert eng.slot_req[1] == rid and len(eng.slot_tokens[1]) == 1
    assert eng.last_tok[1] == eng.slot_tokens[1][0]
    assert eng._inflight is not None
    assert [lane for lane, _ in eng._inflight.lanes] == [0, 1]
    st = eng.stats()
    assert (st["ticks_overlapped"], st["ticks_settled"]) == (3, 0)
    assert st["admissions_overlapped"] == 1
    assert records(eng, Ev.ENG_DECODE)[-1][6] == 1
    # one stamp closes the three waits of the call
    (*_, admit), (*_, prefill), (*_, dec) = (
        records(eng, ev) for ev in (Ev.ENG_ADMIT, Ev.ENG_PREFILL,
                                    Ev.ENG_DECODE))
    assert admit[0] + admit[7] == prefill[0] + prefill[5] + prefill[6] \
        == dec[0] + dec[3] + dec[4]
    assert admit[2] == prefill[2] == dec[2] == 2  # the tick
    # served as the settled engine serves them
    got = {c.request_id: c.tokens for c in eng.settle()}
    while eng.has_work():
        got.update({c.request_id: c.tokens for c in eng.step()})
    want, _ = run(dense(), [(0, [1, 2, 3], 9), (2, [7, 8], 4)], settled=True)
    assert [got[0], got[1]] == [c.tokens for c in want]


def test_an_admission_into_an_idle_engine_enqueues_its_decode_behind_it():
    """Nothing in flight: the forward goes onto an idle device, the
    decode behind it before the first token is read."""
    eng = dense()
    log = spied(eng)
    eng.submit([1, 2, 3], 5)
    assert eng.step() == []
    assert log == [("prefill", 0), ("decode", [_LANE_CARRY, _LANE_OFF]),
                   ("read", ["_Admission"])]
    assert len(eng.slot_tokens[0]) == 1 and eng._inflight is not None
    st = eng.stats()
    assert st["admissions_overlapped"] == 0 and st["ticks_overlapped"] == 1
    (done,) = [c for _ in range(5) for c in eng.step()]
    (want,), _ = run(dense(), [(0, [1, 2, 3], 5)], settled=True)
    assert done.tokens == want.tokens and not eng.has_work()


def test_two_admissions_in_one_tick_are_read_one_after_the_other():
    """Each forward must lie inside its own ``ENG_PREFILL`` record
    (``bucket_prefill_ms.forwards``): the first of a call's admissions
    is read, with the decode it rode behind, before the second is
    dispatched; only the last is unread when the decode is enqueued."""
    eng = dense(n_slots=3)
    eng.submit([1, 2, 3], 9)
    eng.step(), eng.step()
    log = spied(eng)
    eng.submit([7, 8], 4), eng.submit([9], 3)
    assert eng.step() == []
    host = eng.slot_tokens[1][0]
    assert log == [("prefill", 1), ("read", ["_InFlight", "_Admission"]),
                   ("prefill", 2),
                   ("decode", [eng.slot_tokens[0][-1], host, _LANE_CARRY]),
                   ("read", ["_Admission"])]
    assert [len(t) for t in eng.slot_tokens] == [3, 1, 1]
    assert eng.stats()["admissions_overlapped"] == 1
    pre = records(eng, Ev.ENG_PREFILL)[-2:]
    # the first forward's record has ended when the second's starts
    assert pre[0][0] + pre[0][5] + pre[0][6] <= pre[1][0]
    got = {c.request_id: c.tokens for c in eng.settle()}
    while eng.has_work():
        got.update({c.request_id: c.tokens for c in eng.step()})
    want, _ = run(dense(n_slots=3), [(0, [1, 2, 3], 9), (2, [7, 8], 4),
                                     (2, [9], 3)], settled=True)
    assert [got[i] for i in range(3)] == [c.tokens for c in want]


@pytest.mark.parametrize("settled", [False, True])
def test_a_budget_of_one_runs_no_decode(settled):
    """``max_new == 1``: the lane is off in the decode enqueued behind
    its forward (the host knows the budget without the token), and the
    request retires in the call that admitted it."""
    eng = dense()
    eng.submit([1, 2, 3], 9)
    eng.step(), eng.step()
    log = spied(eng)
    rid = eng.submit([7, 8], 1)
    tick = eng.step_settled if settled else eng.step
    (done,) = tick()
    assert done.request_id == rid and len(done.tokens) == 1
    assert [e for e in log if e[0] == "decode"] == [
        ("decode", [_LANE_CARRY, _LANE_OFF])]
    assert not eng.active[1] and eng.slot_req[1] is None
    # alone in the engine it runs no decode at all
    lone = dense()
    log = spied(lone)
    lone.submit([7, 8], 1)
    (only,) = tick.__func__(lone)
    assert only.tokens == done.tokens and not lone.has_work()
    assert log == [("prefill", 0), ("read", ["_Admission"])]
    assert lone.stats()["steps"] == 1
    eng.settle()


def test_an_eos_as_first_token_costs_one_dropped_token():
    """The host does not know the first token when it enqueues the
    decode behind the forward: the lane runs one token, which is dropped
    when it is booked (as for an EOS in flight), and the slot is free
    for the next call's admission."""
    (whole,), _ = run(dense(n_slots=1), [(0, [4, 5, 6], 8)], settled=True)
    eos = whole.tokens[0]
    eng = dense(eos_id=eos)
    eng.submit([1, 2, 3], 12)
    eng.step(), eng.step()
    log = spied(eng)
    rid = eng.submit([4, 5, 6], 8)
    (done,) = eng.step()
    assert (done.request_id, done.tokens) == (rid, [eos])
    assert log[1] == ("decode", [_LANE_CARRY, _LANE_CARRY])  # it ran
    assert (1, rid) in eng._inflight.lanes and not eng.active[1]
    emitted = eng.tokens_emitted
    nxt = eng.submit([4, 5, 6], 8)  # the slot is taken again at once
    (again,) = eng.step()
    assert (again.request_id, again.tokens) == (nxt, [eos])
    assert log[3] == ("prefill", 1)
    # the call booked lane 0's token and the first token, not lane 1's
    assert eng.tokens_emitted == emitted + 2
    eng.eos_id = None
    eng.settle()


def test_a_settled_tick_with_an_admission_leaves_nothing_in_flight():
    """``step_settled()``: the forward, the decode behind it, and one
    wait for both (and for a decode it found in flight); every token
    booked and nothing on the device when it returns."""
    eng = dense()
    eng.submit([1, 2, 3], 9)
    eng.step(), eng.step()  # leaves a decode in flight
    log = spied(eng)
    eng.submit([7, 8], 4)
    assert eng.step_settled() == []
    assert log == [("prefill", 1), ("decode", [_LANE_CARRY, _LANE_CARRY]),
                   ("read", ["_InFlight", "_Admission", "_InFlight"])]
    assert eng._inflight is None
    assert [len(t) for t in eng.slot_tokens] == [4, 2]
    assert eng.tokens_emitted == 6
    got = {}
    while eng.has_work():
        got.update({c.request_id: c.tokens for c in eng.step_settled()})
        assert eng._inflight is None
    want, _ = run(dense(), [(0, [1, 2, 3], 9), (2, [7, 8], 4)], settled=True)
    assert [got[0], got[1]] == [c.tokens for c in want]


# -- (d) a quantum is whole: the scheduler's wrapper and disagg ---------------


def test_the_serve_step_wrapper_leaves_nothing_in_flight():
    eng = dense()
    feed = {0: [([1, 2, 3], 4)], 2: [([9, 8], 3)]}
    serve_step = make_continuous_serve_step(
        eng, next_requests=lambda i: feed.get(i, []))
    state, total = {"step": 0, "completed": 0}, 0
    for _ in range(8):
        before = eng.tokens_emitted
        state, metrics = serve_step(state)
        assert eng._inflight is None
        assert metrics["tokens"] == eng.tokens_emitted - before
        assert sum(map(len, eng.slot_tokens)) + sum(
            r[5] for r in records(eng, Ev.ENG_RETIRE)) == eng.tokens_emitted
        total += metrics["tokens"]
    assert state["completed"] == 2 and total == 7 and not eng.has_work()
    st = eng.stats()
    # Each tick read its own tokens: a wait and an emit loop a record.
    # Only the two decodes behind an admission's forward had anything
    # unread ahead of them on the device.
    decodes = records(eng, Ev.ENG_DECODE)
    for _ts, _ev, _tick, pre, sync, post, _flag, _ in decodes:
        assert pre > 0 and sync > 0 and post > 0
    assert [r[6] for r in decodes] == [1, 0, 1, 0]
    assert (st["ticks_overlapped"], st["ticks_settled"]) == (2, 2)
    assert st["admissions_overlapped"] == 0


def test_a_settled_tick_books_a_decode_it_finds_in_flight():
    """The two calls mixed: ``step_settled()`` after ``step()`` reads
    the decode in flight ahead of its own, and leaves none."""
    eng = dense()
    eng.submit([1, 2, 3], 5)
    eng.step(), eng.step()
    assert eng._inflight is not None and len(eng.slot_tokens[0]) == 2
    eng.step_settled()
    assert eng._inflight is None and len(eng.slot_tokens[0]) == 4
    (done,) = eng.step_settled()
    assert len(done.tokens) == 5 and not eng.has_work()
    (want,), _ = run(dense(), [(0, [1, 2, 3], 5)], settled=True)
    assert done.tokens == want.tokens


def test_the_speculative_engine_ticks_synchronously():
    cfg, params = model("dense")
    spec = SpeculativeBatcher(cfg, params, cfg, params, k=2, n_slots=2,
                              prompt_bucket=BUCKET, max_len=MAX_LEN)
    script = [(0, [1, 2, 3], 6), (1, [7, 8], 1), (2, [9, 4], 5)]
    log = spied(spec)
    got, _ = run(spec, script, settled=False)
    # its round is fed from the host: every first token is read before
    # the next program is enqueued, and no decode of the plain engine's
    # runs
    assert [e[0] for e in log] == ["prefill", "read"] * 3
    assert all(e[1] == ["_Admission"] for e in log[1::2])
    assert spec._inflight is None and spec.settle() == []
    want, _ = run(dense(), script, settled=False)
    assert [c.tokens for c in got] == [c.tokens for c in want]
    st = spec.stats()
    assert st["ticks_overlapped"] == st["ticks_settled"] == 0
    assert st["admissions_overlapped"] == 0


def test_the_disaggregated_backend_ticks_synchronously():
    from pbs_tpu.serve.disagg import DisaggServeBackend

    cfg, _ = model("dense")
    be = DisaggServeBackend("d", cfg, tp=1, dp=1, n_slots=2,
                            prompt_bucket=BUCKET, max_len=MAX_LEN)
    gw = Gateway([be], quotas={"t": TenantQuota(rate=1e9, burst=1e9)})
    gw.submit("t", {"prompt": [1, 2, 3], "max_new": 5})
    done = []
    while gw.busy():
        done += gw.tick()
        assert be.engine._inflight is None
    assert len(done) == 1 and done[0][1]["tokens"] == 5
    assert be.engine.stats()["ticks_overlapped"] == 0


def test_the_gateway_pump_polls_until_the_last_token_is_out():
    eng = dense()
    gw = Gateway([BatcherBackend("b", eng)],
                 quotas={"t": TenantQuota(rate=1e9, burst=1e9)})
    for prompt, n in (([1, 2, 3], 5), ([4, 5], 2), ([6], 7)):
        assert gw.submit("t", {"prompt": prompt, "max_new": n}).admitted
    done = []
    while gw.busy():
        done += gw.tick()
    assert sorted(info["tokens"] for _, info in done) == [2, 5, 7]
    assert not eng.has_work() and eng._inflight is None
    assert eng.stats()["ticks_overlapped"] > 0


# -- (e) the records keep what the readers assume ------------------------------


@pytest.mark.parametrize("settled", [False, True])
def test_route_and_decode_records_share_stamp_and_tick(settled):
    """One ENG_DECODE a dispatched decode, inside its ENG_TICK and with
    its tick number, its three phases end to end within the tick; one
    ENG_ROUTE a dispatched decode, stamped like the ENG_DECODE of the
    call that read it (``benchmarks/readers/_route.py`` keeps a route
    only if its timestamp is an ENG_DECODE's). The one route with no
    ENG_DECODE beside it is read by the call that drains the pipeline
    and enqueues nothing."""
    eng = fresh(engine("moe"))
    eng.trace.consume()
    st0 = eng.stats()
    run(eng, crowd(eng.cfg.vocab), settled=settled)
    st = eng.stats()
    n = (st["ticks_overlapped"] + st["ticks_settled"]
         - st0["ticks_overlapped"] - st0["ticks_settled"])
    ticks = {r[3]: r for r in records(eng, Ev.ENG_TICK)}
    decodes = records(eng, Ev.ENG_DECODE)
    assert len(decodes) == n > 0
    assert len({r[2] for r in decodes}) == n  # one a tick
    for ts, _ev, tick, pre, sync, post, flag, _ in decodes:
        t0, dur = ticks[tick][0], ticks[tick][2]
        assert t0 <= ts and ts + pre + sync + post <= t0 + dur
        assert pre > 0 and flag in (0, 1)
    prefills = {r[0] for r in records(eng, Ev.ENG_PREFILL)}
    routes = [r for r in records(eng, Ev.ENG_ROUTE) if r[0] not in prefills]
    assert len(routes) == n
    by_stamp = {r[0]: r for r in decodes}
    beside = [r for r in routes if r[0] in by_stamp]
    assert all(r[2] == by_stamp[r[0]][2] for r in beside)
    assert len({r[0] for r in beside}) == len(beside)  # one a decode
    drains = len(routes) - len(beside)
    assert drains == (0 if settled else 1)
    if not settled:
        assert sum(r[6] for r in decodes) == (
            st["ticks_overlapped"] - st0["ticks_overlapped"]) > 0


def test_no_program_is_built_under_traffic():
    """The token vector a dispatch is handed is a program's own output
    (the decode's, or a prefill's with a first token written into it):
    the warm-up has met that signature (under a mesh it is a second
    instance of the decode), and traffic builds nothing."""
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    cfg, params = model("dense")
    for mesh in (None, make_mesh({"tp": 2}, devices=jax.devices()[:2])):
        eng = ContinuousBatcher(
            cfg, params if mesh is None else place(params, mesh),
            n_slots=2, prompt_bucket=BUCKET, max_len=MAX_LEN, mesh=mesh)
        built = eng._decode_fn._cache_size()
        assert built == (1 if mesh is None else 2)
        # the prefill takes that vector too (and hands it on with the
        # first token in it): one instance a rung, met by the warm-up
        assert eng._prefill_fn._cache_size() == len(eng.rungs)
        run(eng, crowd(cfg.vocab), settled=False)
        assert eng.stats()["ticks_overlapped"] > 0
        assert eng.stats()["admissions_overlapped"] > 0
        assert eng._decode_fn._cache_size() == built
        assert eng._prefill_fn._cache_size() == len(eng.rungs)
