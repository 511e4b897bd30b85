"""The flight recorder (docs/TRACING.md): rings that keep their newest
records, and the engine-tick, executed-step and gateway records that
say where a tick's and a quantum's host time goes."""

import gc
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.gateway import Gateway, TenantQuota
from pbs_tpu.gateway.backends import Backend
from pbs_tpu.models import TransformerConfig, init_params, make_train_step
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.obs.spans import SpanAssembler
from pbs_tpu.obs.trace import (
    TRACE_REC_WORDS,
    Ev,
    TraceBuffer,
    host_ring,
    job_tag,
    live_rings,
)
from pbs_tpu.runtime import Job, Partition, SchedParams, native
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.telemetry.source import TpuBackend
from pbs_tpu.utils.clock import MS, MonotonicClock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIERS = [False, "ctypes", True]
TINY = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=1,
            d_ff=32, max_seq=64, dtype=jnp.float32)


def ring(capacity: int, tier) -> TraceBuffer:
    if tier and native.load() is None:
        pytest.skip("native runtime unavailable")
    if tier is True and native.fastcall() is None:
        pytest.skip("fastcall tier unavailable")
    return TraceBuffer(capacity, native=tier)


def batch(lo: int, hi: int) -> np.ndarray:
    recs = np.zeros((hi - lo, TRACE_REC_WORDS), dtype="<u8")
    recs[:, 0] = np.arange(lo, hi)
    recs[:, 1] = int(Ev.SCHED_PICK)
    recs[:, 2] = np.arange(lo, hi) * 7
    return recs


# -- A. the ring keeps the newest records ------------------------------------


@pytest.mark.parametrize("tier", TIERS)
def test_unattached_ring_overwrites_oldest(tier):
    cap = 8
    tb = ring(cap, tier)
    for i in range(3 * cap):
        assert tb.emit(i, Ev.SCHED_PICK, i * 7)
    assert tb.lost == 2 * cap          # exactly the records overwritten
    got = tb.peek(cap)
    assert got[:, 0].tolist() == list(range(2 * cap, 3 * cap))  # in order
    assert got[:, 2].tolist() == [i * 7 for i in range(2 * cap, 3 * cap)]
    assert not tb.has_consumer


@pytest.mark.parametrize("tier", TIERS)
def test_unattached_ring_batched_overwrite(tier):
    """emit_many equals that many scalar emits: a batch larger than the
    room overwrites the oldest, one larger than the ring lands its last
    ``capacity`` records."""
    cap = 8
    tb = ring(cap, tier)
    assert tb.emit_many(batch(0, 5)) == 5
    assert tb.emit_many(batch(5, 11)) == 6       # 3 overwritten
    assert tb.lost == 3
    assert tb.peek(cap)[:, 0].tolist() == list(range(3, 11))
    assert tb.emit_many(batch(11, 11 + 3 * cap)) == 3 * cap
    assert tb.lost == 3 + 3 * cap
    assert tb.peek(cap)[:, 0].tolist() == list(
        range(11 + 2 * cap, 11 + 3 * cap))


def test_overwrite_is_byte_identical_across_tiers():
    if native.load() is None or native.fastcall() is None:
        pytest.skip("native tiers unavailable")
    rings = [TraceBuffer(8, native=t) for t in TIERS]
    for tb in rings:
        for i in range(13):
            tb.emit(i, Ev.SCHED_WAKE, i, -3)
        tb.emit_many(batch(13, 13 + 21))
        tb.emit(99, Ev.SCHED_SLEEP)
    images = {bytes(tb._bytes) for tb in rings}
    assert len(images) == 1
    assert {tb.lost for tb in rings} == {13 + 21 + 1 - 8}


@pytest.mark.parametrize("tier", TIERS)
def test_attached_ring_still_drops_new(tier):
    cap = 8
    tb = ring(cap, tier)
    tb.attach_consumer()
    for i in range(3 * cap):
        assert tb.emit(i, Ev.SCHED_PICK) == (i < cap)
    assert tb.emit_many(batch(100, 104)) == 0
    assert tb.lost == 2 * cap + 4
    assert tb.consume(64)[:, 0].tolist() == list(range(cap))  # the oldest
    tb.detach_consumer()               # nobody drains any more
    for i in range(2 * cap):
        tb.emit(200 + i, Ev.SCHED_PICK)
    assert tb.peek(cap)[:, 0].tolist() == list(range(200 + cap, 200 + 2 * cap))


@pytest.mark.parametrize("tier", TIERS)
def test_draining_is_attaching(tier):
    tb = ring(4, tier)
    tb.emit(0, Ev.SCHED_PICK)
    assert not tb.has_consumer
    assert len(tb.peek()) == 1 and not tb.has_consumer   # peek looks only
    assert len(tb.consume()) == 1 and tb.has_consumer
    for i in range(6):
        tb.emit(1 + i, Ev.SCHED_PICK)
    assert tb.lost == 2 and tb.consume()[:, 0].tolist() == [1, 2, 3, 4]


def test_file_backed_attach_marks_the_consumer(tmp_path):
    path = str(tmp_path / "t.ring")
    prod = TraceBuffer.file_backed(path, capacity=4, native=False)
    for i in range(6):
        prod.emit(i, Ev.SCHED_PICK)
    assert prod.peek()[:, 0].tolist() == [2, 3, 4, 5]
    cons = TraceBuffer.file_backed(path, attach=True, native=False)
    assert prod.has_consumer and cons.has_consumer
    assert not prod.emit(6, Ev.SCHED_PICK)         # full: drop-new now
    assert cons.consume()[:, 0].tolist() == [2, 3, 4, 5]


def test_live_rings_are_weakly_held():
    part = Partition("weakly", source=TpuBackend(
        peak_flops=1e12, peak_hbm_bw=1e11))
    names = [n for n, _ in live_rings()]
    assert "partition:weakly#0" in names and "host" in names
    assert dict(live_rings())["partition:weakly#0"] is part.traces[0]
    del part
    gc.collect()
    assert "partition:weakly#0" not in [n for n, _ in live_rings()]


def test_full_collections_are_recorded():
    host = host_ring()
    before = len(host.peek(host.capacity))
    gc.collect()
    recs = host.peek(host.capacity)
    assert len(recs) == before + 1 or host.lost
    ts, ev, dur, gen = recs[-1, :4].tolist()
    assert ev == Ev.HOST_GC and gen == 2 and dur > 0 and ts > 0


# -- B. records where the work happens ---------------------------------------


@pytest.fixture(scope="module")
def cfg():
    return TransformerConfig(**TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def engine_records(eng) -> list[list[int]]:
    return eng.trace.peek(eng.trace.capacity).astype(np.int64).tolist()


def test_one_tick_two_admissions_one_retirement(cfg, params):
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32)
    a = eng.submit([1, 2, 3], 1)        # budget 1: retires in this tick
    b = eng.submit([4, 5, 6, 7], 4)
    done = eng.step()
    assert [c.request_id for c in done] == [a]
    recs = engine_records(eng)
    names = [Ev(r[1]).name for r in recs]
    # a is read before b is dispatched, and retires there; b's forward
    # is read behind the decode's dispatch, and its records are written
    # then (a record is written when its span ends)
    assert names == [
        "ENG_KEYSPLIT", "ENG_PREFILL", "ENG_ADMIT", "ENG_RETIRE",   # a
        "ENG_KEYSPLIT",                                 # b's
        "ENG_KEYSPLIT",                                 # the decode's
        "ENG_PREFILL", "ENG_ADMIT",                     # b
        "ENG_DECODE", "ENG_TICK"]
    tick = recs[-1]
    t0, dur, seq, busy, admitted, retired, queued = tick[0], *tick[2:]
    assert (seq, busy, admitted, retired, queued) == (0, 2, 2, 1, 0)
    for ts, ev, *args in recs[:-1]:
        assert args[0] == seq                    # every child names its tick
        assert all(x >= 0 for x in args)
        length = {Ev.ENG_ADMIT: args[5], Ev.ENG_KEYSPLIT: args[1],
                  Ev.ENG_PREFILL: args[3] + args[4],
                  Ev.ENG_DECODE: args[1] + args[2] + args[3],
                  Ev.ENG_RETIRE: 0}[Ev(ev)]
        assert t0 <= ts and ts + length <= t0 + dur   # inside the tick
    admits = [r for r in recs if r[1] == Ev.ENG_ADMIT]
    assert [(r[3], r[4], r[5]) for r in admits] == [(a, 0, 3), (b, 1, 4)]
    prefills = [r for r in recs if r[1] == Ev.ENG_PREFILL]
    assert [r[7] for r in prefills] == [8, 8]    # both ran, at the bucket
    # A prefill and its key split lie inside their admission.
    for adm, pre in zip(admits, prefills):
        assert adm[0] <= pre[0] and pre[0] + pre[5] + pre[6] <= adm[0] + adm[7]
    # b's wait, its admission and the decode's wait end at one stamp
    dec = recs[-2]
    assert admits[1][0] + admits[1][7] == dec[0] + dec[3] + dec[4] \
        == prefills[1][0] + prefills[1][5] + prefills[1][6]
    retire = recs[3]
    assert retire[3:6] == [a, 0, 1] and retire[7] >= retire[6] > 0
    # The second tick admits nothing and says so.
    eng.step()
    assert engine_records(eng)[-1][5] == 0


def test_prefix_hit_is_on_the_prefill_record(cfg, params):
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32, prefix_cache_size=2)
    eng.submit([1, 2, 3], 2)
    eng.step()
    eng.submit([1, 2, 3], 2)
    eng.step()
    rows = [r[7] for r in engine_records(eng) if r[1] == Ev.ENG_PREFILL]
    assert rows == [8, 0]   # a forward at its rung; a hit ran none: 0 rows


def test_engine_trace_can_be_bound_or_off(cfg, params):
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32)
    mine = TraceBuffer(64)
    eng.bind_trace(mine)                # the driver's ring
    eng.submit([1, 2], 2)
    eng.step()
    assert Ev.ENG_TICK in mine.peek()[:, 1].tolist()
    written = len(mine.peek(64))
    eng.bind_trace(None)                # record nothing
    eng.step()
    assert len(mine.peek(64)) == written and eng.trace is None


class Wall:
    """A monotonic clock a test can push."""

    def __init__(self):
        self.t = 1_000 * MS

    def now_ns(self) -> int:
        return self.t


def serve_through_gateway(cfg, n: int = 5):
    backend = ShardedServeBackend("engine", cfg, tp=1, dp=1, n_slots=2,
                                  prompt_bucket=8, max_len=32, seed=0)
    gw = Gateway([backend], clock=MonotonicClock(), quotas={
        "t": TenantQuota(rate=1e9, burst=1e9, slo="interactive",
                         max_queued=64)})
    rids = []
    for i in range(n):
        res = gw.submit("t", {"prompt": [1 + i, 2, 3], "max_new": 3 + i})
        assert res.admitted
        rids.append(res.rid)
    for _ in range(200):
        if not gw.busy():
            break
        gw.tick()
    assert not gw.busy()
    gw.flush_trace()
    return gw, backend, rids


def test_engine_records_join_the_gateway_chain_by_id(cfg):
    gw, backend, rids = serve_through_gateway(cfg)
    assert gw.trace is not None and gw.spans is not None   # on by default
    assert "gateway:gw" in dict(live_rings())
    grecs = gw.trace.peek(gw.trace.capacity).astype(np.int64)
    asm = SpanAssembler(grecs, gw.spans.rid_table(),
                        gw.spans.member_table(), gw.spans.tenant_table(),
                        rid_base=gw.spans.rid_base)
    assert asm.validate(rids) == []               # still gap-free
    erecs = engine_records(backend.engine)
    admit = {r[3]: r for r in erecs if r[1] == Ev.ENG_ADMIT}
    retire = {r[3]: r for r in erecs if r[1] == Ev.ENG_RETIRE}
    assert len(admit) == len(retire) == len(rids)
    for rid in rids:
        chain = asm.chains[rid]
        # (ts, ev, backend, member, engine_rid + 1): submit, slot, retire
        execs = [c for c in chain if c[1] == Ev.SPAN_EXEC]
        assert len(execs) == 3 and len({c[4] for c in execs}) == 1
        erid = execs[0][4] - 1
        t_submit = next(c[0] for c in chain if c[1] == Ev.SPAN_ADMIT)
        qdelay = next(c[3] for c in chain if c[1] == Ev.SPAN_DISPATCH)
        a = admit[erid]
        # Order and identity on recorded fields, no two wall-clock
        # differences held equal (under six workers a preemption
        # between two stamps is milliseconds): the request left the
        # front door's queue before it entered its slot, and the
        # engine's records carry the request's own slot and length.
        assert t_submit + qdelay <= a[0] and a[6] >= 0
        assert (a[4], a[5]) == (retire[erid][4], 3)  # slot, prompt length
        assert execs[1][0] >= a[0]                 # slot EXEC: at admission
        complete = next(c for c in chain if c[1] == Ev.SPAN_COMPLETE)
        assert execs[2][0] <= complete[0]          # retire EXEC: before it
        assert retire[erid][5] == len(range(3 + rids.index(rid)))


class SlowPollBackend(Backend):
    """One request at a time; ``poll`` takes 30 ms of the clock, as an
    engine tick does."""

    name, capacity = "slow", 1

    def __init__(self, clock: Wall):
        self.clock, self.req = clock, None

    def depth(self) -> int:
        return int(self.req is not None)

    def dispatch_request(self, req, now_ns: int) -> None:
        self.req = req
        if self.exec_hook is not None:
            self.exec_hook(req, now_ns)

    def poll(self, now_ns: int):
        if self.req is None:
            return []
        self.clock.t += 30 * MS
        req, self.req = self.req, None
        return [(req, {"service_ns": 30 * MS, "backend": self.name})]

    def drain(self):
        req, self.req = self.req, None
        return [req] if req is not None else []


def test_gateway_stamps_after_the_poll():
    clock = Wall()
    gw = Gateway([SlowPollBackend(clock)], clock=clock, quotas={
        "t": TenantQuota(rate=1e9, burst=1e9, slo="interactive",
                         max_queued=8)})
    t_submit = clock.t
    first = gw.submit("t", {}).rid
    second = gw.submit("t", {}).rid
    gw.tick()                                   # dispatches the first
    t_tick = clock.t
    done = gw.tick()                            # polls 30 ms, completes it
    assert [rid for rid, _ in done] == [first]
    assert done[0][1]["latency_ns"] == t_tick + 30 * MS - t_submit
    gw.flush_trace()
    recs = gw.trace.peek(512).astype(np.int64).tolist()
    complete = next(r for r in recs if r[1] == Ev.SPAN_COMPLETE)
    assert complete[0] == t_tick + 30 * MS       # when it happened
    # The second request was dispatched in that tick, after the poll:
    # its queue delay has the 30 ms it really waited.
    dispatch = [r for r in recs if r[1] == Ev.SPAN_DISPATCH][1]
    assert dispatch[0] == t_tick + 30 * MS
    assert dispatch[4] == t_tick + 30 * MS - t_submit
    assert gw.inflight[second].queue_delay_ns == dispatch[4]


def test_exec_step_lies_inside_its_quantum():
    be = TpuBackend(clock=MonotonicClock(), peak_flops=1e12,
                    peak_hbm_bw=1e11)
    part = Partition("quanta", source=be, scheduler="credit")
    step = jax.jit(lambda x: x * 1.0001 + 1.0)
    part.add_job(Job("train", step_fn=step, state=jnp.ones((64, 64)),
                     params=SchedParams(weight=256), max_steps=6))
    part.run(max_rounds=50)
    recs = part.peek_traces(4096).astype(np.int64).tolist()
    kinds = [r[1] for r in recs
             if r[1] in (Ev.SCHED_PICK, Ev.EXEC_STEP, Ev.SCHED_DESCHED)]
    assert kinds[:3] == [Ev.SCHED_PICK, Ev.EXEC_STEP, Ev.SCHED_DESCHED]
    steps = [r for r in recs if r[1] == Ev.EXEC_STEP]
    assert len(steps) == 6
    assert steps[0][5] > 0 and steps[-1][5] == 0   # the first one compiled
    picks = [r[0] for r in recs if r[1] == Ev.SCHED_PICK]
    descheds = [r[0] for r in recs if r[1] == Ev.SCHED_DESCHED]
    i = 0
    for pick, desched in zip(picks, descheds):
        inside = [s for s in steps if pick <= s[0] < desched]
        assert inside, "a quantum with no executed step"
        # slot, dispatch, wait, compile, job tag
        for ts, _ev, slot, dispatch, wait, compile_ns, tag, _ in inside:
            assert slot == 0 and tag == job_tag("train")
            assert min(dispatch, wait, compile_ns) >= 0
            assert ts + dispatch + compile_ns + wait <= desched
        # Compile time excluded, the steps fit their quantum's wall.
        assert sum(s[3] + s[4] for s in inside) <= desched - pick - sum(
            s[5] for s in inside)
        i += len(inside)
    assert i == 6
    # The partition's switch turns the source's records off with its own.
    part2 = Partition("quiet", source=be, scheduler="credit")
    part2.trace_enabled = False
    part2.add_job(Job("train", step_fn=step, state=jnp.ones((64, 64)),
                      max_steps=2))
    part2.run(max_rounds=10)
    assert len(part2.peek_traces()) == 0


def test_unbound_backend_writes_its_own_ring():
    be = TpuBackend(clock=MonotonicClock(), peak_flops=1e12,
                    peak_hbm_bw=1e11)
    job = Job("lone", step_fn=jax.jit(lambda x: x + 1), state=jnp.zeros(4))
    be._invoke(job, job.step_fn)
    recs = be.trace.peek().astype(np.int64).tolist()
    assert [r[1] for r in recs] == [Ev.EXEC_STEP]
    assert recs[0][6] == job_tag("lone") and be.trace in [
        r for _, r in live_rings()]


# -- the names the benchmark's readers match ---------------------------------


def _matches():
    out = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmarks", "metrics", "*.json"))):
        with open(path) as f:
            args = json.load(f).get("args", {})
        m = args.get("match")
        for s in ([m] if isinstance(m, str) else m or []):
            out.append((os.path.basename(path)[:-5], s))
    return out


@pytest.fixture(scope="module")
def module_names(cfg, params):
    """The lowered module names of the engine's two programs and the
    train step, as the profiler will show them."""
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32)
    key = jax.random.PRNGKey(0)
    lowered = [
        eng._decode_fn.lower(eng.params, eng.cache,
                             jnp.zeros((2,), jnp.int32),
                             jnp.zeros((2,), jnp.int32), key),
        eng._prefill_fn.lower(eng.params, eng.cache,
                              jnp.zeros((2,), jnp.int32), 0,
                              jnp.zeros((8,), jnp.int32), 1, key)]
    init_opt, train_step = make_train_step(cfg, learning_rate=1e-3)
    state = (params, init_opt(params), 0)
    lowered.append(jax.jit(train_step, donate_argnums=(0,)).lower(
        state, jnp.zeros((1, 16), jnp.int32)))
    names = []
    for low in lowered:
        head = low.as_text().split("module @", 1)[1]
        names.append(head.split(" ", 1)[0].split("(", 1)[0])
    return names


def test_module_names_are_the_three_the_metrics_expect(module_names):
    assert module_names == ["jit__decode", "jit__prefill", "jit_train_step"]


@pytest.mark.parametrize("metric,match", _matches())
def test_metric_match_names_a_real_program(metric, match, module_names):
    """A rename of a jitted function breaks this test, not a metric
    that would silently find no program in the trace."""
    assert match in module_names, (metric, match, module_names)
