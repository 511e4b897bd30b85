"""Vectorized trace ring equivalence + EmitBatch + ledger fast paths.

The PR 5 acceptance bar: batched emit (``emit_many``/``EmitBatch``) and
vectorized ``consume``/``peek`` must be record-for-record identical to
the old scalar path — same records, same order, same drop accounting —
including across ring wrap and on file-backed attach."""

from __future__ import annotations

import numpy as np
import pytest

from conftest import require_native
from pbs_tpu.obs.trace import (
    TRACE_REC_WORDS,
    EmitBatch,
    Ev,
    TraceBuffer,
)
from pbs_tpu.runtime import native

U64 = 2**64 - 1


class ScalarRef:
    """Reference semantics of the pre-vectorization scalar ring: emit
    drops (and counts) when full, consume drains FIFO."""

    def __init__(self, cap: int):
        self.cap = cap
        self.buf: list[list[int]] = []
        self.lost = 0

    def emit(self, ts, ev, *args):
        a = list(args)[:6] + [0] * (6 - min(6, len(args)))
        if len(self.buf) >= self.cap:
            self.lost += 1
            return False
        self.buf.append([int(ts), int(ev)] + [int(x) & U64 for x in a])
        return True

    def consume(self, n):
        out, self.buf = self.buf[:n], self.buf[n:]
        return out


def _interleaved_equivalence(tb: TraceBuffer, consumer: TraceBuffer,
                             seed: int, steps: int = 1500) -> None:
    rng = np.random.default_rng(seed)
    ref = ScalarRef(tb.capacity)
    drained: list[list[int]] = []
    drained_ref: list[list[int]] = []
    for step in range(steps):
        r = rng.random()
        if r < 0.45:  # single emit, sometimes with odd args
            args = (int(rng.integers(0, 9)), -3, 1, 2, 3, 4, 5, 6)[
                : int(rng.integers(0, 8))]
            tb.emit(step, Ev.SCHED_WAKE, *args)
            ref.emit(step, Ev.SCHED_WAKE, *args)
        elif r < 0.7:  # batched emit
            k = int(rng.integers(1, 2 * tb.capacity))
            recs = np.zeros((k, TRACE_REC_WORDS), dtype="<u8")
            recs[:, 0] = step
            recs[:, 1] = int(Ev.SCHED_PICK)
            recs[:, 2] = np.arange(k)
            tb.emit_many(recs)
            for row in recs.tolist():
                ref.emit(row[0], row[1], *row[2:])
        else:  # drain in chunks
            k = int(rng.integers(1, tb.capacity))
            drained.extend(consumer.consume(k).tolist())
            drained_ref.extend(ref.consume(k))
    drained.extend(consumer.consume(10**6).tolist())
    drained_ref.extend(ref.consume(10**6))
    assert drained == drained_ref
    assert tb.lost == ref.lost


@pytest.mark.parametrize("use_native", [False, "ctypes", True])
def test_batched_paths_match_scalar_reference(use_native):
    """Interleaved single/batched emits drained in chunks reproduce the
    exact scalar-path record sequence, drop counter included, across
    many wraps (capacity 16, ~thousands of records) — on the Python,
    ctypes, and (when buildable) fastcall tiers."""
    if use_native:
        require_native()
    tb = TraceBuffer(capacity=16, native=use_native)
    # The drop-new contract is the contract of a ring WITH a consumer
    # (unattached, a full ring overwrites its oldest record:
    # tests/test_flight_recorder.py).
    tb.attach_consumer()
    _interleaved_equivalence(tb, tb, seed=7)


def test_file_backed_attach_equivalence(tmp_path):
    """Producer writes batched into a file-backed ring; the attached
    consumer (the xenbaked-style monitor mapping) sees the identical
    stream and shared drop counter."""
    path = str(tmp_path / "ring.trace")
    prod = TraceBuffer.file_backed(path, capacity=12, native=False)
    cons = TraceBuffer.file_backed(path, attach=True, native=False)
    _interleaved_equivalence(prod, cons, seed=11)


def test_emit_many_wrap_is_two_slices_exact():
    """Deterministic wrap check: fill to mid-ring, then a batch that
    wraps; drained payloads stay in emit order."""
    tb = TraceBuffer(capacity=8, native=False)
    for i in range(5):
        tb.emit(i, Ev.SCHED_WAKE, i)
    assert tb.consume(3).shape[0] == 3  # tail now mid-ring
    recs = np.zeros((7, TRACE_REC_WORDS), dtype="<u8")
    recs[:, 0] = np.arange(100, 107)
    recs[:, 1] = int(Ev.SCHED_PICK)
    assert tb.emit_many(recs) == 6  # space for 6; wraps the physical end
    assert tb.lost == 1  # 7th batched record found the ring full
    got = tb.consume(16)
    assert [int(r[0]) for r in got] == [3, 4, 100, 101, 102, 103, 104, 105]
    assert tb.consume(16).shape[0] == 0


def test_emit_arg_normalization_matches_scalar():
    """Negatives mask to two's complement, >6 args truncate, missing
    args zero-fill — byte-identical to the old list-building path."""
    tb = TraceBuffer(capacity=4, native=False)
    tb.emit(1, Ev.SCHED_WAKE, -1, 2**65 + 3, 7)
    tb.emit(2, Ev.SCHED_WAKE, 1, 2, 3, 4, 5, 6, 7, 8)  # extra args dropped
    got = tb.consume().tolist()
    assert got[0] == [1, int(Ev.SCHED_WAKE), U64, 3, 7, 0, 0, 0]
    assert got[1] == [2, int(Ev.SCHED_WAKE), 1, 2, 3, 4, 5, 6]


def test_peek_vectorized_keeps_newest_and_consumer_tail():
    tb = TraceBuffer(capacity=8, native=False)
    for i in range(6):
        tb.emit(i, Ev.SCHED_WAKE)
    assert [int(r[0]) for r in tb.peek(3)] == [3, 4, 5]  # newest n
    assert tb.consume(16).shape[0] == 6  # peek stole nothing


# -- EmitBatch --------------------------------------------------------------


def test_emit_batch_watermarks_and_flush():
    tb = TraceBuffer(capacity=64, native=False)
    b = EmitBatch(tb, capacity=4, flush_ns=1000)
    b.emit(0, Ev.SCHED_WAKE, 1)
    b.emit(1, Ev.SCHED_WAKE, 2)
    assert tb.consume(64).shape[0] == 0  # staged
    b.emit(2, Ev.SCHED_WAKE, 3)
    b.emit(3, Ev.SCHED_WAKE, 4)  # size watermark
    assert tb.consume(64).shape[0] == 4
    b.emit(10, Ev.SCHED_WAKE, 5)
    b.emit(2000, Ev.SCHED_WAKE, 6)  # time watermark (ts span >= 1000)
    assert [int(r[2]) for r in tb.consume(64)] == [5, 6]
    b.emit(3000, Ev.SCHED_WAKE, 7)
    assert b.pending() == 1
    assert b.flush() == 1
    assert b.pending() == 0 and tb.consume(64).shape[0] == 1


def test_partition_batched_run_matches_unbatched_stream():
    """A batched sim-style partition run drains the same SCHED record
    stream as an unbatched one (determinism: batching only changes WHEN
    records reach the ring, never content or order)."""
    from pbs_tpu.runtime import Job, Partition
    from pbs_tpu.telemetry import SimBackend, SimProfile

    def run(batched: bool):
        be = SimBackend()
        part = Partition("t", source=be, scheduler="credit")
        if batched:
            part.enable_trace_batching()
        be.register("a", SimProfile.steady())
        part.add_job(Job("a", max_steps=5))
        part.run()
        return part.drain_traces().tolist()

    assert run(True) == run(False)


@pytest.mark.parametrize("batched", [False, True])
def test_sampler_overflow_lands_in_trace_in_both_modes(batched):
    """TELEM_OVERFLOW is mode-independent: the sampler's staged trace
    channel exists whether or not the partition batches its scheduler
    events (trace CONTENT must not depend on enable_trace_batching)."""
    from pbs_tpu.runtime import Job, Partition
    from pbs_tpu.telemetry import Counter, SimBackend, SimProfile

    be = SimBackend()
    part = Partition("t", source=be, scheduler="credit")
    if batched:
        part.enable_trace_batching()
    be.register("a", SimProfile.steady(step_time_ns=100_000))
    job = part.add_job(Job("a", max_steps=10))
    sid = part.sampler.arm(job.contexts[0], Counter.STEPS_RETIRED, period=3)
    part.run()
    recs = part.drain_traces()
    ovf = [r for r in recs.tolist() if r[1] == int(Ev.TELEM_OVERFLOW)]
    assert len(ovf) == 1  # fired once, suspended until rearm
    assert ovf[0][3] == sid and ovf[0][4] == int(Counter.STEPS_RETIRED)


# -- ledger fast path -------------------------------------------------------


@pytest.mark.parametrize("use_native", [False, "ctypes", True])
def test_snapshot_many_matches_scalar_snapshots(use_native):
    from pbs_tpu.telemetry import NUM_COUNTERS, Ledger

    if use_native:
        require_native()
    led = Ledger(8, native=use_native)
    for s in range(8):
        led.add_many(s, np.arange(NUM_COUNTERS, dtype="<u8") * (s + 1))
    many = led.snapshot_many(range(8))
    assert many.shape == (8, NUM_COUNTERS)
    for s in range(8):
        np.testing.assert_array_equal(many[s], led.snapshot(s))
    assert led.snapshot_many([]).shape == (0, NUM_COUNTERS)
