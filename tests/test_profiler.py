"""Measured telemetry: XLA-profiler sampling behind the TpuBackend seam.

Round-1 verdict gap #3: HBM_STALL_NS was a static roofline estimate, so
the feedback filter's phase detection could never see a real program
change phase. These tests prove the measured path does: a two-phase job
(matmul-heavy -> elementwise-heavy) shows stall_rate actually moving,
and FeedbackPolicy reacts while running against TpuBackend (not only
SimBackend). Reference behavior being matched: real counters published
per context switch, xen-4.2.1/xen/arch/x86/perfctr.c:1547-1573.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.runtime.job import Job, SchedParams
from pbs_tpu.runtime.partition import Partition
from pbs_tpu.sched.feedback import FeedbackPolicy
from pbs_tpu.telemetry.counters import Counter
from pbs_tpu.telemetry.profiler import (
    TraceStats,
    XlaQuantumProfiler,
    classify_op,
    parse_trace_events,
)
from pbs_tpu.telemetry.source import TpuBackend


# ---------------------------------------------------------------------------
# Parser unit tests (synthetic events — no profiler needed)
# ---------------------------------------------------------------------------


def _ev(name, ts, dur, pid=1, args=None):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": pid,
            "args": args or {}}


def test_classify_op_buckets():
    assert classify_op("dot_general.1") == "compute"
    assert classify_op("wrapped_convolution") == "compute"
    assert classify_op("all-reduce.3") == "collective"
    assert classify_op("reduce-scatter") == "collective"
    assert classify_op("collective-permute.2") == "collective"
    assert classify_op("wrapped_tanh") == "memory"
    assert classify_op("fusion.12") == "memory"
    # fusion with a dot root is compute (TPU names most ops 'fusion')
    assert classify_op("fusion.4", long_name="fusion(dot(...))") == "compute"
    # runtime / python frames are not ops
    assert classify_op("PjRtCpuExecutable::Execute") is None
    assert classify_op("ParseArguments") is None
    assert classify_op("$profiler.py:246 trace") is None
    assert classify_op("end: dot_general.1") is None
    # control-flow containers span their whole body (children are
    # billed individually) — counting them double-bills the body
    assert classify_op("while.246") is None
    assert classify_op("conditional.3") is None
    assert classify_op("get-tuple-element.17") is None
    assert classify_op("opt-barrier.1") is None
    # dtype casts are NOT compute ('convert' must not substring-match
    # 'conv'); Pallas/Mosaic kernels ARE — but a bare custom-call is
    # not (lax.top_k in the MoE router lowers there too)
    assert classify_op("convert.5") == "memory"
    assert classify_op("tpu_custom_call.1") == "compute"
    assert classify_op("mosaic.3") == "compute"
    assert classify_op("fwd_kernel.2") == "compute"
    assert classify_op("_fwd_kernel.2") == "compute"  # real spelling
    assert classify_op("_mm_kernel") == "compute"
    assert classify_op("custom-call.2") == "memory"  # e.g. router top_k
    assert classify_op("custom-call.7",
                       long_name="custom-call(mosaic ...)") == "compute"
    assert classify_op("custom-call.8",
                       long_name="flash_fwd kernel") == "compute"


def test_parse_trace_events_sums_and_union():
    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        _ev("dot_general.1", ts=0, dur=100),
        _ev("wrapped_add", ts=100, dur=50),
        _ev("all-reduce.1", ts=150, dur=30),
        # overlapping op on another thread: union must not double-count
        _ev("wrapped_mul", ts=120, dur=40),
        _ev("ParseArguments", ts=0, dur=999),  # runtime noise: ignored
    ]
    st = parse_trace_events(events)
    assert st.source == "host"
    assert st.n_ops == 4
    assert st.compute_ns == 100_000
    assert st.memory_ns == 90_000
    assert st.collective_ns == 30_000
    assert st.device_time_ns == 180_000  # [0,180) µs union
    assert 0 < st.stall_frac < 1
    assert st.top_ops[0][0] == "dot_general.1"


def test_parse_trace_events_prefers_device_lanes():
    events = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        _ev("fusion.1", ts=0, dur=10, pid=7),
        _ev("wrapped_tanh", ts=0, dur=500, pid=1),  # host shadow: ignored
    ]
    st = parse_trace_events(events)
    assert st.source == "device"
    assert st.n_ops == 1 and st.memory_ns == 10_000


def test_parse_trace_events_takes_an_empty_device_lane_for_no_device():
    """A process that loaded libtpu only to compile for a described chip
    (``tests/test_tpu_compile.py``, in whichever xdist worker) traces an
    empty ``/device:CUSTOM:Megascale Trace`` lane beside its CPU thunks:
    the thunks are what ran."""
    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:CUSTOM:Megascale Trace"}},
        {"ph": "M", "name": "process_name", "pid": 701,
         "args": {"name": "/host:CPU"}},
        _ev("dot_general.1", ts=0, dur=100, pid=701),
    ]
    st = parse_trace_events(events)
    assert st.source == "host"
    assert st.n_ops == 1 and st.compute_ns == 100_000


def test_stall_frac_empty_trace():
    st = TraceStats()
    assert st.stall_frac == 0.0 and st.collective_frac == 0.0


# ---------------------------------------------------------------------------
# Live profiler: real jitted work, real trace (CPU backend in CI)
# ---------------------------------------------------------------------------


def test_profiler_measures_matmul_vs_elementwise():
    """The measured stall fraction separates an MXU-bound program from
    an HBM-bound one — the phase signal the roofline estimate could
    never produce from wall time alone."""
    n = 384
    x = jnp.ones((n, n), jnp.float32)

    @jax.jit
    def matmul_heavy(a):
        for _ in range(8):
            a = a @ a / n
        return a

    @jax.jit
    def elementwise_heavy(a):
        for _ in range(60):
            a = jnp.tanh(a) + 0.1
        return a

    matmul_heavy(x).block_until_ready()  # compile outside the trace
    elementwise_heavy(x).block_until_ready()

    prof = XlaQuantumProfiler()
    _, st_mm = prof.profile(lambda: matmul_heavy(x).block_until_ready())
    _, st_ew = prof.profile(lambda: elementwise_heavy(x).block_until_ready())
    assert st_mm is not None and st_mm.n_ops > 0
    assert st_ew is not None and st_ew.n_ops > 0
    assert st_mm.compute_ns > 0, st_mm.top_ops
    # The elementwise program spends a much larger fraction off the MXU.
    assert st_ew.stall_frac > st_mm.stall_frac + 0.2, (
        st_mm.top_ops, st_ew.top_ops)


def test_profiler_failure_still_returns_result():
    prof = XlaQuantumProfiler()
    out, st = prof.profile(lambda: 41 + 1)
    assert out == 42  # whatever the trace did, the quantum's result lands


# ---------------------------------------------------------------------------
# TpuBackend integration: measured stall_rate changes phase
# ---------------------------------------------------------------------------


def _two_phase_job(name, flip_at, n=256, reps_mm=6, reps_ew=40):
    """A real jitted job that switches from matmul-heavy to
    elementwise-heavy after ``flip_at`` steps (host-side phase switch,
    like a training run entering a data-bound phase)."""

    @jax.jit
    def mm(a):
        for _ in range(reps_mm):
            a = a @ a / n
        return a

    @jax.jit
    def ew(a):
        for _ in range(reps_ew):
            a = jnp.tanh(a) + 0.1
        return a

    state = {"x": jnp.ones((n, n), jnp.float32), "step": 0}
    mm(state["x"]).block_until_ready()
    ew(state["x"]).block_until_ready()

    def step_fn(st):
        fn = mm if st["step"] < flip_at else ew
        return {"x": fn(st["x"]), "step": st["step"] + 1}

    return Job(name, step_fn=step_fn, state=state,
               params=SchedParams(tslice_us=100))


def test_measured_stall_rate_changes_phase_under_tpu_backend():
    be = TpuBackend(profile_every=2)
    part = Partition("p", source=be)
    job = part.add_job(_two_phase_job("two-phase", flip_at=6))

    stalls = []
    for _ in range(12):
        part.run(max_rounds=1)
        m = be.measured("two-phase")
        if m is not None:
            stalls.append(m.stall_frac)
    assert be.profiler.samples >= 2, be.profiler.last_error
    # Early samples (matmul phase) vs late samples (elementwise phase).
    assert stalls[-1] > stalls[0] + 0.2, stalls
    # The ledger counters reflect the measured stall, not a constant.
    ctx = job.contexts[0]
    assert int(ctx.counters[Counter.HBM_STALL_NS]) > 0


def test_feedback_policy_reacts_to_phase_change_virtual_clock():
    """Tier-1 sibling of the real-timing test below, on the simulated
    backend: the SAME assertions (stall_rate crosses the 10%-stalled
    grow/shrink threshold when the program's phase flips, the policy
    ticks) driven from a deterministic two-phase SimProfile instead of
    live XLA traces — host load cannot move the verdict."""
    from pbs_tpu.sched.feedback import FeedbackPolicy
    from pbs_tpu.telemetry.source import SimBackend, SimPhase, SimProfile

    be = SimBackend()
    part = Partition("p", source=be)
    fb = FeedbackPolicy(part, tick_ns=1)  # tick every quantum boundary
    prof = SimProfile([
        # Phase A: MXU-dominant -> stall well under the threshold.
        # 5 steps at one 100 us step per 100 us quantum = the flip
        # lands mid-run exactly like the live test's flip_at=5.
        SimPhase(steps=5, step_time_ns=100_000, stall_frac=0.02,
                 collective_wait_ns=500),
        # Phase B: HBM-bound -> stall_rate rises sharply past it.
        SimPhase(steps=-1, step_time_ns=100_000, stall_frac=0.5,
                 collective_wait_ns=500),
    ])
    be.register("fb", prof)
    job = Job("fb", params=SchedParams(tslice_us=100))
    job.contexts[0].avg_step_ns = 100_000
    part.add_job(job)

    rates = []
    for _ in range(10):
        part.run(max_rounds=1)
        rates.append(job.stall_rate)
    early, late = rates[2], rates[-1]
    assert late > early, rates
    assert late >= 100.0, rates  # crosses the policy threshold
    st = fb.state_of(job)
    assert st.ticks > 0


@pytest.mark.slow
def test_feedback_policy_reacts_to_measured_phase_change():
    """FeedbackPolicy against TpuBackend (verdict #3 'done' bar): the
    job's stall_rate must actually move when the program's phase flips,
    crossing the 10%-stalled threshold that separates grow from
    shrink (sched_credit.c:360-369 analog).

    ``slow``: the measured stall fractions come from REAL wall-clock
    XLA traces; on a loaded 1-vCPU CI box the host jitter can swamp
    the phase signal (documented flaky at PR 12 HEAD — 2/2 identical
    failures on a clean worktree under load). The virtual-clock
    sibling above keeps the policy-reacts contract in tier-1."""
    be = TpuBackend(profile_every=1)
    part = Partition("p", source=be)
    fb = FeedbackPolicy(part, tick_ns=1)  # tick every quantum boundary
    job = part.add_job(_two_phase_job("fb", flip_at=5))

    rates = []
    for _ in range(10):
        part.run(max_rounds=1)
        rates.append(job.stall_rate)
    early, late = rates[2], rates[-1]
    # Phase A: MXU-dominant -> measured stall small. Phase B: HBM-bound
    # -> stall_rate rises sharply (units: per-mille of device time).
    assert late > early, rates
    assert late >= 100.0, rates  # crosses the policy threshold
    st = fb.state_of(job)
    assert st.ticks > 0
