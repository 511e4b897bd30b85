"""Compilation-aware admission + per-job compile attribution.

Verdict #10 'done' bar: admitting N distinct programs on one partition
reports compile-time attribution per job, and admission gates on
projected compile-cache pressure. The scarce resource is TPU-new
(SURVEY.md §7 — Xen guests don't JIT kernels); the admission shape
copies the reference's fail-fast memory claims (XENMEM_claim_pages).
"""

import time

import jax
import jax.numpy as jnp
import pytest

from pbs_tpu.obs import trace as obs_trace
from pbs_tpu.runtime.compile_gate import (
    CompileAdmission,
    CompileBudget,
    CompileBudgetExceeded,
)
from pbs_tpu.runtime.job import Job
from pbs_tpu.runtime.partition import Partition
from pbs_tpu.telemetry.compile import CompileMeter
from pbs_tpu.telemetry.counters import Counter
from pbs_tpu.telemetry.source import TpuBackend


def _host_compiles(since_ns: int, scope: str) -> list[list[int]]:
    """HOST_COMPILE records of ``scope`` that began since ``since_ns``:
    [ts, event, kind, wall_ns, function tag, scope tag, cache, ...]."""
    ring = obs_trace.host_ring()
    recs = ring.peek(ring.capacity).astype("int64")
    return [r for r in recs[recs[:, 1] == int(obs_trace.Ev.HOST_COMPILE)]
            .tolist() if r[0] >= since_ns
            and r[5] == obs_trace.job_tag(scope)]


def _distinct_program_job(name: str, scale: float, size: int = 64) -> Job:
    """Each (scale, size) pair jits a DISTINCT program — different
    constants folded in, so the compile cache can't share entries."""

    @jax.jit
    def step(x):
        return jnp.tanh(x * scale) + 1.0 / (size + scale)

    return Job(name, step_fn=step, state=jnp.ones((size, size)), max_steps=2)


def test_compile_attribution_per_job():
    """N distinct programs -> each job's ledger shows ITS compile count
    and a positive compile time (the 'done' bar sentence)."""
    be = TpuBackend()
    part = Partition("p", source=be)
    jobs = [part.add_job(_distinct_program_job(f"prog{i}", 1.0 + i,
                                               size=64 + 8 * i))
            for i in range(3)]
    part.run(max_rounds=20)
    for job in jobs:
        ctx = job.contexts[0]
        assert int(ctx.counters[Counter.COMPILES]) >= 1, job.name
        assert int(ctx.counters[Counter.COMPILE_TIME_NS]) > 0, job.name
    # Distinct programs: each job paid for its own compilation —
    # attribution is per-job, not pooled on the first job.
    total = sum(int(j.contexts[0].counters[Counter.COMPILES]) for j in jobs)
    assert total >= 3


def test_compile_ns_of_nested_jits_is_inside_its_call():
    """``take()`` never exceeds the wall of the call it was attributed
    in (PERF.md section 7 row 0d). JAX reports a jit traced inside a
    jit under its own event and again inside the outer one: the
    durations summed passed the call's wall, and
    ``EXEC_STEP.dispatch_ns`` was clipped to 0."""
    def body(x):
        for _ in range(30):  # a new jit each: none finds a cached trace
            x = jax.jit(lambda y: jnp.sin(y) * 1.0173)(x)
        return x

    meter = CompileMeter.install()
    t0 = time.monotonic_ns()
    with meter.attribute("meter-nested"):
        jax.block_until_ready(jax.jit(body)(jnp.ones((16, 16))))
    wall = time.monotonic_ns() - t0
    n, ns = meter.take("meter-nested")
    assert n >= 1 and 0 < ns <= wall, (n, ns, wall)
    # On the ring as in the sums: one HOST_COMPILE an outermost event,
    # the thirty inner jits inside the outer trace's record and not
    # beside it, and the records' walls are what take() summed.
    recs = _host_compiles(t0, "meter-nested")
    names = [obs_trace.tag_name(r[4]) for r in recs]
    assert "body" in names and "jit(body)" in names
    assert not [x for x in names if "lambda" in x], names
    assert sum(r[3] for r in recs) == ns
    assert sum(1 for r in recs if r[2] == 2) <= n


def test_a_harvest_compile_is_not_in_the_first_steps_wall():
    """A foreign tenant's executable is compiled by the backend before
    its first step (``_job_cost``): the job pays for that compile, the
    step's record and charge do not hold it."""
    job = Job.foreign("meter-foreign", jax.jit(lambda x: jnp.tanh(x * 1.0391)),
                      jnp.ones((32, 32)), max_steps=1)
    be = TpuBackend(profile_every=0)
    t_made = time.monotonic_ns()
    be._job_cost(job)
    t0 = time.monotonic_ns()
    dt, _metrics, n, ns = be._invoke(job, job.step_fn)
    wall = time.monotonic_ns() - t0
    _ts, _ev, _slot, dispatch, wait, compile_ns, _tag, _ = \
        be.trace.peek().astype("int64").tolist()[-1]
    assert n >= 1 and ns > compile_ns >= 0
    assert min(dispatch, wait, dt) >= 0
    assert dispatch + compile_ns + wait <= wall
    # On the ring: the harvest's records carry the job's scope and end
    # before the step's span begins; the span's compile wall is the
    # step record's.
    recs = _host_compiles(t_made, "meter-foreign")
    harvest = [r for r in recs if r[0] < t0]
    assert {r[2] for r in harvest} == {0, 1, 2}
    assert all(r[0] + r[3] <= t0 for r in harvest)
    assert sum(r[3] for r in recs if r[0] >= t0) == compile_ns
    assert ns == sum(r[3] for r in recs)


def test_compile_time_excluded_from_runtime_charge():
    """First-dispatch jit cost must not be billed as device time — a
    tenant whose first quantum compiles for seconds would sink into
    credit debt and starve behind its neighbors (found by the
    co-located continuous-batching drive). Compile spend lives in its
    own counters; DEVICE_TIME_NS reflects execution only."""
    be = TpuBackend()
    part = Partition("p", source=be)
    job = part.add_job(_distinct_program_job("firstcomp", 3.14, size=96))
    part.run(max_rounds=1)  # the compiling quantum
    ctx = job.contexts[0]
    dev = int(ctx.counters[Counter.DEVICE_TIME_NS])
    comp = int(ctx.counters[Counter.COMPILE_TIME_NS])
    assert comp > 0
    # execution of a 96x96 tanh is far cheaper than its compilation;
    # had compile leaked into the runtime charge, dev would dwarf it
    assert dev < comp, (dev, comp)
    # and the measured step-time estimate stays execution-sized, so
    # the scheduler's quantum->steps conversion isn't poisoned either
    assert ctx.avg_step_ns < comp


def test_cached_program_does_not_recharge():
    """Steps after the first reuse the compiled program: compile
    counters stop growing (the cache hit is visible as absence)."""
    be = TpuBackend()
    part = Partition("p", source=be)
    job = part.add_job(_distinct_program_job("once", 7.7))
    part.run(max_rounds=1)
    after_first = int(job.contexts[0].counters[Counter.COMPILE_TIME_NS])
    part.run(max_rounds=10)
    assert int(job.contexts[0].counters[Counter.COMPILE_TIME_NS]) == (
        after_first)
    assert int(job.contexts[0].counters[Counter.STEPS_RETIRED]) == 2


def test_admission_gates_on_program_count():
    be = TpuBackend()
    gate = CompileAdmission(CompileBudget(max_programs=2))
    part = Partition("p", source=be, compile_admission=gate)
    part.add_job(_distinct_program_job("a", 1.1))
    part.add_job(_distinct_program_job("b", 2.2))
    with pytest.raises(CompileBudgetExceeded, match="thrash"):
        part.add_job(_distinct_program_job("c", 3.3))
    assert gate.rejections == 1
    # rejection left nothing behind: removing one admits the next
    part.remove_job(part.job("a"))
    part.add_job(_distinct_program_job("c", 3.3))
    assert sorted(gate.programs) == ["b", "c"]


def test_admission_respects_declared_program_count():
    gate = CompileAdmission(CompileBudget(max_programs=4))
    part = Partition("p", source=TpuBackend(), compile_admission=gate)
    part.add_job(Job("multi", step_fn=lambda s: s, state=0, n_programs=3,
                     max_steps=1))
    with pytest.raises(CompileBudgetExceeded):
        part.add_job(Job("big", step_fn=lambda s: s, state=0, n_programs=2,
                         max_steps=1))
    part.add_job(Job("fits", step_fn=lambda s: s, state=0, n_programs=1,
                     max_steps=1))


def test_admission_gates_on_time_budget_with_observed_mean():
    """Once measured compile data exists, projections use the observed
    mean — a partition near its compile-time budget rejects programs
    it can no longer afford."""
    meter = CompileMeter.install()
    gate = CompileAdmission(CompileBudget(budget_ns=1), meter=meter)
    part = Partition("p", source=TpuBackend(), compile_admission=gate)
    gate.charge("ghost", 0)  # no-op: unknown job ignored
    first = _distinct_program_job("first", 9.9)
    first.est_compile_ns = 0  # declared-free: admitted despite budget
    part.add_job(first)
    part.run(max_rounds=5)  # first job compiles; MEASURED spend charged
    assert gate.spent_ns.get("first", 0) > 0
    # Now committed spend alone exceeds the budget, and the undeclared
    # second job projects via the observed fleet mean (> 0 after any
    # real compile in this process) — rejected on measured evidence.
    assert meter.mean_compile_ns > 0
    with pytest.raises(CompileBudgetExceeded, match="budget"):
        part.add_job(_distinct_program_job("second", 10.1))


def test_budget_holds_reservations_before_any_compile():
    """The claim is HELD: two projected-8s jobs cannot both fit a 10s
    budget just because neither has compiled yet (review finding)."""
    gate = CompileAdmission(CompileBudget(budget_ns=10_000))
    part = Partition("p", source=TpuBackend(), compile_admission=gate)
    part.add_job(Job("a", step_fn=lambda s: s, state=0,
                     est_compile_ns=8_000, max_steps=1))
    with pytest.raises(CompileBudgetExceeded):
        part.add_job(Job("b", step_fn=lambda s: s, state=0,
                         est_compile_ns=8_000, max_steps=1))
    assert gate.committed_ns() == 8_000
    part.remove_job(part.job("a"))  # release frees the reservation
    assert gate.committed_ns() == 0
    part.add_job(Job("b", step_fn=lambda s: s, state=0,
                     est_compile_ns=8_000, max_steps=1))


def test_declared_estimate_overrides_mean():
    gate = CompileAdmission(CompileBudget(budget_ns=1_000_000))
    part = Partition("p", source=TpuBackend(), compile_admission=gate)
    with pytest.raises(CompileBudgetExceeded):
        part.add_job(Job("honest", step_fn=lambda s: s, state=0,
                         est_compile_ns=2_000_000, max_steps=1))
    part.add_job(Job("cheap", step_fn=lambda s: s, state=0,
                     est_compile_ns=10_000, max_steps=1))


def test_dump_surface():
    gate = CompileAdmission(CompileBudget(max_programs=8, budget_ns=10**12))
    part = Partition("p", source=TpuBackend(), compile_admission=gate)
    part.add_job(_distinct_program_job("d", 5.5))
    d = gate.dump()
    assert d["programs_held"] == {"d": 1}
    assert d["max_programs"] == 8 and d["rejections"] == 0
