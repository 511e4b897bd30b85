"""Telemetry sources: who fills the per-job counter deltas.

The reference stacks a low-level CPU driver (``perfctr.c``: family detect,
MSR programming, rdpmc sampling) under a virtualization module
(``pmustate.c``) that snapshots counters at every context switch. The TPU
has no public per-tenant PMC file (SURVEY.md §7 "hard parts"), so we keep
the same seam as a ``TelemetrySource`` protocol with two backends:

- ``SimBackend`` — deterministic, host-only synthetic workloads: the
  fake-backend pattern of ``tools/tests/x86_emulator`` (compile the policy
  against mocked hardware and test it as a normal program). Every
  scheduler/policy test in ``tests/`` runs against this.
- ``TpuBackend`` — real measurements: step wall time (device-synchronised),
  XLA cost analysis per compiled executable (FLOPs, HBM bytes), measured
  per-op time from periodic XLA-profiler samples (``profiler.py`` — the
  rdpmc-read analog, ``perfctr.c:1547-1573``) with a roofline HBM-stall
  estimate as the cold-start fallback, and in-graph metrics the job's
  step function returns to the host (collective wait — the batched
  ``vcrd_op`` analog, ``sched_credit.c:249-259``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Protocol

import numpy as np

from pbs_tpu.faults import injector as _faults
from pbs_tpu.telemetry.counters import NUM_COUNTERS, Counter
from pbs_tpu.utils.clock import Clock, MonotonicClock, VirtualClock

#: Channels a ``telemetry.counters`` 'stall' fault freezes: the
#: PMC-grade measurements a dead readout stops delivering. Progress
#: counters (STEPS_RETIRED, TOKENS, YIELDS) are runtime-observed — the
#: job really ran — so a stalled readout must NOT erase progress; that
#: split is exactly what lets the feedback policy *detect* staleness
#: (steps advanced, device time didn't) and stop steering on it.
_STALLABLE = (Counter.DEVICE_TIME_NS, Counter.HBM_BYTES,
              Counter.HBM_STALL_NS, Counter.COLLECTIVE_WAIT_NS,
              Counter.DEVICE_FLOPS)

#: Channels a 'spike' fault multiplies: the noisy-counter adversity the
#: feedback policy's stability window must absorb (PAPER.md's "counter
#: noise" premise) — rate inputs only, never progress.
_SPIKABLE = (Counter.HBM_STALL_NS, Counter.COLLECTIVE_WAIT_NS)

# Plain-int counter indices for the quantum hot loop: indexing numpy
# with an IntEnum pays an __index__ round trip per store.
_I_DEV = int(Counter.DEVICE_TIME_NS)
_I_HBM = int(Counter.HBM_BYTES)
_I_STALL = int(Counter.HBM_STALL_NS)
_I_COLL = int(Counter.COLLECTIVE_WAIT_NS)
_I_FLOPS = int(Counter.DEVICE_FLOPS)
_I_STEPS = int(Counter.STEPS_RETIRED)
_I_TOKENS = int(Counter.TOKENS)


def apply_counter_faults(job_name: str, deltas: np.ndarray) -> np.ndarray:
    """``telemetry.counters`` injection seam (stream key = job name),
    shared by every backend: consult once per execute call, mutate the
    delta vector in place. No injector installed = one global load."""
    f = _faults.consult("telemetry.counters", job_name)
    if f is None:
        return deltas
    if f.fault == "stall":
        for c in _STALLABLE:
            deltas[c] = 0
    elif f.fault == "spike":
        factor = float(f.args.get("factor", 10.0))
        for c in _SPIKABLE:
            deltas[c] = np.uint64(int(deltas[c]) * factor)
    return deltas


class TelemetrySource(Protocol):
    """Executes one quantum of a job's work and reports counter deltas."""

    clock: Clock

    def execute(self, ctx: Any, n_steps: int) -> np.ndarray:
        """Run ``n_steps`` steps of ``ctx.job`` and return u64 deltas
        (length NUM_COUNTERS)."""
        ...

    def execute_micro(self, ctx: Any, n_micro: int) -> np.ndarray:
        """Run ``n_micro`` micro-steps (1/``job.micro_per_step`` of a
        step each), advancing ``ctx.micro_progress`` and retiring a full
        step on each wrap. Lets the executor deschedule a long-step job
        mid-step at a chunk boundary (sub-step latency bounding)."""
        ...


# ---------------------------------------------------------------------------
# Simulation backend
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SimPhase:
    """One behavioral phase of a synthetic workload.

    Lets tests reproduce the reference's phase transitions: e.g. a guest
    moving between cache-friendly and cache-thrashing phases, which the
    windowed filter at ``sched_credit.c:302-389`` must track.
    """

    steps: int  # phase length in steps (last phase may be -1 = forever)
    step_time_ns: int = 1_000_000  # device time per step
    hbm_bytes: int = 1 << 20
    stall_frac: float = 0.1  # fraction of step time stalled on HBM
    collective_wait_ns: int = 0  # spin-latency analog per step
    flops: int = 1 << 30
    tokens: int = 0
    # Relative half-width of per-step noise on step time and collective
    # wait (0.1 = ±10%). Drawn from the backend's own seeded Generator —
    # never module-level RNG state — so runs replay bit-for-bit.
    jitter: float = 0.0


@dataclasses.dataclass
class SimProfile:
    phases: list[SimPhase]

    def phase_at(self, step: int) -> SimPhase:
        s = step
        for ph in self.phases:
            if ph.steps < 0 or s < ph.steps:
                return ph
            s -= ph.steps
        return self.phases[-1]

    @staticmethod
    def steady(**kw) -> "SimProfile":
        return SimProfile([SimPhase(steps=-1, **kw)])


class SimBackend:
    """Deterministic synthetic telemetry; advances a VirtualClock.

    Jobs registered here need no real step function — the backend *is*
    the device. This is the CPU-CI substrate mandated by SURVEY.md §4.

    Every stochastic choice (phase jitter) routes through explicit
    seeded ``np.random.Generator``s — one per job, keyed (seed, job
    name) and advanced only by that job's own execution. Two backends
    built with the same seed produce byte-identical telemetry (the
    ``pbs_tpu.sim`` trace-digest determinism gate), and the noise a job
    experiences is a function of its own step sequence alone, not of
    scheduler dispatch order — so policy comparisons over the same
    (workload, seed) are noise-controlled.
    """

    def __init__(self, clock: VirtualClock | None = None, seed: int = 0):
        self.clock: VirtualClock = clock or VirtualClock()
        self.seed = int(seed)
        self._rngs: dict[str, np.random.Generator] = {}
        self._profiles: dict[str, SimProfile] = {}
        self._steps_done: dict[str, int] = {}
        # Single-infinite-phase profiles (most of the sim catalog)
        # resolved once at register time: the quantum hot loop then
        # skips the per-step phase_at() schedule walk.
        self._steady: dict[str, SimPhase | None] = {}

    def _rng_for(self, job_name: str) -> np.random.Generator:
        rng = self._rngs.get(job_name)
        if rng is None:
            import zlib

            rng = self._rngs[job_name] = np.random.default_rng(
                [self.seed, zlib.crc32(job_name.encode())])
        return rng

    @staticmethod
    def _jittered(rng: np.random.Generator, value: int,
                  jitter: float) -> int:
        """±jitter noise on ``value`` via the job's seeded Generator."""
        if jitter <= 0.0 or value <= 0:
            return value
        return max(1, int(value * (1.0 + jitter * (2.0 * rng.random() - 1.0))))

    def register(self, job_name: str, profile: SimProfile) -> None:
        self._profiles[job_name] = profile
        self._steps_done[job_name] = 0  # fresh phase schedule per register
        phases = profile.phases
        self._steady[job_name] = (
            phases[0] if len(phases) == 1 and phases[0].steps < 0 else None)

    def seek(self, job_name: str, steps_done: int) -> None:
        """Reposition the phase schedule — migration restore lands a job
        mid-profile instead of replaying it from phase zero."""
        self._steps_done[job_name] = int(steps_done)

    def position(self, job_name: str) -> int:
        """Current phase-schedule cursor (the save-side peer of
        :meth:`seek`)."""
        return self._steps_done.get(job_name, 0)

    def _charge_phase(self, deltas: np.ndarray, ph: SimPhase,
                      k: int, rng: np.random.Generator) -> int:
        """Advance the clock by 1/k of the phase's step and charge the
        proportional traffic; returns the advanced nanoseconds."""
        t = self._jittered(rng, max(1, ph.step_time_ns // k), ph.jitter)
        self.clock.advance(t)
        deltas[Counter.DEVICE_TIME_NS] += t
        deltas[Counter.HBM_BYTES] += ph.hbm_bytes // k
        deltas[Counter.HBM_STALL_NS] += int(t * ph.stall_frac)
        deltas[Counter.COLLECTIVE_WAIT_NS] += self._jittered(
            rng, ph.collective_wait_ns // k, ph.jitter)
        deltas[Counter.DEVICE_FLOPS] += ph.flops // k
        return t

    def execute(self, ctx: Any, n_steps: int) -> np.ndarray:
        # The quantum hot loop (pbst perf: sim.smoke / sim.sustained):
        # accumulate in plain Python ints and store each counter ONCE
        # per quantum instead of paying a numpy scalar read-modify-write
        # per counter per step. RNG draw order (step-time draw, then
        # collective draw iff wait>0 — exactly _jittered's skip rule)
        # and all integer rounding match _charge_phase bit-for-bit, so
        # trace digests and golden chaos digests are unchanged.
        name = ctx.job.name
        rng = self._rngs.get(name)
        if rng is None:
            rng = self._rng_for(name)
        random = rng.random
        step = self._steps_done[name]
        steady = self._steady[name]
        t_tot = hbm = stall = coll = flops = tokens = 0
        if steady is not None:
            # Steady single-phase tenant (most of the catalog): phase
            # fields resolve to locals once per quantum, and the
            # per-step loop specializes on (jitter, collective) so it
            # draws exactly the randoms _jittered would — stream and
            # rounding identical to the general path below.
            base = steady.step_time_ns
            if base < 1:
                base = 1
            jit = steady.jitter
            frac = steady.stall_frac
            cw = steady.collective_wait_ns
            hbm = steady.hbm_bytes * n_steps
            flops = steady.flops * n_steps
            tokens = steady.tokens * n_steps
            if jit > 0.0:
                if n_steps >= 8:
                    # Long quantum: one batched draw + vectorized
                    # jitter. Generator.random(n) consumes the exact
                    # bit stream of n scalar random() calls (pinned by
                    # tests/test_sim_trace.py digests), and every
                    # float64 op below mirrors the scalar expression
                    # tree, so totals are bit-identical.
                    if cw > 0:
                        r = random(2 * n_steps)
                        rt, rc = r[0::2], r[1::2]
                    else:
                        rt, rc = random(n_steps), None
                    t = (base * (1.0 + jit * (2.0 * rt - 1.0))) \
                        .astype(np.int64)
                    np.maximum(t, 1, out=t)
                    t_tot = int(t.sum())
                    stall = int((t * frac).astype(np.int64).sum())
                    if rc is not None:
                        c = (cw * (1.0 + jit * (2.0 * rc - 1.0))) \
                            .astype(np.int64)
                        np.maximum(c, 1, out=c)
                        coll = int(c.sum())
                elif cw > 0:
                    for _ in range(n_steps):
                        t = int(base * (1.0 + jit * (2.0 * random() - 1.0)))
                        if t < 1:
                            t = 1
                        c = int(cw * (1.0 + jit * (2.0 * random() - 1.0)))
                        if c < 1:
                            c = 1
                        t_tot += t
                        stall += int(t * frac)
                        coll += c
                else:
                    for _ in range(n_steps):
                        t = int(base * (1.0 + jit * (2.0 * random() - 1.0)))
                        if t < 1:
                            t = 1
                        t_tot += t
                        stall += int(t * frac)
            else:
                t_tot = base * n_steps
                stall = int(base * frac) * n_steps
                coll = cw * n_steps
            step += n_steps
        else:
            prof = self._profiles[name]
            for _ in range(n_steps):
                ph = prof.phase_at(step)
                jit = ph.jitter
                t = ph.step_time_ns
                if t < 1:
                    t = 1
                if jit > 0.0:
                    t = int(t * (1.0 + jit * (2.0 * random() - 1.0)))
                    if t < 1:
                        t = 1
                c = ph.collective_wait_ns
                if c > 0 and jit > 0.0:
                    c = int(c * (1.0 + jit * (2.0 * random() - 1.0)))
                    if c < 1:
                        c = 1
                t_tot += t
                hbm += ph.hbm_bytes
                stall += int(t * ph.stall_frac)
                coll += c
                flops += ph.flops
                tokens += ph.tokens
                step += 1
        self._steps_done[name] = step
        self.clock.advance(t_tot)
        deltas = np.zeros(NUM_COUNTERS, dtype=np.uint64)
        deltas[_I_DEV] = t_tot
        deltas[_I_HBM] = hbm
        deltas[_I_STALL] = stall
        deltas[_I_COLL] = coll
        deltas[_I_FLOPS] = flops
        deltas[_I_STEPS] = n_steps
        deltas[_I_TOKENS] = tokens
        if _faults._active is not None:
            return apply_counter_faults(name, deltas)
        return deltas

    def execute_micro(self, ctx: Any, n_micro: int) -> np.ndarray:
        """Micro-step execution: each unit burns 1/K of the phase's step
        time and traffic; a step retires (and its tokens land) when the
        micro cursor wraps. Ending a quantum mid-step records a YIELD —
        the voluntary early exit the latency bound relies on."""
        name = ctx.job.name
        K = ctx.job.micro_per_step
        prof = self._profiles[name]
        rng = self._rng_for(name)
        deltas = np.zeros(NUM_COUNTERS, dtype=np.uint64)
        for _ in range(n_micro):
            step = self._steps_done[name]
            ph = prof.phase_at(step)
            self._charge_phase(deltas, ph, K, rng)
            ctx.micro_progress += 1
            if ctx.micro_progress >= K:
                ctx.micro_progress = 0
                deltas[Counter.STEPS_RETIRED] += 1
                deltas[Counter.TOKENS] += ph.tokens
                self._steps_done[name] = step + 1
        if ctx.micro_progress:
            deltas[Counter.YIELDS] += 1
        return apply_counter_faults(name, deltas)


# ---------------------------------------------------------------------------
# TPU backend
# ---------------------------------------------------------------------------


def cost_analysis_of(compiled) -> tuple[int, int]:
    """(flops, hbm_bytes) from an XLA compiled executable."""
    ca = compiled.cost_analysis()
    return int(ca.get("flops", 0.0)), int(ca.get("bytes accessed", 0.0))


#: What every invocation of a job but its first enters in the place of
#: an ``exec.first`` span.
_NOT_FIRST = contextlib.nullcontext()


class TpuBackend:
    """Measures real jobs: wall time + XLA cost analysis + in-graph metrics.

    A job's ``step_fn(state) -> state`` may instead return
    ``(state, metrics)`` where ``metrics`` is a dict of scalars; the key
    ``collective_wait_ns`` feeds the contention channel (batched per step
    — deliberately NOT per-event, fixing the reference's hypercall storm
    noted at SURVEY.md §3.5).
    """

    def __init__(
        self,
        clock: Clock | None = None,
        peak_flops: float | None = None,
        peak_hbm_bw: float | None = None,
        profile_every: int = 0,
        profiler=None,
    ):
        self.clock = clock or MonotonicClock()
        # Roofline peaks: an explicit argument wins; otherwise the
        # device's own entry in the peak table (telemetry/peaks.py —
        # an unlisted TPU kind raises here, at construction).
        if peak_flops is None or peak_hbm_bw is None:
            from pbs_tpu.telemetry.peaks import device_peaks

            peaks = device_peaks()
            if peak_flops is None:
                peak_flops = peaks.flops
            if peak_hbm_bw is None:
                peak_hbm_bw = peaks.hbm_bw
        self.peak_flops = peak_flops
        self.peak_hbm_bw = peak_hbm_bw
        # per-job (flops, bytes) from cost analysis, captured at first run
        self._costs: dict[str, tuple[int, int]] = {}
        #: Executables whose cost analysis the backend refused. Such a
        #: job runs without a roofline stall estimate, so the count is
        #: kept where a caller (chip_smoke.py) can require it to be 0.
        self.cost_failures = 0
        self.last_cost_error: str | None = None
        # Measured-telemetry sampling: every N-th invocation per job runs
        # under the XLA profiler; the parsed per-op time fills the stall/
        # collective counters and its fractions carry forward until the
        # next sample. 0 = roofline-estimate only (round-1 behavior).
        self.profile_every = int(profile_every)
        if profiler is None and self.profile_every > 0:
            from pbs_tpu.telemetry.profiler import XlaQuantumProfiler

            profiler = XlaQuantumProfiler()
        self.profiler = profiler
        self._measured: dict[str, Any] = {}  # job name -> TraceStats
        self._since_profile: dict[str, int] = {}
        # Per-job compile attribution (telemetry.compile): every
        # invocation runs in the job's attribution scope, so first-call
        # jit compilation lands in ITS ledger slots, not nowhere.
        from pbs_tpu.telemetry.compile import CompileMeter

        self.compile_meter = CompileMeter.install()
        # Flight recorder (docs/TRACING.md): one EXEC_STEP per invoked
        # unit. ``_emit_step(ctx, ts_ns, event, *args)`` is the driver's
        # (a Partition binds its own rings, so the record lands between
        # the quantum's SCHED_PICK and SCHED_DESCHED); unbound, the
        # backend's own ring, made on the first step.
        self._emit_step: Callable[..., None] | None = None
        self.trace = None
        # Imported here: obs/__init__ reaches back into telemetry.
        from pbs_tpu.obs import trace as obs_trace

        self._obs = obs_trace
        # Full collections and compiles, beside the steps; a job's
        # first invocation is an ``exec.first`` span there.
        obs_trace.host_ring()
        self._invoked: set[str] = set()

    def bind_trace(self, emit: Callable[..., None]) -> None:
        """Hand the backend its driver's ring: ``emit(ctx, ts_ns,
        event, *args)``."""
        self._emit_step = emit

    def _own_ring(self) -> Callable[..., None]:
        self.trace = ring = self._obs.TraceBuffer()
        self._obs.register_ring("exec", ring)
        self._emit_step = lambda _ctx, ts, ev, *a: ring.emit(ts, ev, *a)
        return self._emit_step

    def _job_cost(self, job) -> tuple[int, int]:
        c = self._costs.get(job.name)
        if c is None:
            compiled = getattr(job, "compiled", None)
            if compiled is None and getattr(job, "_foreign_spec", None):
                # Foreign tenant (Job.foreign): harvest the executable
                # from the jit wrapper without the workload's help —
                # the MSR-interception analog (vpmu_core2.c:367-418
                # reads the guest's counter MSRs; here we read the
                # guest's XLA cost analysis). Attributed compile spend
                # lands in the job's own COMPILE_* counters.
                fn, a, k = job._foreign_spec
                # A callable with no .lower is not a jit stage: it gets
                # profiler telemetry only. A jit stage that fails to
                # lower or compile is the tenant's fault and propagates
                # (the executor contains it to the job).
                if hasattr(fn, "lower"):
                    with self.compile_meter.attribute(job.name):
                        compiled = fn.lower(*a, **k).compile()
                    job.compiled = compiled
            c = (0, 0)
            if compiled is not None:
                try:
                    c = cost_analysis_of(compiled)
                except Exception as e:  # noqa: BLE001 — counted, see init
                    self.cost_failures += 1
                    self.last_cost_error = f"{type(e).__name__}: {e}"
            self._costs[job.name] = c
        return c

    _METRIC_KEYS = (
        ("collective_wait_ns", Counter.COLLECTIVE_WAIT_NS),
        ("gang_skew_ns", Counter.GANG_SKEW_NS),
        ("tokens", Counter.TOKENS),
        ("spec_proposed", Counter.SPEC_PROPOSED),
    )

    def measured(self, job_name: str):
        """Latest measured TraceStats for a job (None before the first
        profiler sample, or with profiling disabled)."""
        return self._measured.get(job_name)

    def _profile_due(self, job) -> bool:
        # Per-job override first (foreign tenants carry their own
        # sampling period so they get measured phases even when the
        # backend-wide default is roofline-only).
        every = getattr(job, "profile_every", None) or self.profile_every
        if not every:
            return False
        if self.profiler is None:
            from pbs_tpu.telemetry.profiler import XlaQuantumProfiler

            self.profiler = XlaQuantumProfiler()
        k = self._since_profile.get(job.name, every)
        due = k >= every  # first invocation profiles
        self._since_profile[job.name] = 1 if due else k + 1
        return due

    def _invoke(self, job, fn, ctx=None) -> tuple[int, dict, int, int]:
        """Run one host-callable unit; returns (run_ns, metrics,
        n_compiles, compile_ns). Compilation time is split OUT of the
        runtime charge: a tenant's first-dispatch jit cost (seconds)
        billed as device time would sink it into deep credit debt and
        starve it for the equivalent share — compile spend is tracked
        in its own counters and governed by the admission budget
        (runtime/compile_gate.py), not by the runtime scheduler.

        Leaves one ``EXEC_STEP`` record: how long ``fn`` took to return
        (the dispatch: for a jitted step, until the program is
        enqueued; compile time taken out, as in the charge) and how
        long ``block_until_ready`` then waited. The compile time of the
        record and of the charge is what the meter saw inside this call:
        a wall time of work done before ``fn`` returned, so neither
        difference below can go negative."""
        import jax

        t_returned = 0

        def run():
            nonlocal t_returned
            out = fn(job.state)
            t_returned = time.monotonic_ns()
            metrics: dict[str, float] = {}
            if (isinstance(out, tuple) and len(out) == 2
                    and isinstance(out[1], dict)):
                st, metrics = out
            else:
                st = out
            # A device error (an OOM, a failed execution) surfaces
            # here and must reach the executor: a swallowed one is a
            # step that "ran".
            jax.block_until_ready(st)
            return st, metrics

        # Compiled for this job outside a step (a foreign tenant's
        # executable, harvested by _job_cost): the job's to pay for,
        # not part of this call's wall.
        n_before, ns_before = self.compile_meter.take(job.name)
        # A job's first invocation, whole: what it traces, lowers and
        # loads is in its HOST_COMPILE records, under the job's name;
        # the span's wall less compile is the first execution.
        first = _NOT_FIRST
        if job.name not in self._invoked:
            self._invoked.add(job.name)
            first = self._obs.host_phase(
                "exec.first", ctx.ledger_slot if ctx is not None else 0,
                scope=job.name)
        t0 = time.monotonic_ns()
        with first, self.compile_meter.attribute(job.name), \
                jax.profiler.TraceAnnotation("pbst.exec.step"):
            if self._profile_due(job):
                (job.state, metrics), stats = self.profiler.profile(run)
                if stats is not None and stats.n_ops:
                    self._measured[job.name] = stats
            else:
                job.state, metrics = run()
        t1 = time.monotonic_ns()
        n_c, c_ns = self.compile_meter.take(job.name)
        (self._emit_step or self._own_ring())(
            ctx, t0, self._obs.Ev.EXEC_STEP,
            ctx.ledger_slot if ctx is not None else -1,
            t_returned - t0 - c_ns, t1 - t_returned, c_ns,
            self._obs.job_tag(job.name))
        return (t1 - t0 - c_ns, metrics, n_c + n_before,
                c_ns + ns_before)

    def _charge(self, deltas: np.ndarray, dt: int, flops: int,
                nbytes: int, metrics: dict, measured=None) -> None:
        # In-graph instrumented kernels (ops.matmul emits its own tile/
        # byte counters, PMC-style) outrank the static cost-analysis
        # estimate for the same quantity.
        flops = int(metrics.get("device_flops", flops))
        nbytes = int(metrics.get("hbm_bytes", nbytes))
        deltas[Counter.DEVICE_TIME_NS] += dt
        deltas[Counter.HBM_BYTES] += nbytes
        deltas[Counter.DEVICE_FLOPS] += flops
        if measured is not None and measured.n_ops:
            # Measured path (the rdpmc analog): fractions from the latest
            # profiler sample apply to this quantum's wall time — stall
            # tracks what the ops actually did, so phase changes show up
            # without waiting for the next sample's absolute numbers.
            deltas[Counter.HBM_STALL_NS] += int(dt * measured.stall_frac)
            if "collective_wait_ns" not in metrics and measured.collective_ns:
                deltas[Counter.COLLECTIVE_WAIT_NS] += int(
                    dt * measured.collective_frac)
        elif flops or nbytes:
            # Roofline stall estimate: fraction of the step the program
            # was memory-bound. Coarse, but behind the TelemetrySource
            # seam so fidelity can improve without policy changes.
            t_mem = nbytes / self.peak_hbm_bw
            t_flop = flops / self.peak_flops
            frac = t_mem / (t_mem + t_flop) if (t_mem + t_flop) > 0 else 0.0
            deltas[Counter.HBM_STALL_NS] += int(dt * frac)
        for key, ctr in self._METRIC_KEYS:
            if key in metrics:
                deltas[ctr] += np.uint64(max(0, int(metrics[key])))

    def execute(self, ctx: Any, n_steps: int) -> np.ndarray:
        job = ctx.job
        deltas = np.zeros(NUM_COUNTERS, dtype=np.uint64)
        flops, nbytes = self._job_cost(job)
        for _ in range(n_steps):
            dt, metrics, n_c, c_ns = self._invoke(job, job.step_fn, ctx)
            self._charge(deltas, dt, flops, nbytes, metrics,
                         measured=self._measured.get(job.name))
            deltas[Counter.COMPILES] += n_c
            deltas[Counter.COMPILE_TIME_NS] += c_ns
            deltas[Counter.STEPS_RETIRED] += 1
        return apply_counter_faults(job.name, deltas)

    def execute_micro(self, ctx: Any, n_micro: int) -> np.ndarray:
        """Chunked execution of a long-step job: each call to
        ``micro_step_fn`` advances one compiled chunk (e.g. a
        gradient-accumulation micro-batch running an inner ``lax.scan``);
        the host checks between chunks whether the quantum is spent —
        that host check IS the early-exit hook SURVEY.md §7 calls for.
        A full step (and its cost-analysis FLOPs/bytes) retires when the
        micro cursor wraps."""
        job = ctx.job
        K = job.micro_per_step
        fn = job.micro_step_fn
        if fn is None:
            # step_fn advances a FULL step; silently substituting it
            # would run K real steps per retired step and mischarge
            # FLOPs/HBM by 1/K.
            raise ValueError(
                f"job {job.name!r} has micro_per_step={K} but no "
                "micro_step_fn; provide a chunk-sized step "
                "(e.g. models.make_micro_train_step)")
        deltas = np.zeros(NUM_COUNTERS, dtype=np.uint64)
        flops, nbytes = self._job_cost(job)
        for _ in range(n_micro):
            dt, metrics, n_c, c_ns = self._invoke(job, fn, ctx)
            self._charge(deltas, dt, flops // K, nbytes // K, metrics,
                         measured=self._measured.get(job.name))
            deltas[Counter.COMPILES] += n_c
            deltas[Counter.COMPILE_TIME_NS] += c_ns
            ctx.micro_progress += 1
            if ctx.micro_progress >= K:
                ctx.micro_progress = 0
                deltas[Counter.STEPS_RETIRED] += 1
        if ctx.micro_progress:
            deltas[Counter.YIELDS] += 1
        return apply_counter_faults(job.name, deltas)
