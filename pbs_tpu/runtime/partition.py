"""Partitions: cpupool analogs owning devices, a scheduler, and jobs.

Xen cpupools (``xen/common/cpupool.c``) hard-partition pCPUs into pools,
each with its own scheduler instance; domains live in exactly one pool.
Here a Partition owns a set of device lanes (TPU cores/chips or sim
lanes), one scheduler instance chosen from the registry, the telemetry
ledger for its contexts (the 8-page shared_info analog,
``xen/common/domain.c:618-626``), and the timer substrate.

The cooperative ``run()`` loop drives executors round-robin on one host
thread — the simulation/CI mode. Under a ``VirtualClock`` the loop is
fully deterministic; when every executor is idle the clock jumps to the
next timer deadline (event-driven simulation).
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

import os

from pbs_tpu.obs.trace import (
    Ev,
    EmitBatch,
    TraceBuffer,
    merge_records,
    register_ring,
)
from pbs_tpu.runtime import xsm
from pbs_tpu.runtime.events import EventBus, Virq
from pbs_tpu.runtime.executor import Executor
from pbs_tpu.runtime.job import ContextState, Job, SchedParams
from pbs_tpu.runtime.timer import TimerWheel
from pbs_tpu.sched.base import Scheduler, make_scheduler
from pbs_tpu.telemetry.ledger import Ledger
from pbs_tpu.telemetry.source import TelemetrySource
from pbs_tpu.utils.clock import Clock, VirtualClock
from pbs_tpu.utils.params import string_param

DEFAULT_LEDGER_SLOTS = 128

# ``sched=`` boot param (schedule.c:65-70): the scheduler a partition
# gets when its creator doesn't pick one explicitly.
_sched_param = string_param("sched", "credit")


class Partition:
    def __init__(
        self,
        name: str,
        source: TelemetrySource,
        scheduler: str | None = None,
        n_executors: int = 1,
        devices: list[Any] | None = None,
        clock: Clock | None = None,
        ledger_slots: int = DEFAULT_LEDGER_SLOTS,
        ledger_path: str | None = None,
        trace_dir: str | None = None,
        sched_params: dict[str, Any] | None = None,
        memory: "MemoryManager | None" = None,
        compile_admission: "CompileAdmission | None" = None,
    ):
        self.name = name
        self.source = source
        self.clock = clock if clock is not None else source.clock
        self.timers = TimerWheel()
        # File-backed ledger lets external monitors (pbst top) read live
        # telemetry lock-free across processes.
        self._ledger_path = ledger_path
        if ledger_path is not None:
            self.ledger = Ledger.file_backed(ledger_path, ledger_slots)
        else:
            self.ledger = Ledger(ledger_slots)
        # Per-executor lockless trace rings (per-CPU rings, trace.c).
        self.traces: list[TraceBuffer] = []
        # Master trace switch (the tb_init_done analog): single-owner
        # drivers that consume no ring (sim sweep cells) turn it off so
        # dispatch events skip the ring entirely.
        self.trace_enabled = True
        # Optional per-ring staging batches (enable_trace_batching):
        # single-threaded drivers (the sim engine) trade immediate ring
        # visibility for one vectorized write per batch.
        self._trace_batches: list[EmitBatch] | None = None
        # Async signaling fabric (event_channel.c analog); delivered by
        # the run loop between quanta.
        self.events = EventBus()
        # i-mode counter sampling: thresholds -> Virq.TELEMETRY -> rearm
        # (the VIRQ_PERFCTR overflow path, telemetry/sampler.py).
        from pbs_tpu.telemetry.sampler import OverflowSampler

        self.sampler = OverflowSampler(self.events)
        # Optional quantum/tick recorder (pbs_tpu.sim.trace.TraceRecorder):
        # when set, every dispatched quantum and feedback tick is appended
        # as a JSONL record so the run can be replayed in the simulator.
        self.recorder = None
        # Optional HBM accounting/admission (runtime.memory).
        self.memory = memory
        # Optional compile-cache admission (runtime.compile_gate): the
        # TPU-new scarce resource SURVEY.md §7 flags — distinct programs
        # per partition and cumulative compile time.
        self.compile_admission = compile_admission
        # Lifecycle hook scripts (the /etc/xen/scripts hotplug analog,
        # runtime.hooks): required job-add hooks gate admission.
        from pbs_tpu.runtime.hooks import HookRegistry

        self.hooks = HookRegistry()
        self._free_slots = list(range(ledger_slots - 1, -1, -1))
        self.jobs: list[Job] = []
        # Monotone quantum counter; WallWatchdog reads it out-of-band.
        self.progress_epoch = 0
        # Hook invoked on contained job failures (crash-dump wiring).
        self.on_job_failure: Callable[[Job, BaseException], None] | None = None
        self.executors: list[Executor] = []
        self.scheduler: Scheduler = make_scheduler(
            scheduler if scheduler is not None else _sched_param.value,
            self, **(sched_params or {})
        )
        # File-backed rings let an external xenbaked-style monitor attach
        # live (obs.mon); otherwise rings live in process memory.
        # Absolute path: the meta sidecar publishes it for monitors that
        # run with a different working directory.
        self._trace_dir = (
            os.path.abspath(trace_dir) if trace_dir is not None else None)
        if self._trace_dir is not None:
            os.makedirs(self._trace_dir, exist_ok=True)
        devices = devices or [None] * n_executors
        for i, dev in enumerate(devices):
            ex = Executor(self, i, device=dev)
            self.executors.append(ex)
            if self._trace_dir is not None:
                self.traces.append(TraceBuffer.file_backed(
                    os.path.join(self._trace_dir, f"trace{i}.ring")))
            else:
                self.traces.append(TraceBuffer())
            self.scheduler.executor_added(ex)
        # Overflow crossings land in ring 0 as TELEM_OVERFLOW in every
        # mode (trace content must not depend on whether trace batching
        # is enabled): the sampler stages a quantum's firings and
        # flushes at the end of each check() call.
        if self.traces:
            self.sampler.bind_trace(
                EmitBatch(self.traces[0], capacity=64), self.clock)
        for i, ring in enumerate(self.traces):
            register_ring(f"partition:{name}#{i}", ring)
        # A source that records its executed steps (TpuBackend's
        # EXEC_STEP) writes them into the lane's ring, between the
        # quantum's SCHED_PICK and SCHED_DESCHED.
        if hasattr(source, "bind_trace"):
            source.bind_trace(self._source_emit)

    # -- admission (domain_create analog, xen/common/domain.c) -----------

    def add_job(self, job: Job, subject: str = xsm.SYSTEM) -> Job:
        xsm.xsm_check(subject, "job.create", job.label)
        if self.compile_admission is not None:
            # Fail-fast compile-cache claim FIRST: it touches no shared
            # state beyond its own table, so rejection leaves nothing
            # to unwind (the XENMEM_claim_pages ordering).
            self.compile_admission.admit(job)
        if self.memory is not None:
            # Fail-fast HBM admission (XENMEM_claim_pages): account +
            # claim the working set before touching scheduler state, so
            # a denied job leaves nothing behind.
            from pbs_tpu.runtime.memory import nbytes_of

            need = (job.mem_bytes if job.mem_bytes is not None
                    else nbytes_of(job.state))
            self.memory.open_account(job.name)
            try:
                self.memory.claim_or_balloon(job.name, need)
            except Exception:
                self.memory.close_account(job.name)
                if self.compile_admission is not None:
                    self.compile_admission.release(job.name)
                raise
        try:
            for ctx in job.contexts:
                if not self._free_slots:
                    raise RuntimeError("ledger slots exhausted")
                ctx.ledger_slot = self._free_slots.pop()
                self.ledger.reset(ctx.ledger_slot)
        except Exception:
            # Unwind fully — slots back on the freelist, account closed —
            # so a failed admission leaves nothing behind and the name
            # stays retryable.
            for ctx in job.contexts:
                if ctx.ledger_slot >= 0:
                    self._free_slots.append(ctx.ledger_slot)
                    ctx.ledger_slot = -1
            if self.memory is not None:
                self.memory.close_account(job.name)
            if self.compile_admission is not None:
                self.compile_admission.release(job.name)
            raise
        # Scheduler enrollment is part of the same atomic admission: a
        # job_added/wake failure must unwind jobs-list membership, the
        # ledger slots, and the memory account, or the name stops being
        # retryable and the slots leak.
        enrolled = False
        try:
            self.jobs.append(job)
            self.scheduler.job_added(job)
            enrolled = True
            for ctx in job.contexts:
                if ctx.state is ContextState.RUNNABLE:
                    self.scheduler.wake(ctx)
            self._publish_meta()
            # Hotplug: a REQUIRED job-add hook failing aborts the whole
            # admission (the vif-attach-fails semantics) via the unwind
            # below; optional failures are contained inside fire().
            self.hooks.fire("job-add", self._hook_env(job),
                            console=job.console)
            job.console.write(
                f"admitted to {self.name} "
                f"({len(job.contexts)} ctx, scheduler "
                f"{self.scheduler.name})")
        except Exception:
            if enrolled:
                try:
                    self.scheduler.job_removed(job)
                except Exception:  # noqa: BLE001 — best-effort unwind
                    pass
            if job in self.jobs:
                self.jobs.remove(job)
            for ctx in job.contexts:
                if ctx.ledger_slot >= 0:
                    self._free_slots.append(ctx.ledger_slot)
                    ctx.ledger_slot = -1
            if self.memory is not None:
                self.memory.close_account(job.name)
            if self.compile_admission is not None:
                self.compile_admission.release(job.name)
            try:
                # A required-hook failure lands AFTER the sidecar was
                # published: republish so monitors never attribute the
                # freed slots to a job that was never admitted.
                self._publish_meta()
            except Exception:  # noqa: BLE001 — unwind must complete
                pass
            raise
        return job

    def create_job(
        self,
        name: str,
        step_fn: Callable | None = None,
        state: Any = None,
        params: SchedParams | None = None,
        **kw: Any,
    ) -> Job:
        job = Job(name, step_fn=step_fn, state=state, params=params, **kw)
        return self.add_job(job)

    def _hook_env(self, job: Job, **extra: str) -> dict[str, str]:
        return {
            "PBST_JOB": job.name,
            "PBST_PARTITION": self.name,
            "PBST_LABEL": job.label,
            **extra,
        }

    def remove_job(self, job: Job, subject: str = xsm.SYSTEM) -> None:
        xsm.xsm_check(subject, "job.destroy", job.label)
        from pbs_tpu.runtime.hooks import HookError

        try:
            # Teardown hooks run while the job still exists (the detach
            # script sees the device); failure cannot block destruction.
            self.hooks.fire("job-remove", self._hook_env(job),
                            console=job.console)
        except HookError:
            pass
        job.console.write("destroyed")
        if self.memory is not None:
            self.memory.close_account(job.name)
        if self.compile_admission is not None:
            self.compile_admission.release(job.name)
        # Dead jobs must not pin their contexts via armed samples (or
        # keep getting scanned by every overflow check).
        self.sampler.disarm_job(job)
        self.scheduler.job_removed(job)
        self.jobs.remove(job)
        for ctx in job.contexts:
            if ctx.ledger_slot >= 0:
                self._free_slots.append(ctx.ledger_slot)
                ctx.ledger_slot = -1
        self._publish_meta()

    def job(self, name: str) -> Job:
        for j in self.jobs:
            if j.name == name:
                return j
        raise KeyError(name)

    # -- run-state control (vcpu_sleep/wake, schedule.c) -----------------

    def sleep_job(self, job: Job, notify: bool = True) -> None:
        """``notify=False`` is the internal-quiesce form (Remus epoch
        capture, migration save): a sub-second suspend/resume cycle is
        not a lifecycle event, and hotplug scripts must not run inside
        it (Xen likewise never runs scripts on Remus epochs)."""
        from pbs_tpu.runtime.hooks import HookError

        changed = False
        for ctx in job.contexts:
            if ctx.runnable():
                ctx.state = ContextState.BLOCKED
                self.scheduler.sleep(ctx)
                changed = True
        if changed and notify:
            try:
                self.hooks.fire("job-sleep", self._hook_env(job),
                                console=job.console)
            except HookError:
                pass  # run-state changes cannot be vetoed

    def wake_job(self, job: Job, notify: bool = True) -> None:
        from pbs_tpu.runtime.hooks import HookError

        if getattr(job, "paged", None) is not None:
            # xenpaging fault path: touching a paged tenant restores
            # its device state first (claiming HBM back; may raise
            # OutOfDeviceMemory, leaving the job asleep+paged).
            from pbs_tpu.runtime.paging import page_in_job

            page_in_job(self, job)
        changed = False
        for ctx in job.contexts:
            if ctx.state is ContextState.BLOCKED:
                ctx.state = ContextState.RUNNABLE
                self.scheduler.wake(ctx)
                changed = True
        if changed and notify:
            try:
                self.hooks.fire("job-wake", self._hook_env(job),
                                console=job.console)
            except HookError:
                pass

    def fail_job(self, job: Job, exc: BaseException,
                 ctx: "ExecutionContext | None" = None,
                 lane: int = 0) -> None:
        """Contain a fault to one job (the MCE-containment model,
        ``tools/tests/mce-test``): mark every context FAILED, notify,
        dump — the partition and its other tenants keep running.
        ``ctx``/``lane`` identify the faulting context and executor so
        the postmortem trace names the right victim."""
        job.error = f"{type(exc).__name__}: {exc}"
        job.console.write(f"FAULT contained: {job.error}")
        self.sampler.disarm_job(job)
        from pbs_tpu.runtime.hooks import HookError

        try:
            self.hooks.fire(
                "job-fail",
                self._hook_env(job, PBST_ERROR=job.error),
                console=job.console)
        except HookError:
            pass  # containment must complete regardless
        for c in job.contexts:
            if c.state is not ContextState.FAILED:
                c.state = ContextState.FAILED
                self.scheduler.sleep(c)
        if ctx is None and job.contexts:
            ctx = job.contexts[0]
        self.trace_emit(lane, Ev.JOB_FAILED,
                        ctx.ledger_slot if ctx is not None else 0)
        self.events.send_virq(Virq.JOB_FAILED)
        if self.on_job_failure is not None:
            self.on_job_failure(job, exc)

    # -- the loop --------------------------------------------------------

    def pending_work(self) -> bool:
        # PARKED counts: a timer (acct refill) will unpark it
        # (CSCHED_FLAG_VCPU_PARKED is cleared in csched_acct).
        live = (ContextState.RUNNABLE, ContextState.RUNNING,
                ContextState.PARKED)
        return any(
            ctx.state in live for j in self.jobs for ctx in j.contexts
        )

    def run(
        self,
        until_ns: int | None = None,
        max_rounds: int | None = None,
    ) -> int:
        """Drive executors until no runnable work (or bounds hit).

        Returns the number of quanta executed.
        """
        rounds = 0
        quanta = 0
        # Hot-loop hoists: bound methods + the executor list are loop
        # invariants, and a round is ~one dispatched quantum.
        now_ns = self.clock.now_ns
        deliver_pending = self.events.deliver_pending
        executors = self.executors
        while True:
            if until_ns is not None and now_ns() >= until_ns:
                break
            if max_rounds is not None and rounds >= max_rounds:
                break
            rounds += 1
            deliver_pending()
            ran_any = False
            for ex in executors:
                if until_ns is not None and now_ns() >= until_ns:
                    break
                if ex.schedule_once():
                    ran_any = True
                    quanta += 1
            if not ran_any:
                if not self.pending_work():
                    break
                # All runnable work exists but nothing was dispatched
                # (e.g. parked for cap enforcement): jump to the next
                # timer event under virtual time, else we're stuck.
                deadline = self.timers.next_deadline()
                if deadline is None:
                    break
                if isinstance(self.clock, VirtualClock):
                    if deadline > self.clock.now_ns():
                        self.clock.advance(deadline - self.clock.now_ns())
                    self.timers.fire_due(self.clock.now_ns())
                else:
                    import time as _t

                    _t.sleep(min(0.001, max(0.0, (deadline - self.clock.now_ns()) / 1e9)))
        # Refresh the monitor sidecar so adapted tslice/weights are
        # visible to pbst top after the run; staged trace batches land
        # in the rings so attached monitors see the full stream.
        self.flush_traces()
        self._publish_meta()
        return quanta

    # -- observability ---------------------------------------------------

    def _publish_meta(self) -> None:
        """Sidecar slot map so external monitors can label ledger slots
        (the xenstore-registered device metadata analog)."""
        if self._ledger_path is None:
            return
        import json

        meta = {
            "partition": self.name,
            "scheduler": self.scheduler.name,
            "trace_dir": self._trace_dir,
            "n_rings": len(self.traces),
            # Counter-source provenance (docs/HWTELEM.md): sources
            # that can say what they are (hwtelem ladder tiers) do, so
            # `pbst top` never reports sim-sourced numbers as live.
            "source": (self.source.describe()
                       if hasattr(self.source, "describe") else
                       {"tier": type(self.source).__name__}),
            "slots": {
                str(ctx.ledger_slot): {
                    "ctx": ctx.name,
                    "job": job.name,
                    "weight": job.params.weight,
                    "cap": job.params.cap,
                    "tslice_us": job.params.tslice_us,
                }
                for job in self.jobs
                for ctx in job.contexts
                if ctx.ledger_slot >= 0
            },
        }
        tmp = self._ledger_path + ".meta.json.tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, self._ledger_path + ".meta.json")

    def enable_trace_batching(self, capacity: int = 256,
                              flush_ns: int = 1_000_000) -> None:
        """Stage trace events per ring through :class:`EmitBatch` (one
        vectorized ``emit_many`` per watermark instead of a scalar emit
        per event). Only for single-threaded drivers that own every
        producer — the sim engine — because staged records reach the
        ring at flush granularity; live multi-threaded partitions keep
        scalar emits so cross-thread ring order matches emit order."""
        self._trace_batches = [
            EmitBatch(t, capacity=capacity, flush_ns=flush_ns)
            for t in self.traces
        ]

    def flush_traces(self) -> None:
        if self._trace_batches is not None:
            for b in self._trace_batches:
                b.flush()

    def trace_emit(self, exi: int, event: int, *args: int,
                   ts_ns: int | None = None) -> None:
        """``ts_ns`` stamps a record that is written when its span ends
        with the span's start; the default is now."""
        if self.trace_enabled and 0 <= exi < len(self.traces):
            if ts_ns is None:
                ts_ns = self.clock.now_ns()
            if self._trace_batches is not None:
                self._trace_batches[exi].emit(ts_ns, event, *args)
            else:
                self.traces[exi].emit(ts_ns, event, *args)

    def _source_emit(self, ctx, ts_ns: int, event: int,
                     *args: int) -> None:
        """The source's records go to the ring of the lane that is
        running ``ctx`` (lane 0 when it cannot be told)."""
        lane = 0
        for ex in self.executors:
            if ex.current is ctx:
                lane = ex.index
                break
        self.trace_emit(lane, event, *args, ts_ns=ts_ns)

    def peek_traces(self, max_records: int = 4096):
        """Non-destructive tail of all rings, merged and time-sorted —
        for postmortems/snapshots that must not race a live consumer."""
        self.flush_traces()
        return merge_records([t.peek(max_records) for t in self.traces])

    def drain_traces(self, max_records: int = 4096):
        """xentrace analog: drain all rings, merged and time-sorted."""
        self.flush_traces()
        return merge_records([t.consume(max_records) for t in self.traces])

    def dump(self) -> dict[str, Any]:
        """The 'r'/'z' console-key dump surface
        (``keyhandler.c:543-563``, ``schedule_customized_dump``
        ``schedule.c:1442-1451``)."""
        return {
            "partition": self.name,
            "scheduler": self.scheduler.dump_settings(),
            "executors": [
                {
                    "index": ex.index,
                    "sched_invocations": ex.sched_invocations,
                    **self.scheduler.dump_executor(ex),
                }
                for ex in self.executors
            ],
            "contexts": self.scheduler.dump_admin_conf(),
        }
