"""Measured telemetry: per-quantum XLA profiler sampling.

The reference's whole point is cheap *measured* hardware counters:
``perfctr_cpu_vsuspend`` publishes rdpmc sums into the per-vcpu state at
every context switch (``xen-4.2.1/xen/arch/x86/perfctr.c:1547-1573``),
so the feedback filter sees real LLC-miss rates, not estimates. A TPU
exposes no per-tenant PMC file, but it does expose the XLA profiler:
wrapping a quantum in ``jax.profiler.trace`` yields a perfetto trace
with one event per executed HLO op (device lanes on real TPU, thunk
events on the CPU backend). This module parses that trace and buckets
per-op time into

- **compute** — MXU-shaped ops (dot/conv): the systolic array is busy;
- **collective** — ICI/DCN ops (all-reduce, all-gather, ppermute, ...):
  the measured analog of spin-lock wait;
- **memory** — everything else (fusions, copies, elementwise): on a TPU
  these are HBM-bandwidth-bound, so their duration is the measured
  stand-in for the reference's LLC-stall counter.

Profiling every quantum would serialize the device and double step
latency; like i-mode sampling, the backend profiles every N-th quantum
and carries the measured fractions forward until the next sample. The
static roofline estimate (``source.py``) remains the cold-start
fallback before the first sample lands — same seam, better fidelity.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import threading
from typing import Any, Callable, Iterable

__all__ = [
    "TraceStats",
    "XlaQuantumProfiler",
    "classify_op",
    "parse_trace_dir",
    "parse_trace_events",
]

# HLO-ish op event names: lowercase op (optionally wrapped_/fused_),
# optional ".N" suffix. Excludes runtime frames (CamelCase, '::',
# spaces), python frames ('$file.py:123 fn') and 'end: op' markers.
_OP_RE = re.compile(r"^_?(wrapped_|fused_)?[a-z][a-z0-9\-_]*(\.[0-9]+)?$")

_COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
    "send-done", "recv-done",
)
_COMPUTE_MARKS = ("dot", "convolution", "einsum", "cholesky",
                  "triangular-solve", "fft",
                  # Pallas kernels lower through Mosaic; their events
                  # surface either under the mosaic/tpu_custom_call
                  # target or under the kernel function's own name
                  # (ops/attention.py _fwd_kernel etc.). Bare
                  # 'custom-call' is NOT compute — lax.top_k (the MoE
                  # router) and host callbacks lower there too; those
                  # are identified per-kernel via long_name below.
                  "tpu_custom_call", "mosaic", "fwd_kernel",
                  "bwd_dq_kernel", "bwd_dkv_kernel", "mm_kernel")
# long_name markers that make a bare custom-call a compute kernel.
_CUSTOM_CALL_COMPUTE = ("mosaic", "flash", "_kernel", "matmul")
# Control-flow CONTAINERS: their event duration spans the whole body,
# whose ops appear as their own events — counting the container would
# double-bill every inner op into the memory bucket (a lax.scan train
# loop showed up as one giant 'while' stall). Structural no-op events
# are excluded with them.
_CONTAINER_OPS = ("while", "conditional", "call", "tuple", "parameter",
                  "get-tuple-element", "constant", "bitcast",
                  "opt-barrier", "after-all")


def classify_op(name: str, long_name: str = "") -> str | None:
    """Bucket one trace event: 'compute' | 'collective' | 'memory' |
    None (not an HLO op — runtime/python frame, or a control-flow
    container whose children are billed individually)."""
    if not _OP_RE.match(name):
        return None
    # Our Pallas kernel fns are underscore-prefixed (_fwd_kernel,
    # _mm_kernel — ops/); strip the prefix so the marks match however
    # the event surfaces.
    base = name.lstrip("_")
    for pre in ("wrapped_", "fused_"):
        if base.startswith(pre):
            base = base[len(pre):]
    for pre in _CONTAINER_OPS:
        if base == pre or base.startswith(pre + "."):
            return None
    for pre in _COLLECTIVE_PREFIXES:
        if base == pre or base.startswith(pre + "."):
            return "collective"
    # Exact-boundary matching on the op name ('dot_general.1',
    # 'convolution.3'), NOT substrings — 'convert' must not hit 'conv'
    # and bill dtype casts to the MXU bucket. Fusions are classified by
    # their root in long_name ('fusion(dot(...))'), where the mark is
    # anchored to a call-paren.
    for m in _COMPUTE_MARKS:
        if base == m or base.startswith((m + ".", m + "_", m + "-")):
            return "compute"
        if (m + "(") in long_name:
            return "compute"
    if base == "custom-call" or base.startswith("custom-call."):
        if any(k in long_name for k in _CUSTOM_CALL_COMPUTE):
            return "compute"
    return "memory"


@dataclasses.dataclass
class TraceStats:
    """Measured per-op time for one profiled quantum (all ns)."""

    device_time_ns: int = 0  # union of op intervals (busy time)
    compute_ns: int = 0
    collective_ns: int = 0
    memory_ns: int = 0
    n_ops: int = 0
    top_ops: list[tuple[str, int]] = dataclasses.field(default_factory=list)
    source: str = "none"  # 'device' (TPU lanes) or 'host' (CPU thunks)

    @property
    def stall_frac(self) -> float:
        """Fraction of busy time NOT on the MXU — the measured
        HBM-stall proxy (reference: LLC-miss-rate, perfctr.c)."""
        busy = self.compute_ns + self.memory_ns + self.collective_ns
        return self.memory_ns / busy if busy > 0 else 0.0

    @property
    def collective_frac(self) -> float:
        busy = self.compute_ns + self.memory_ns + self.collective_ns
        return self.collective_ns / busy if busy > 0 else 0.0


def _merged_span(intervals: list[tuple[int, int]]) -> int:
    """Total length of the union of [start, end) intervals."""
    if not intervals:
        return 0
    intervals.sort()
    total = 0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s)


def parse_trace_events(events: Iterable[dict]) -> TraceStats:
    """Aggregate a perfetto ``traceEvents`` list into :class:`TraceStats`.

    Prefers device-lane processes (``/device:TPU:N``) when present (real
    chip); otherwise falls back to host thunk events (CPU backend), so
    the same parser serves CI and production.
    """
    events = list(events)
    pid_names: dict[Any, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e.get("pid")] = (e.get("args") or {}).get("name", "")
    # A device lane that ran nothing is no device: a process that loaded
    # libtpu only to compile for a described chip gets an empty
    # ``/device:CUSTOM:Megascale Trace`` lane beside its CPU thunks.
    device_pids = {p for p, n in pid_names.items() if "/device:" in n} \
        & {e.get("pid") for e in events if e.get("ph") == "X"}

    stats = TraceStats(source="device" if device_pids else "host")
    intervals: list[tuple[int, int]] = []
    per_op: dict[str, int] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        name = e.get("name", "")
        args = e.get("args") or {}
        kind = classify_op(name, str(args.get("long_name", "")))
        if kind is None:
            continue
        # trace timestamps are µs floats; keep ns precision.
        dur = int(float(e.get("dur", 0)) * 1000)
        ts = int(float(e.get("ts", 0)) * 1000)
        if dur <= 0:
            continue
        stats.n_ops += 1
        intervals.append((ts, ts + dur))
        per_op[name] = per_op.get(name, 0) + dur
        if kind == "compute":
            stats.compute_ns += dur
        elif kind == "collective":
            stats.collective_ns += dur
        else:
            stats.memory_ns += dur
    stats.device_time_ns = _merged_span(intervals)
    stats.top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:8]
    return stats


def parse_trace_dir(logdir: str) -> TraceStats | None:
    """Parse the newest ``*.trace.json.gz`` under a profiler logdir."""
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")
    )
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return parse_trace_events(doc.get("traceEvents", []))


# Only one profiler session may exist per process (libtpu and the CPU
# tracer both enforce this); concurrent quanta skip their sample rather
# than block the executor.
_PROFILE_LOCK = threading.Lock()


class XlaQuantumProfiler:
    """Wraps host-callable quanta in ``jax.profiler.trace`` and returns
    parsed :class:`TraceStats` (the rdpmc-read analog)."""

    def __init__(self, keep_logdir: str | None = None):
        self.keep_logdir = keep_logdir  # None = tmpdir, deleted after parse
        self.samples = 0
        self.failures = 0
        self.last_error: str | None = None

    def profile(self, fn: Callable[[], Any]) -> tuple[Any, TraceStats | None]:
        """Run ``fn`` under the profiler; returns (fn(), stats|None).
        Never raises on profiler trouble — the quantum's result always
        comes back; a failed sample leaves stats None and is counted
        in ``failures``. What ``fn`` itself raises propagates."""
        if not _PROFILE_LOCK.acquire(blocking=False):
            return fn(), None  # another quantum holds the one session
        logdir = self.keep_logdir or tempfile.mkdtemp(prefix="pbst_prof_")
        try:
            # Start/stop failures are the profiler's problem and must
            # not affect the quantum — but ``fn`` runs EXACTLY once
            # either way (a data-loading step advances external cursors;
            # re-running it would double-step the job).
            session = None
            try:
                import jax

                session = jax.profiler.trace(logdir)
                session.__enter__()
            except Exception as e:
                self.failures += 1
                self.last_error = f"{type(e).__name__}: {e}"
                session = None
            try:
                out = fn()
            finally:
                if session is not None:
                    try:
                        session.__exit__(None, None, None)
                    except Exception as e:  # noqa: BLE001 — sample lost
                        self.failures += 1
                        self.last_error = f"{type(e).__name__}: {e}"
                        session = None
            if session is None:
                return out, None
            try:
                stats = parse_trace_dir(logdir)
                if stats is not None:
                    self.samples += 1
                else:
                    self.failures += 1
                    self.last_error = f"no trace file under {logdir}"
                return out, stats
            except Exception as e:
                self.failures += 1
                self.last_error = f"{type(e).__name__}: {e}"
                return out, None
        finally:
            _PROFILE_LOCK.release()
            if self.keep_logdir is None:
                shutil.rmtree(logdir, ignore_errors=True)
