"""Compile-time metering: per-job attribution of XLA compilation cost.

SURVEY.md §7 lists compile-cache thrash as the #1 TPU-specific
multiplexing hazard the reference never had: Xen guests don't JIT
their own kernels, but every distinct program a tenant brings costs
seconds of XLA compile time and a compile-cache slot, and a partition
multiplexing many tenants can spend more time compiling than running.

This module taps JAX's public monitoring stream (``jax.monitoring``:
the ``/jax/core/compile/backend_compile_duration`` event fires once per
actual XLA compilation) and attributes each event to the job whose
dispatch triggered it — the scope is set by ``TpuBackend`` around every
host-callable invocation. The time it reports is a wall time: JAX's
events nest (a jit traced inside a jit reports its trace under its own
event and again inside the outer one; an eager op inside a trace
compiles inside it), so their durations summed can pass the wall of the
call they happened in. The meter stamps the outermost event's start and
end on the thread itself and counts that span once. The drained
per-job sums land in the ``COMPILES`` / ``COMPILE_TIME_NS`` ledger
slots, making compilation a first-class scheduled-resource like device
time, and feed the admission gate in ``pbs_tpu.runtime.compile_gate``.

Each outermost event is also one ``HOST_COMPILE`` record of the
process's ``host`` ring (``obs/trace.py``; docs/TRACING.md "Where a
start-up goes"): when it began on the rings' clock, its kind, its wall,
the function JAX named, the attribution scope in force and, for a
backend event, whether the persistent cache served it. The meter is the
process's one listener on that stream: the persistent cache's hit and
miss events, which ``utils.compile_cache.cache_counts`` reports, are
heard here too.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator

#: The monitoring event that corresponds to one real XLA compilation.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Front-end work (tracing, MLIR emission) also attributed to the job,
#: but not counted as a cache-filling "compile".
FRONTEND_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_EVENTS = frozenset((BACKEND_COMPILE_EVENT,) + FRONTEND_EVENTS)
#: HOST_COMPILE.kind (``obs.trace.COMPILE_KINDS``).
_KIND = {FRONTEND_EVENTS[0]: 0, FRONTEND_EVENTS[1]: 1,
         BACKEND_COMPILE_EVENT: 2}
#: The persistent cache's events, fired inside a backend event on the
#: thread that compiles: a hit is a deserialized executable (with the
#: time the read took); a miss is counted when the freshly compiled
#: entry is written.
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileMeter:
    """Singleton tap on the JAX compile-event stream.

    ``attribute(name)`` scopes the current thread's compilations to a
    job; unattributed events accumulate under ``"<ambient>"`` so system
    compile load is visible too, never silently dropped.
    """

    _instance: "CompileMeter | None" = None
    _instance_lock = threading.Lock()

    def __init__(self) -> None:
        # Imported here: obs' package reaches back into telemetry.
        from pbs_tpu.obs import trace as obs_trace

        self._obs = obs_trace
        self._lock = threading.Lock()
        self._tls = threading.local()
        # name -> [compiles, wall ns of compiling] (pending drain)
        self._pending: dict[str, list[int]] = {}
        # lifetime totals (admission projections read these)
        self.total_compiles = 0
        self.total_compile_ns = 0
        # persistent-cache verdicts, process-wide
        self.cache_hits = 0
        self.cache_misses = 0
        self._installed = False

    @classmethod
    def install(cls) -> "CompileMeter":
        """Create-or-return the process-wide meter (the listener API has
        no deregistration, so exactly one is ever installed)."""
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
                cls._instance._register()
            return cls._instance

    def _register(self) -> None:
        if self._installed:
            return
        import jax

        # JAX reports an event's start as a scalar and its end as a
        # duration (dispatch.log_elapsed_time), both on the thread that
        # does the work.
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)
        jax.monitoring.register_event_listener(self._on_cache)
        self._installed = True

    # -- listeners --------------------------------------------------------

    def _on_start(self, event: str, _value: float, **kw) -> None:
        if event not in _EVENTS:
            return
        depth = getattr(self._tls, "depth", 0)
        if depth == 0:
            self._tls.t_open = time.monotonic_ns()
        self._tls.depth = depth + 1

    def _on_cache(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._tls.cache = self._obs.CACHE_HIT
            with self._lock:
                self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            self._tls.cache = self._obs.CACHE_MISS
            with self._lock:
                self.cache_misses += 1

    def _on_event(self, event: str, duration_s: float, **kw) -> None:
        if event == CACHE_RETRIEVAL_EVENT:
            self._tls.retrieval_ns = int(duration_s * 1e9)
            return
        if event not in _EVENTS:
            return
        # The outermost event's span on this thread's own clock, once;
        # an event nested in it is inside that span already.
        depth = getattr(self._tls, "depth", 0) - 1
        if depth < 0:  # began before the meter was installed
            return
        tls = self._tls
        tls.depth = depth
        wall = time.monotonic_ns() - tls.t_open if depth == 0 else 0
        is_backend = event == BACKEND_COMPILE_EVENT
        scope = getattr(tls, "scope", None)
        if is_backend:  # the verdict the cache gave inside this event
            cache = getattr(tls, "cache", 0)
            retrieval_ns = getattr(tls, "retrieval_ns", 0)
            tls.cache = tls.retrieval_ns = 0
        else:
            cache = retrieval_ns = 0
        if depth == 0:
            obs = self._obs
            tls.wall_ns = getattr(tls, "wall_ns", 0) + wall
            obs.host_emit(
                tls.t_open, obs.Ev.HOST_COMPILE, _KIND[event], wall,
                obs.job_tag(str(kw.get("fun_name", ""))),
                obs.job_tag(scope) if scope else 0, cache, retrieval_ns)
        if not (wall or is_backend):
            return
        scope = scope or "<ambient>"
        with self._lock:
            ent = self._pending.setdefault(scope, [0, 0])
            ent[1] += wall
            if is_backend:
                ent[0] += 1
                self.total_compiles += 1
                self.total_compile_ns += int(duration_s * 1e9)

    # -- attribution scope ------------------------------------------------

    @contextlib.contextmanager
    def attribute(self, name: str) -> Iterator[None]:
        prev = getattr(self._tls, "scope", None)
        self._tls.scope = name
        try:
            yield
        finally:
            self._tls.scope = prev

    def thread_wall_ns(self) -> int:
        """Wall nanoseconds of outermost compile events that ended on
        the calling thread, ever: the difference over a span is the
        compile wall inside it (``obs.trace.host_phase``)."""
        return getattr(self._tls, "wall_ns", 0)

    def take(self, name: str) -> tuple[int, int]:
        """Drain (compiles, compile_ns) attributed to ``name`` since the
        last take. Frontend time is part of compile_ns — from the
        tenant's perspective it is all time-to-first-step — and
        compile_ns is wall time: never more than the wall of the scopes
        it was attributed in."""
        with self._lock:
            ent = self._pending.pop(name, None)
        return tuple(ent) if ent else (0, 0)

    def peek_all(self) -> dict[str, tuple[int, int]]:
        with self._lock:
            return {k: tuple(v) for k, v in self._pending.items()}

    @property
    def mean_compile_ns(self) -> int:
        """Observed average per-compilation cost — the projection basis
        for admission when a job declares no estimate."""
        if self.total_compiles == 0:
            return 0
        return self.total_compile_ns // self.total_compiles
