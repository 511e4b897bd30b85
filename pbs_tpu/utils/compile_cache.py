"""JAX's persistent compilation cache, placed once for every entry point.

The directory is part of the cache key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
this module sets nothing; where it is not, the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``). Root scripts,
``tpu_tests/`` and the JAX-touching ``pbst`` commands call
:func:`setup_compilation_cache` before their first compile.
"""

from __future__ import annotations

import os
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The last stamp an entry point's imports can take before it asks JAX
# for its backend: every entry point imports this module last and calls
# it first (benchmarks/run.py after ``import jax`` and its whole
# harness), so HOST_START's ``import_ns`` closes the interpreter's start
# and the imports.
_T_IMPORTED_NS = time.monotonic_ns()
_started = False


def setup_compilation_cache() -> str:
    """Enable the persistent cache and return the directory in effect.
    Call after ``import jax`` and before the first compile (it
    initializes the backend to learn the platform). The first call also
    writes the process's ``HOST_START`` record (docs/TRACING.md "Where a
    start-up goes") and, through the ``host`` ring, installs the
    compile meter that every later compile and cache verdict is heard
    by."""
    global _started
    import jax

    from pbs_tpu.obs import trace as obs_trace

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    t_asked = time.monotonic_ns()
    backend = jax.default_backend()
    t_answered = time.monotonic_ns()
    if backend != "cpu":
        # On an accelerator keep every program, not only those past
        # JAX's 1 s default: on a v5e the flagship's serving programs
        # compile in under a second each, and a process that starts
        # cold pays for all of them. On the CPU the default stands
        # (XLA:CPU logs two long lines for every entry it loads).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not _started:
        _started = True
        start, flags = obs_trace.process_start_ns()
        import pbs_tpu

        obs_trace.host_emit(
            start, obs_trace.Ev.HOST_START,
            pbs_tpu.T_IMPORT_NS - start, _T_IMPORTED_NS - start,
            t_answered - start, t_answered - t_asked,
            jax.device_count(), flags)
    return jax.config.jax_compilation_cache_dir


def cache_counts() -> dict[str, int]:
    """Persistent-cache hits and misses the process's compile meter has
    heard (installed by :func:`setup_compilation_cache` at the
    latest)."""
    from pbs_tpu.telemetry.compile import CompileMeter

    meter = CompileMeter.install()
    return {"hits": meter.cache_hits, "misses": meter.cache_misses}
