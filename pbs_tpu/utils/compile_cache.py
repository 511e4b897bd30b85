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

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: ``jax.monitoring`` event names of the persistent cache (a hit is a
#: deserialized executable; a miss is counted when the freshly compiled
#: entry is written).
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == _HIT:
        _counts["hits"] += 1
    elif event == _MISS:
        _counts["misses"] += 1


def setup_compilation_cache() -> str:
    """Enable the persistent cache and return the directory in effect.
    Call after ``import jax`` and before the first compile (it
    initializes the backend to learn the platform)."""
    global _listening
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    if jax.default_backend() != "cpu":
        # On an accelerator keep every program, not only those past
        # JAX's 1 s default: on a v5e the flagship's serving programs
        # compile in under a second each, and a process that starts
        # cold pays for all of them. On the CPU the default stands
        # (XLA:CPU logs two long lines for every entry it loads).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not _listening:
        # The listener API has no deregistration: install once.
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return jax.config.jax_compilation_cache_dir


def cache_counts() -> dict[str, int]:
    """Persistent-cache hits and misses since
    :func:`setup_compilation_cache` (process-wide)."""
    return dict(_counts)
