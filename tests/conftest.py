"""Test configuration: force an 8-device virtual CPU platform.

All scheduler/policy tests run hardware-free against SimBackend (the
x86_emulator fake-backend pattern, SURVEY.md §4); JAX-touching tests see
8 virtual CPU devices so multi-chip sharding compiles and executes
without TPUs.

The library and the CLI choose no platform themselves (JAX's default
stands, so on a machine with a chip they run on it); the tests pin the
CPU here — through the environment, which child processes inherit, and
through ``jax.config`` before the first backend touch.
"""

import os
import subprocess

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")

# The native runtime needs no build step here: its binaries are not
# tracked, and pbs_tpu.runtime.native builds each from native/*.cc on
# first use whenever its source-hash stamp is missing or stale (~1 s),
# so tests can never pass against a binary that matches no source.


# -- native runtime plumbing (session-scoped: ONE build + load per run,
# never a per-test 120 s make timeout) --------------------------------

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def native_lib():
    """The loaded native runtime (ctypes bindings), building the .so
    at most once per session; SKIPS the requesting test with the
    cached failure reason when no toolchain can produce one."""
    from pbs_tpu.runtime import native

    lib = native.load()
    if lib is None:
        pytest.skip(
            f"native runtime unavailable: {native.unavailable_reason()}")
    return lib


#: Sanitizer flavors of the native runtime (dynamic witness for the
#: memmodel static passes): flavor -> (make target, artifact name).
#: Build outcome is cached per flavor so a host without the toolchain
#: pays one failed make per session, not one per test, and every skip
#: carries the same cached compiler error.
_SAN_FLAVORS = {
    "asan": ("asan", "libpbst_runtime_asan.so"),
    "ubsan": ("ubsan", "libpbst_runtime_ubsan.so"),
}
_san_cache: dict = {}  # flavor -> (path | None, failure reason | None)


def native_sanitizer_lib(flavor: str) -> tuple:
    """(path, None) to the ASan/UBSan build of the native runtime, or
    (None, why) when it cannot be produced. Builds at most once per
    flavor per session (compile-to-temp + atomic mv in the Makefile)."""
    if flavor in _san_cache:
        return _san_cache[flavor]
    target, artifact = _SAN_FLAVORS[flavor]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native_dir = os.path.join(root, "native")
    try:
        out = subprocess.run(
            ["make", "-C", native_dir, target], capture_output=True,
            text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _san_cache[flavor] = (None, f"build not attempted: {e}")
        return _san_cache[flavor]
    if out.returncode != 0:
        tail = " | ".join(
            (out.stderr or out.stdout or "").strip().splitlines()[-4:])
        _san_cache[flavor] = (None, f"make {target} failed: {tail[:400]}")
        return _san_cache[flavor]
    path = os.path.join(native_dir, artifact)
    if not os.path.exists(path):
        _san_cache[flavor] = (None, f"make {target} produced no {artifact}")
    else:
        _san_cache[flavor] = (path, None)
    return _san_cache[flavor]


def require_native(flavor: str | None = None) -> str | None:
    """Imperative form of ``native_lib`` for native-parametrized tests
    (``@pytest.mark.parametrize("use_native", ...)`` can't request a
    fixture conditionally): skip with the cached WHY when the runtime
    is unavailable.

    With ``flavor`` ("asan"/"ubsan"), additionally require that
    sanitizer build of the runtime and return its path (for a
    subprocess's PBST_NATIVE_LIB); skips with the cached build-failure
    reason when the toolchain can't produce it."""
    from pbs_tpu.runtime import native

    if not native.available():
        pytest.skip(
            f"native runtime unavailable: {native.unavailable_reason()}")
    if flavor is None:
        return None
    path, why = native_sanitizer_lib(flavor)
    if path is None:
        pytest.skip(f"native {flavor} runtime unavailable: {why}")
    return path


@pytest.fixture
def donating():
    """``donating(call, *dead)``: run ``call()``, return its result,
    and hold it to buffer donation — every array in ``dead`` (handles
    that went into a donating program) is deleted afterwards. Where
    JAX warns that the platform could not use the donation, only the
    assertion is skipped: the call and its result still count."""
    import warnings

    def run(call, *dead):
        with warnings.catch_warnings(record=True) as seen:
            warnings.simplefilter("always")
            out = call()
        if not any("donated buffers were not usable" in str(w.message)
                   for w in seen):
            assert all(a.is_deleted() for a in dead), \
                "a donating program left its input handle alive"
        return out

    return run
