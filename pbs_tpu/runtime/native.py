"""ctypes bindings for the native runtime (native/pbst_runtime.cc).

The reference's hot paths are C compiled into the hypervisor/guest
kernel; ours is a small C++ shared library over flat u64 buffers —
seqlock ledger writes/snapshots and the lockless trace ring — bound via
ctypes (no pybind11 in this image; the ABI is flat by design). The
binaries are not tracked: each is built from ``native/*.cc`` with the
in-tree Makefile on first use and rebuilt whenever its sources change
(a stamp file beside it holds the hash of the sources it was built
from — mtimes do not survive a checkout or a copy). Everything degrades
to the pure-Python implementations when a toolchain is unavailable, so
nothing upstack depends on native availability.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

from pbs_tpu.obs.lockprof import ProfiledLock

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
#: PBST_NATIVE_LIB points the loader at an alternate build of the same
#: ABI — the sanitizer tier (libpbst_runtime_{asan,ubsan}.so) runs the
#: whole ctypes surface under ASan/UBSan in a subprocess with nothing
#: but this env var changed. An override path is used as-is: no
#: freshness check and no rebuild (the override names a specific
#: artifact, and `make asan` owns its freshness).
_LIB_OVERRIDE = os.environ.get("PBST_NATIVE_LIB") or None
_LIB_PATH = os.path.abspath(
    _LIB_OVERRIDE if _LIB_OVERRIDE
    else os.path.join(_NATIVE_DIR, "libpbst_runtime.so"))

_lock = ProfiledLock("native_load")
_lib: ctypes.CDLL | None = None
_tried = False
#: Why the native runtime is unavailable (build/load failure), cached
#: for diagnosability: `pbst perf` prints it, and the system console
#: ring records it once — "why is everything slow" must not require a
#: debugger (the failure used to be swallowed silently).
_fail_reason: str | None = None

_U64P = ctypes.POINTER(ctypes.c_uint64)
_I64P = ctypes.POINTER(ctypes.c_int64)


def _note_failure(reason: str) -> None:
    global _fail_reason
    _fail_reason = reason
    from pbs_tpu.obs import console

    console.log(f"native: runtime unavailable, pure-Python fallback "
                f"paths in use ({reason})")


#: What each artifact is built from (its freshness stamp hashes these).
_RUNTIME_SOURCES = ("pbst_runtime.cc", "Makefile")
_FASTCALL_SOURCES = ("pbst_fastcall.cc", "pbst_runtime.cc", "Makefile")


def _source_hash(sources: tuple[str, ...]) -> str:
    h = hashlib.sha256()
    for name in sources:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _fresh(artifact: str, sources: tuple[str, ...]) -> bool:
    """True when ``artifact`` exists and its stamp names the sources
    now in the tree."""
    try:
        with open(artifact + ".srchash") as f:
            return (os.path.exists(artifact)
                    and f.read().strip() == _source_hash(sources))
    except OSError:
        return False


def _build(artifact: str, sources: tuple[str, ...],
           target: str | None = None) -> bool:
    """``make -B`` the artifact (its mtime says nothing after a copy)
    and stamp it with the hash of what it was built from."""
    stamp = artifact + ".srchash"
    try:
        # A failed build must not leave a stamp vouching for an old .so.
        if os.path.exists(stamp):
            os.unlink(stamp)
        want = _source_hash(sources)
        proc = subprocess.run(
            ["make", "-B", "-C", os.path.abspath(_NATIVE_DIR)]
            + ([target] if target else []),
            capture_output=True, text=True, timeout=120,
        )
    except Exception as e:  # no make, sandboxed exec, timeout, ...
        _note_failure(f"build not attempted: {type(e).__name__}: {e}")
        return False
    if proc.returncode != 0 or not os.path.exists(artifact):
        # The actionable part of a failed make is the stderr tail (the
        # compiler error), not the whole transcript.
        tail = " | ".join(
            (proc.stderr or proc.stdout or "").strip().splitlines()[-4:])
        _note_failure(f"make exited {proc.returncode}: {tail[:400]}")
        return False
    tmp = f"{stamp}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(want + "\n")
    os.replace(tmp, stamp)
    return True


def _declare(lib: ctypes.CDLL) -> None:
    lib.pbst_ledger_slot_words.restype = ctypes.c_int
    lib.pbst_ledger_reset.argtypes = [_U64P, ctypes.c_int64]
    lib.pbst_ledger_resume.argtypes = [
        _U64P, ctypes.c_int64, ctypes.c_uint64, _U64P]
    lib.pbst_ledger_suspend.argtypes = [_U64P, ctypes.c_int64, _U64P]
    lib.pbst_ledger_add.argtypes = [
        _U64P, ctypes.c_int64, ctypes.c_int, ctypes.c_uint64]
    lib.pbst_ledger_add_many.argtypes = [_U64P, ctypes.c_int64, _U64P]
    lib.pbst_ledger_snapshot.argtypes = [
        _U64P, ctypes.c_int64, _U64P, ctypes.c_int]
    lib.pbst_ledger_snapshot.restype = ctypes.c_int
    lib.pbst_ledger_tsc_start.argtypes = [_U64P, ctypes.c_int64]
    lib.pbst_ledger_tsc_start.restype = ctypes.c_uint64
    lib.pbst_ledger_snapshot_many.argtypes = [
        _U64P, ctypes.c_int64, _I64P, ctypes.c_int, _U64P, ctypes.c_int]
    lib.pbst_ledger_snapshot_many.restype = ctypes.c_int
    lib.pbst_hist_record.argtypes = [
        _U64P, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int]
    lib.pbst_hist_record_many.argtypes = [
        _U64P, ctypes.c_int64, _I64P, _U64P, ctypes.c_int, ctypes.c_int]
    lib.pbst_hist_record_many.restype = ctypes.c_int
    lib.pbst_trace_init.argtypes = [_U64P, ctypes.c_uint64]
    lib.pbst_trace_emit.argtypes = [_U64P] + [ctypes.c_uint64] * 8
    lib.pbst_trace_emit.restype = ctypes.c_int
    lib.pbst_trace_emit_many.argtypes = [_U64P, _U64P, ctypes.c_int]
    lib.pbst_trace_emit_many.restype = ctypes.c_int
    lib.pbst_trace_consume.argtypes = [_U64P, _U64P, ctypes.c_int]
    lib.pbst_trace_consume.restype = ctypes.c_int
    lib.pbst_trace_lost.argtypes = [_U64P]
    lib.pbst_trace_lost.restype = ctypes.c_uint64
    # Trace-layout getters, same stale-binary story as the sim ABI
    # getters below: obs/trace.py can assert the ring geometry this
    # .so was compiled with matches its own TRACE_*_WORDS mirrors.
    lib.pbst_trace_rec_words.restype = ctypes.c_int
    lib.pbst_trace_header_words.restype = ctypes.c_int
    _U8P = ctypes.POINTER(ctypes.c_uint8)
    lib.pbst_gather_rows.argtypes = [
        _U8P, ctypes.c_uint64, _U64P, ctypes.c_int, ctypes.c_uint64, _U8P]
    lib.pbst_gather_rows.restype = ctypes.c_int
    lib.pbst_db_header_words.restype = ctypes.c_int
    lib.pbst_db_init.argtypes = [_U64P, ctypes.c_uint64]
    lib.pbst_db_valid.argtypes = [_U64P]
    lib.pbst_db_valid.restype = ctypes.c_int
    lib.pbst_db_send.argtypes = [_U64P, ctypes.c_uint64]
    lib.pbst_db_send.restype = ctypes.c_uint64
    lib.pbst_db_pending.argtypes = [_U64P, ctypes.c_uint64]
    lib.pbst_db_pending.restype = ctypes.c_uint64
    lib.pbst_db_take.argtypes = [_U64P, ctypes.c_uint64]
    lib.pbst_db_take.restype = ctypes.c_uint64
    lib.pbst_db_seq.argtypes = [_U64P]
    lib.pbst_db_seq.restype = ctypes.c_uint64
    lib.pbst_db_wait.argtypes = [_U64P, ctypes.c_uint64, ctypes.c_uint64]
    lib.pbst_db_wait.restype = ctypes.c_uint64
    # Sweep-mode sim dispatch core (pbst_sim_run family). The ABI/word
    # getters let the marshaller (sim/native_core.py) assert that the
    # layout it builds is the layout this .so was compiled with — a
    # stale binary degrades to the Python engine instead of reading a
    # shifted state block.
    _F64P = ctypes.POINTER(ctypes.c_double)
    for fn in ("pbst_sim_abi", "pbst_sim_gs_words", "pbst_sim_js_words",
               "pbst_sim_jf_words", "pbst_sim_ev_words"):
        getattr(lib, fn).restype = ctypes.c_int64
    lib.pbst_sim_run.restype = ctypes.c_int64
    lib.pbst_sim_run.argtypes = [
        _I64P, _F64P, _I64P, _F64P, _U64P, _U64P,  # gs gf js jf ctr prev
        _I64P, _F64P,                               # ph_i ph_f
        _I64P, _I64P, _F64P, _I64P,                 # heap runq window hist
        _U64P, _U64P, _U64P, _U64P, _U64P,          # rng/wt/ww/qt/qq tabs
        _I64P,                                      # ev
    ]


def load() -> ctypes.CDLL | None:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _LIB_OVERRIDE:
            if not os.path.exists(_LIB_PATH):
                # make only knows how to produce the default artifact;
                # an override names exactly one file, so a missing one
                # is the caller's bug, not a build trigger.
                _note_failure(
                    f"PBST_NATIVE_LIB={_LIB_PATH} does not exist")
                return None
        elif not _fresh(_LIB_PATH, _RUNTIME_SOURCES):
            if not _build(_LIB_PATH, _RUNTIME_SOURCES):
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _declare(lib)
            _lib = lib
        except (OSError, AttributeError) as e:
            _note_failure(f"load failed: {type(e).__name__}: {e}")
        return _lib


def available() -> bool:
    return load() is not None


_FC_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "pbst_fastcall.so"))
_fc = None
_fc_tried = False


def fastcall():
    """The METH_FASTCALL binding module (native/pbst_fastcall.cc), or
    None. A tier ABOVE the ctypes bindings, not a replacement: it
    wraps the same C entry points with ~100 ns call overhead instead
    of ctypes' ~700 ns, and needs Python.h to build — hosts without
    the headers (or any import problem) stay on ctypes, with the
    reason cached for :func:`last_failure` consumers (the `pbst perf`
    report stamp) and logged to the console ring."""
    global _fc, _fc_tried
    if load() is None:
        return None  # no base library — reason already cached
        # (outside _lock: load() takes the same non-reentrant lock)
    with _lock:
        if _fc is not None or _fc_tried:
            return _fc
    # Build OUTSIDE the lock: a 120 s make held under it would convoy
    # every ring/ledger constructor. make is idempotent, so a racing
    # duplicate build is wasteful but harmless; the import below is
    # serialized again. A failed build (no Python.h) leaves no stamp
    # and the freshness check below decides.
    if not _fresh(_FC_PATH, _FASTCALL_SOURCES):
        _build(_FC_PATH, _FASTCALL_SOURCES, target="fastcall")
    with _lock:
        if _fc is not None or _fc_tried:
            return _fc
        _fc_tried = True
        if not _fresh(_FC_PATH, _FASTCALL_SOURCES):
            _note_failure("fastcall tier unavailable (Python.h or "
                          "toolchain missing); ctypes tier in use")
            return None
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "pbst_fastcall", _FC_PATH)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            for sym in ("trace_emit", "trace_emit_many",
                        "trace_consume", "hist_record",
                        "hist_record_many", "ledger_snapshot_many",
                        "sim_run"):
                if not hasattr(mod, sym):
                    raise AttributeError(f"stale fastcall .so: {sym}")
            _fc = mod
        except Exception as e:  # stale ABI, wrong interpreter, ...
            _fc = None
            _note_failure(f"fastcall import failed "
                          f"({type(e).__name__}: {e}); ctypes tier "
                          "in use")
        return _fc


def unavailable_reason() -> str | None:
    """Why :func:`load` returned None (build/load failure), or None
    when the library is loadable or no attempt failed yet. Cached so
    ``pbst perf`` and test skip messages can say WHY the fast paths
    are off instead of reporting a silent slowdown."""
    load()
    return None if _lib is not None else (
        _fail_reason or "never attempted")


def last_failure() -> str | None:
    """The most recent cached failure from ANY tier — including a
    fastcall build/import failure on a host whose base library loads
    fine (where :func:`unavailable_reason` correctly reports None).
    ``pbst perf``'s report stamp carries this so "why am I on the
    ctypes tier" has an answer."""
    return _fail_reason


def as_u64p(arr: np.ndarray):
    """uint64 pointer into a (C-contiguous) numpy array's buffer."""
    assert arr.dtype == np.uint64 and arr.flags["C_CONTIGUOUS"]
    return arr.ctypes.data_as(_U64P)


def as_i64p(arr: np.ndarray):
    """int64 pointer into a (C-contiguous) numpy array's buffer (slot
    index vectors for the *_many entry points)."""
    assert arr.dtype == np.int64 and arr.flags["C_CONTIGUOUS"]
    return arr.ctypes.data_as(_I64P)


def as_f64p(arr: np.ndarray):
    """float64 pointer into a (C-contiguous) numpy array's buffer (the
    sim core's float state blocks and pre-drawn jitter streams)."""
    assert arr.dtype == np.float64 and arr.flags["C_CONTIGUOUS"]
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
