"""Trace buffers: lockless event rings + taxonomy (xentrace analog).

Reference: per-CPU lockless trace rings in xen-heap pages
(``xen-4.2.1/xen/common/trace.c:53-120``, producers behind
``tb_init_done``), a structured event taxonomy (``TRC_SCHED_*`` etc.,
``xen/include/public/trace.h:35-74``), drained by the ``xentrace`` CLI
and post-processed by ``xentrace_format``; ``xenbaked``/``xenmon``
digest scheduler events into per-domain histories.

Here: one ring per executor over a flat u64 buffer (native SPSC ring in
``native/pbst_runtime.cc`` when available, Python fallback otherwise),
records of (timestamp, event, 6 args), a lost-record counter instead of
blocking, and host-side formatting/digestion in ``pbs_tpu.cli``.

**Full-ring contract** (the flight recorder, docs/TRACING.md): a ring
with NO consumer attached overwrites its oldest record, so a process
that runs for an hour with nobody draining it still holds its newest
``capacity`` records (``lost`` counts the records overwritten). A ring
WITH a consumer (``file_backed(attach=True)``, ``attach_consumer()``,
or simply the first ``consume()``: draining is attaching) keeps the
drop-new contract: the tail is the consumer's, a drained stream is
never torn, ``lost`` counts the drops. In-process readers that must not
become the consumer use ``peek`` and :func:`live_rings`.

**Hot-path contract** (``pbst perf`` pins it in both modes,
docs/PERF.md): ``emit`` writes the whole record with ONE
``struct.pack_into`` (no per-word store loop, nothing allocated per
event) — or one sub-µs vectorcall when the native runtime is loaded;
``emit_many``/``consume``/``peek`` move records in at most two
contiguous slice copies each (wrap-aware; one
``pbst_trace_emit_many``/``pbst_trace_consume`` C call when native);
and producers with bursty event streams stage through
:class:`EmitBatch` so N events cost one batched ring write instead of
N scalar ones. Native and Python paths are byte-identical — same ring
bytes, same drop counters (tests/test_native_fastpath.py).

**Batched-writer concurrency contract** (mirrors the ledger's): the
pure-Python vectorized producer paths (``emit_many``, and any
``EmitBatch`` over a non-native ring) are plain slice stores + a
header store with no fences — in-process SPSC is always safe (stores
are program-ordered under the GIL), and a cross-process consumer
attached to a file-backed ring is safe on TSO hosts (x86: the head
store cannot pass the record stores). A cross-process producer
needing release semantics on weaker memory models must use the native
paths (scalar ``emit`` or ``emit_many``, whose head store is an
atomic release).
"""

from __future__ import annotations

import contextlib
import enum
import functools
import gc
import os
import struct
import threading
import time
import types
import weakref
import zlib
from typing import Iterator

import numpy as np

from pbs_tpu import knobs
from pbs_tpu.utils.params import integer_param

TRACE_HEADER_WORDS = 8
TRACE_REC_WORDS = 8
#: Header word that says a consumer owns the tail (drop-new when full);
#: 0 = flight recorder (overwrite-oldest). Words 5..7 are reserved.
_W_CONSUMER = 4

# EmitBatch staging watermarks, declared in the knob registry
# (obs.trace.emit_batch_*): how many records one producer stages, and
# the staged-timestamp span that forces a flush.
EMIT_BATCH_CAPACITY = knobs.default("obs.trace.emit_batch_capacity")
EMIT_BATCH_FLUSH_NS = knobs.default("obs.trace.emit_batch_flush_ns")

_U64_MASK = 2**64 - 1

#: Pack formats for a record prefix of 2 + k words (k = 0..6 args):
#: one C-level struct.pack_into per staged/emitted record replaces the
#: per-word memoryview store loop — the "sub-µs emit" path. The
#: out-of-range fallback masks args exactly like the old store loop.
_PACK_FMTS = tuple("<" + "Q" * (2 + k) for k in range(7))
#: Zero padding for the unwritten tail words of a short record.
_ZERO_TAIL = tuple(bytes((6 - k) * 8) for k in range(7))

# ``tbuf_size=`` boot param analog (xen/common/trace.c): default ring
# capacity in records for rings whose creator doesn't size them. Sized
# so that a whole benchmark run fits with room to spare: the busiest
# cell (colo-train-serve) writes about 200 records a second across its
# rings (32 quanta/s x PICK + EXEC_STEP + DESCHED, 16 engine ticks/s x
# TICK + KEYSPLIT + DECODE, a dozen records a request at 3 requests/s)
# over a 51 s window plus ~25 s of set-up = ~15,000 records if they all
# shared one ring; 32768 is twice that, 2 MiB a ring. A longer run
# keeps its newest 32768 (the full-ring contract above).
_tbuf_size = integer_param("tbuf_size", 32768)


class Ev(enum.IntEnum):
    """Event taxonomy (TRC_* analog, public/trace.h:35-74). The top
    byte is the subsystem class, like TRC_SCHED/TRC_MEM/..."""

    # scheduler class (0x01xx)
    SCHED_PICK = 0x0101  # args: ctx_slot, quantum_ns
    SCHED_DESCHED = 0x0102  # args: ctx_slot, ran_ns, credit_mu
    SCHED_WAKE = 0x0103  # args: ctx_slot, boosted
    SCHED_SLEEP = 0x0104  # args: ctx_slot
    SCHED_STEAL = 0x0105  # args: ctx_slot, from_ex, to_ex
    SCHED_PARK = 0x0106  # args: ctx_slot
    SCHED_UNPARK = 0x0107  # args: ctx_slot
    SCHED_ACCT = 0x0108  # args: acct_count, weight_total
    # feedback class (0x02xx)
    FB_TICK = 0x0201  # args: job_slot, stall_rate_x1000, tslice_us
    FB_GROW = 0x0202  # args: job_slot, new_tslice_us
    FB_SHRINK = 0x0203  # args: job_slot, new_tslice_us
    FB_RESET = 0x0204  # args: job_slot
    # job lifecycle (0x03xx)
    JOB_ADD = 0x0301  # args: job_slot, n_contexts, weight
    JOB_REMOVE = 0x0302
    JOB_DONE = 0x0303
    JOB_FAILED = 0x0304  # args: ctx_slot
    # checkpoint (0x04xx)
    CKPT_BEGIN = 0x0401  # args: job_slot, step
    CKPT_END = 0x0402  # args: job_slot, bytes, dur_ns
    # contention channel (0x05xx) — the vcrd_op analog
    CONTENTION = 0x0501  # args: job_slot, wait_ns, events
    # serving gateway (0x06xx) — the front-door class (docs/GATEWAY.md);
    # tenant_slot is the gateway's stable per-tenant index, cls is the
    # SLO-class index (0=interactive, 1=batch)
    GW_ADMIT = 0x0601  # args: tenant_slot, cls, cost, queue_depth
    GW_SHED = 0x0602  # args: tenant_slot, cls, reason_code, retry_after_ns
    GW_DISPATCH = 0x0603  # args: tenant_slot, cls, backend_slot, qdelay_ns
    GW_COMPLETE = 0x0604  # args: tenant_slot, cls, backend_slot, service_ns
    GW_REQUEUE = 0x0605  # args: tenant_slot, cls, backend_slot
    GW_QDELAY = 0x0606  # args: cls, p50_ns, p99_ns, shed_ppm
    # telemetry sampling (0x07xx) — the i-mode overflow path
    # (telemetry/sampler.py): one record per threshold crossing, staged
    # through an EmitBatch so a quantum's firings cost one ring write
    TELEM_OVERFLOW = 0x0701  # args: ledger_slot, sample_id, counter, value
    # request spans (0x08xx) — the causal request timeline through the
    # serving tier (docs/TRACING.md; pbs_tpu.obs.spans). ``span`` is
    # the recorder-interned id of the gateway rid (stitching key across
    # federated members), ``member`` the interned gateway name. All
    # emitted through the SpanRecorder's EmitBatch, never scalar.
    SPAN_ADMIT = 0x0801  # args: span, tenant_slot, cls, cost, member
    SPAN_SHED = 0x0802  # args: tenant_slot, cls, reason_code, member
    SPAN_ENQUEUE = 0x0803  # args: span, tenant_slot, cls, member
    SPAN_DISPATCH = 0x0804  # args: span, backend_slot, qdelay_ns,
    #                               deficit_x1000, member
    SPAN_EXEC = 0x0805  # args: span, backend_slot, member,
    #                           engine_rid + 1 (0: the backend has none)
    SPAN_COMPLETE = 0x0806  # args: span, backend_slot, service_ns,
    #                               latency_ns, member
    SPAN_REQUEUE = 0x0807  # args: span, backend_slot, member
    SPAN_HANDOFF = 0x0808  # args: span, from_member, to_member
    SPAN_RECOVER = 0x0809  # args: span, member, generation — crash
    #   recovery re-anchored this request's chain (docs/DURABILITY.md):
    #   legal from ANY state (including as the chain's first record
    #   when the pre-crash span records died in a staging batch) and
    #   resets the chain to QUEUED — recovery requeues everything it
    #   recovers, and a COMPLETE whose frame never committed may
    #   legitimately be followed by a re-execution.
    # autopilot decisions (0x09xx) — the self-tuning loop's audit trail
    # (docs/AUTOPILOT.md; pbs_tpu.autopilot). Emitted through the
    # shared SpanRecorder ring so every decision lands in emission
    # order next to the request chains it affected; the assembler
    # ignores the class, chain validation is untouched.
    AP_PROPOSE = 0x0901  # args: cand_score_x1e6, live_score_x1e6,
    #                            margin_x1e6 (i64 two's complement —
    #                            scores can be negative), injected
    AP_CANARY = 0x0902  # args: n_members, guard_window_ns
    AP_PROMOTE = 0x0903  # args: n_members, reserved
    AP_ROLLBACK = 0x0904  # args: reason_code, max_burn_x1000
    # serving engine (0x0Axx) — the inside of ContinuousBatcher.step
    # (models/serving.py; docs/TRACING.md "Engine and executed-step
    # records"). One record per span, written when the span ENDS:
    # ``ts_ns`` is the span's START on the ring clock
    # (time.monotonic_ns), durations are arguments. ``tick`` is the
    # engine's tick sequence number (its ``steps`` at entry): every
    # child of a tick carries it; ``rid`` is the engine request id,
    # which SPAN_EXEC's fourth argument carries +1 on the gateway side.
    ENG_TICK = 0x0A01  # args: dur_ns, tick, active_slots, admitted,
    #                          retired, queue_len
    ENG_ADMIT = 0x0A02  # args: tick, rid, slot, prompt_len, wait_ns
    #                           (submit -> slot, engine latency clock),
    #                           dur_ns (-> first token on the host: the
    #                           stamp its ENG_PREFILL's sync_ns ends at)
    ENG_PREFILL = 0x0A03  # args: tick, rid, slot, dispatch_ns (call
    #                             returns), sync_ns (-> first token on
    #                             the host: the last admission of a call
    #                             is read once the call's decode is
    #                             enqueued behind its forward, so this
    #                             holds that dispatch and ends where the
    #                             ENG_DECODE's sync_ns ends), rows (the
    #                             padded length the prompt forward ran
    #                             at, its rung; 0:
    #                             a prefix hit, cached KV was installed
    #                             and no prompt forward ran)
    ENG_KEYSPLIT = 0x0A04  # args: tick, dur_ns
    ENG_DECODE = 0x0A05  # one a step() that enqueues a decode. args:
    #                      tick, pre_ns (-> this call's program
    #                      enqueued), sync_ns (-> everything this call
    #                      books on the host: the decode the call before
    #                      enqueued and the first token of the admission
    #                      this call left unread; step_settled(): this
    #                      call's own decode too; 0 where there is
    #                      nothing to book), post_ns (emit and retire
    #                      loops -> step returns), overlapped (1: the
    #                      program was enqueued behind work the host had
    #                      not waited for, the decode before it or this
    #                      call's prompt forward; 0: onto a device the
    #                      host had drained)
    ENG_RETIRE = 0x0A06  # args: tick, rid, slot, tokens, ttft_ns,
    #                            latency_ns (engine latency clock)
    ENG_ROUTE = 0x0A07  # one a prefill and one a decode of a program
    #                     that routes tokens to experts, stamped like that
    #                     ENG_PREFILL, or like the ENG_DECODE (ts and tick)
    #                     of the step() that read the decode. args: tick,
    #                     tokens routed, assignments to held experts, to
    #                     absent ones, held experts touched (the last
    #                     three summed over expert layers), largest load
    #                     of one expert. A layer held whole (held = all
    #                     the router scores) counts no absent assignment
    ENG_SELECT = 0x0A08  # one a prefill and one a decode of a program
    #                      with a layer that chooses its positions
    #                      (models/plan.MlaKind), from the host's slot
    #                      table, no device read: stamped like that
    #                      ENG_PREFILL, or like the ENG_DECODE (ts and
    #                      tick) of the step() that enqueued the decode.
    #                      args: tick, rows (a decode's busy lanes, a
    #                      prefill's prompt tokens), live positions (a
    #                      query sees them: summed over the rows),
    #                      chosen positions (min(live, topk) a row,
    #                      summed), topk, blocks (a decode's: the (lane,
    #                      block) pairs its one-pass attention streams,
    #                      cursor // block + 1 a busy lane, summed; a
    #                      prefill's: the (query block, key block)
    #                      pairs its one-pass attention runs,
    #                      mla.ingest_pairs; 0 where the jax.numpy
    #                      form runs); each of one such layer
    ENG_ATTEND = 0x0A09  # one a decode of a program whose softmax
    #                      layers over keys and values stream a lane's
    #                      live blocks (ops/kv_attend.py), from the
    #                      host's slot table, no device read: stamped
    #                      like the ENG_DECODE (ts and tick) of the
    #                      step() that enqueued the decode. args: tick,
    #                      busy lanes, live positions (a busy lane's
    #                      query sees them: summed), blocks fetched
    #                      (cursor // block + 1 a busy lane, no more
    #                      than the layer keeps; 1 an idle lane; summed
    #                      over those layers), blocks their caches
    #                      have (lanes x kept / block a layer, summed),
    #                      the layers. None where the jax.numpy form
    #                      runs (a CPU, a mesh, a shape the kernel's
    #                      tiling does not take). Heads narrower than a
    #                      row of 128 lanes lie several to a row
    #                      (slot_programs.kv_pack): the blocks are of
    #                      those rows. The recurrent kinds' scopes
    #                      (attn.kda, attn.mamba, attn.mamba2, attn.conv)
    #                      add no record: their bytes follow from the
    #                      busy lanes
    ENG_DRAFT = 0x0A0A  # one a decode of a program that drafts for
    #                     itself (a plan with a drafting block), written
    #                     when the host READS that decode and books its
    #                     tokens (ContinuousBatcher._book_window), ts
    #                     the booking's own: that is when the host
    #                     learns what the tick accepted. args: tick (of
    #                     the step() that read it), lanes (that the
    #                     decode ran), drafts proposed (one a lane of
    #                     them that still holds the request it held
    #                     then: the others book nothing), drafts
    #                     accepted (lanes the device advanced by two),
    #                     tokens booked, tokens dropped (computed behind
    #                     the token that finished a request: past its
    #                     budget or behind its EOS; never served)
    # executed step (0x0Bxx) — TpuBackend._invoke (telemetry/source.py):
    # one record per host-callable unit, inside its SCHED_PICK..DESCHED.
    EXEC_STEP = 0x0B01  # args: ctx_slot, dispatch_ns (fn returns),
    #                           wait_ns (block_until_ready), compile_ns,
    #                           job_tag (crc32 of the job name)
    # host (0x0Cxx) — what the process itself did to its threads.
    HOST_GC = 0x0C01  # args: dur_ns, generation, collected
    # Set-up from the inside (docs/TRACING.md "Where a start-up goes").
    # ``ts_ns`` of HOST_START is the process's own start on the ring
    # clock; the three durations count from it and ascend.
    HOST_START = 0x0C02  # args: pkg_ns (-> pbs_tpu first imported),
    #                            import_ns (-> the entry point's imports
    #                            done, the last stamp before the backend
    #                            is asked for), backend_ns (-> the JAX
    #                            backend answered), ask_ns (of that,
    #                            setup_compilation_cache's own question:
    #                            the backend's start where nothing asked
    #                            before it, microseconds where it was
    #                            up), devices, flags (START_* below)
    HOST_COMPILE = 0x0C03  # one an outermost JAX compile event, ts_ns
    #                        its start. args: kind (COMPILE_KINDS),
    #                        wall_ns, fun tag (job_tag of JAX's
    #                        fun_name), scope tag (the attribute() scope
    #                        in force; 0: ambient), cache (CACHE_*; a
    #                        backend event's verdict), retrieval_ns (a
    #                        hit's read of the persistent cache)
    HOST_PHASE = 0x0C04  # one a named span of a constructor
    #                      (host_phase), ts_ns its start. args: name tag,
    #                      wall_ns, compile_ns (the meter's wall inside
    #                      it, this thread), size (rows, lanes, bytes:
    #                      the span's own), scope tag (what its
    #                      HOST_COMPILEs carry; 0: none)


#: HOST_START.flags: the origin is pbs_tpu's first import, because
#: /proc/self/stat could not be read or CLOCK_BOOTTIME and the ring
#: clock have parted (a suspended host).
START_FROM_IMPORT = 1
#: HOST_COMPILE.kind, in the order JAX fires them for one program.
COMPILE_KINDS = ("trace", "lower", "backend")
#: HOST_COMPILE.cache: not asked (below JAX's thresholds, or the cache
#: is off), served by the persistent cache, compiled and written to it.
CACHE_NONE, CACHE_HIT, CACHE_MISS = 0, 1, 2

#: tag -> the name it was made from, for whoever prints a record.
_tag_names: dict[int, str] = {0: "-"}


@functools.lru_cache(maxsize=None)
def job_tag(name: str) -> int:
    """The stable u32 that a record carries for a name (EXEC_STEP's job,
    HOST_COMPILE's function and scope, HOST_PHASE's span; crc32: str
    hashing is salted per process). The name is kept for
    :func:`tag_name`."""
    tag = zlib.crc32(name.encode())
    _tag_names[tag] = name
    return tag


def tag_name(tag: int) -> str:
    """The name ``tag`` was made from in this process, else its hex."""
    return _tag_names.get(tag) or f"0x{tag:08x}"


class TraceBuffer:
    """One SPSC ring. Producer: an executor. Consumer: a monitor."""

    def __init__(self, capacity: int | None = None, buf=None,
                 native: bool | str | None = None, _attach: bool = False):
        # ``native``: None auto-detects, True requires the C library,
        # False pins the pure-Python paths, "ctypes" pins the ctypes
        # binding tier (native minus the fastcall accelerator — the
        # tier a host without Python.h runs; tests/benches use it).
        self.capacity = capacity = (
            capacity if capacity is not None else _tbuf_size.value)
        nwords = TRACE_HEADER_WORDS + capacity * TRACE_REC_WORDS
        if buf is None:
            buf = bytearray(nwords * 8)
        self._arr = np.frombuffer(memoryview(buf), dtype="<u8", count=nwords)
        # Cached header/word views: plain-int loads and stores with no
        # numpy scalar boxing on the per-event path. Native-endian 'Q'
        # over the '<u8' layout — this framework targets little-endian
        # hosts (the native library shares the same assumption).
        words = memoryview(buf)[: nwords * 8].cast("B").cast("Q")
        self._hdr = words[:TRACE_HEADER_WORDS]
        self._words = words
        # Byte view for struct.pack_into: the pure-Python emit writes
        # the whole record in one C call, no per-word store loop.
        self._bytes = memoryview(buf)[: nwords * 8].cast("B")
        self._nat = None
        self._ptr = None
        self._fc = None
        self._addr = 0
        if native is not False:
            from pbs_tpu.runtime import native as native_mod

            lib = native_mod.load()
            if lib is not None:
                self._nat = lib
                self._ptr = native_mod.as_u64p(self._arr)
                # Fastcall tier (native/pbst_fastcall.cc): same C entry
                # points, ~7x lower call overhead than ctypes. The
                # address is cached once — .ctypes.data costs µs per
                # access. native="ctypes" pins the ctypes tier (tests).
                if native != "ctypes":
                    self._fc = native_mod.fastcall()
                    self._addr = self._arr.ctypes.data
            elif native is True:
                raise RuntimeError("native runtime requested but unavailable")
        if _attach:
            return  # consumer attach: the producer owns the header
        if self._nat is not None:
            self._nat.pbst_trace_init(self._ptr, capacity)
        else:
            self._arr[:TRACE_HEADER_WORDS] = 0
            self._arr[2] = capacity

    @classmethod
    def file_backed(cls, path: str, capacity: int | None = None,
                    native: bool | str | None = None,
                    attach: bool = False) -> "TraceBuffer":
        """Ring over a shared mmap — xenbaked's view of the hypervisor
        trace pages (``tools/xenmon/xenbaked.c`` maps the per-CPU rings
        dom0-side). ``attach=True`` joins an existing producer's ring as
        the (single) consumer: the header is left alone and capacity
        comes from the file. The mapping is read-write either way — the
        consumer must advance the shared tail word."""
        import mmap
        import os

        if attach:
            fd = os.open(path, os.O_RDWR)
            try:
                mm = mmap.mmap(fd, os.fstat(fd).st_size)
            finally:
                os.close(fd)
            cap = int(np.frombuffer(mm, dtype="<u8", count=3)[2])
            tb = cls(cap, buf=mm, native=native, _attach=True)
            tb.attach_consumer()
        else:
            capacity = capacity if capacity is not None else _tbuf_size.value
            nbytes = (TRACE_HEADER_WORDS + capacity * TRACE_REC_WORDS) * 8
            fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
            try:
                if os.fstat(fd).st_size < nbytes:
                    os.ftruncate(fd, nbytes)
                mm = mmap.mmap(fd, nbytes)
            finally:
                os.close(fd)
            tb = cls(capacity, buf=mm, native=native)
        tb._mmap = mm
        return tb

    # -- full-ring contract ----------------------------------------------

    def attach_consumer(self) -> None:
        """From now on the tail is a consumer's: a full ring drops the
        new record instead of overwriting the oldest."""
        self._hdr[_W_CONSUMER] = 1

    def detach_consumer(self) -> None:
        """Back to the flight recorder: nobody drains this ring any
        more, so a full ring keeps its newest records."""
        self._hdr[_W_CONSUMER] = 0

    @property
    def has_consumer(self) -> bool:
        return bool(self._hdr[_W_CONSUMER])

    # -- producer --------------------------------------------------------

    def emit(self, ts_ns: int, event: int, *args: int) -> bool:
        fc = self._fc
        if fc is not None:
            # Sub-µs native emit: one vectorcall, args masked in C.
            if len(args) > 6:
                args = args[:6]
            return fc.trace_emit(self._addr, ts_ns, event, *args)
        if self._nat is not None:
            a = [int(x) & _U64_MASK for x in args[:6]]
            a += [0] * (6 - len(a))
            return bool(
                self._nat.pbst_trace_emit(self._ptr, ts_ns, int(event), *a))
        hdr = self._hdr
        head = hdr[0]
        cap = self.capacity
        if head - hdr[1] >= cap:
            hdr[3] += 1
            if hdr[_W_CONSUMER]:
                return False
            hdr[1] = head - cap + 1  # overwrite the oldest
        off = (TRACE_HEADER_WORDS + (head % cap) * TRACE_REC_WORDS) * 8
        n = len(args)
        if n > 6:
            args = args[:6]
            n = 6
        b = self._bytes
        try:
            # Fast path: every field already a 0..2^64-1 int — one C
            # pack writes the whole record prefix.
            struct.pack_into(_PACK_FMTS[n], b, off, ts_ns, event, *args)
        except struct.error:
            # Every field masks to two's complement — including
            # ts_ns/event, matching the native tiers (a negative
            # clock-skew timestamp must not raise on one tier and
            # record on another).
            struct.pack_into(
                _PACK_FMTS[n], b, off, int(ts_ns) & _U64_MASK,
                int(event) & _U64_MASK,
                *[int(x) & _U64_MASK for x in args])
        if n < 6:
            b[off + (2 + n) * 8:off + TRACE_REC_WORDS * 8] = _ZERO_TAIL[n]
        hdr[0] = head + 1
        return True

    def emit_many(self, recs: np.ndarray) -> int:
        """Batched emit of an ``(n, 8)`` u64 record array in at most two
        contiguous slice copies (wrap-aware). Returns the number of
        records accepted. With a consumer attached, records that don't
        fit are dropped tail-first with the lost counter charged; with
        none, the oldest records make room (``lost`` counts them) and
        all ``n`` are accepted — exactly the semantics of ``n`` scalar
        :meth:`emit` calls. See the module docstring for the
        batched-writer concurrency contract."""
        recs = np.ascontiguousarray(recs, dtype="<u8")
        if recs.ndim != 2 or recs.shape[1] != TRACE_REC_WORDS:
            raise ValueError(
                f"emit_many wants (n, {TRACE_REC_WORDS}) u64 records, "
                f"got shape {recs.shape}")
        n = recs.shape[0]
        if n == 0:
            return 0
        if self._fc is not None:
            return self._fc.trace_emit_many(self._addr, recs, n)
        if self._nat is not None:
            from pbs_tpu.runtime import native as native_mod

            return int(self._nat.pbst_trace_emit_many(
                self._ptr, native_mod.as_u64p(recs.reshape(-1)), n))
        hdr = self._hdr
        head, tail, cap = hdr[0], hdr[1], self.capacity
        space = cap - (head - tail)
        k = n if n <= space else space
        skip = 0  # leading records of the batch that never land
        if k < n:
            hdr[3] += n - k
            if not hdr[_W_CONSUMER]:
                k = n if n <= cap else cap
                skip = n - k
                hdr[1] = head + n - cap
        if k == 0:
            return 0
        w = TRACE_REC_WORDS
        flat = recs.reshape(-1)[skip * w:]
        arr = self._arr
        start = (head + skip) % cap
        k1 = min(k, cap - start)
        off = TRACE_HEADER_WORDS + start * TRACE_REC_WORDS
        arr[off:off + k1 * w] = flat[:k1 * w]
        if k > k1:
            arr[TRACE_HEADER_WORDS:TRACE_HEADER_WORDS + (k - k1) * w] = (
                flat[k1 * w:k * w])
        hdr[0] = head + skip + k
        return skip + k

    # -- consumer --------------------------------------------------------

    def _copy_out(self, first: int, n: int) -> np.ndarray:
        """Wrap-aware bulk copy of records [first, first+n) into a fresh
        (n, 8) array — one or two contiguous slices, no per-record loop."""
        out = np.empty((n, TRACE_REC_WORDS), dtype="<u8")
        if n:
            flat = out.reshape(-1)
            arr = self._arr
            cap = self.capacity
            start = first % cap
            k1 = min(n, cap - start)
            off = TRACE_HEADER_WORDS + start * TRACE_REC_WORDS
            w = TRACE_REC_WORDS
            flat[:k1 * w] = arr[off:off + k1 * w]
            if n > k1:
                flat[k1 * w:] = arr[
                    TRACE_HEADER_WORDS:TRACE_HEADER_WORDS + (n - k1) * w]
        return out

    def consume(self, max_records: int = 1024) -> np.ndarray:
        """(n, 8) u64 array of drained records. Draining is attaching:
        the first call marks the ring as having a consumer (the
        full-ring contract in the module docstring)."""
        if self._fc is not None:
            out = np.empty(max_records * TRACE_REC_WORDS, dtype="<u8")
            n = self._fc.trace_consume(self._addr, out, max_records)
            return out[: n * TRACE_REC_WORDS].reshape(n, TRACE_REC_WORDS)
        if self._nat is not None:
            from pbs_tpu.runtime import native as native_mod

            out = np.empty(max_records * TRACE_REC_WORDS, dtype="<u8")
            n = self._nat.pbst_trace_consume(
                self._ptr, native_mod.as_u64p(out), max_records)
            return out[: n * TRACE_REC_WORDS].reshape(n, TRACE_REC_WORDS)
        hdr = self._hdr
        if not hdr[_W_CONSUMER]:
            hdr[_W_CONSUMER] = 1
        tail = hdr[1]
        n = min(hdr[0] - tail, max_records)
        recs = self._copy_out(tail, n)
        if n:
            hdr[1] = tail + n
        return recs

    def peek(self, max_records: int = 1024) -> np.ndarray:
        """Last ``max_records`` undrained records WITHOUT consuming them
        — postmortem readers (crash dumps) must not steal records from an
        attached live consumer. Reads the shared header words directly
        (same layout for the native ring), so it also works on a ring the
        native library owns; safe in-process where the producer is
        quiescent or slow relative to the copy."""
        hdr = self._hdr
        head, tail = hdr[0], hdr[1]
        avail = head - tail
        n = min(avail, max_records)
        return self._copy_out(tail + (avail - n), n)  # newest n records

    @property
    def lost(self) -> int:
        if self._nat is not None:
            return int(self._nat.pbst_trace_lost(self._ptr))
        return self._hdr[3]


class EmitBatch:
    """Per-producer staging buffer over one ring: N events become one
    wrap-aware ``emit_many`` instead of N scalar emits.

    Flush happens on a **size watermark** (the staging buffer fills) or
    a **time watermark** (the staged span of event timestamps exceeds
    ``flush_ns`` — timestamps, not wall time, so virtual-clock runs stay
    deterministic), or explicitly via :meth:`flush` (the partition's
    drain/peek paths flush before reading so batched records are never
    invisible to an in-process consumer).

    NOT thread-safe: one batch per producer thread, and only where that
    producer owns the ring (the SPSC contract). Producers needing
    cross-thread ordering keep scalar ``TraceBuffer.emit`` — a staged
    record does not reach the ring until flush, so two threads batching
    into one ring would interleave at flush granularity, not emit order.
    """

    __slots__ = ("ring", "capacity", "flush_ns", "_bytes", "_buf",
                 "_bufp", "_fc_flush", "_n", "_t0", "emitted",
                 "flushes")

    def __init__(self, ring: TraceBuffer, capacity: int = EMIT_BATCH_CAPACITY,
                 flush_ns: int = EMIT_BATCH_FLUSH_NS):
        if capacity <= 0:
            raise ValueError("EmitBatch capacity must be > 0")
        self.ring = ring
        self.capacity = int(capacity)
        self.flush_ns = int(flush_ns)
        # Staging block: a bytearray written by struct.pack_into (one C
        # call per staged record) with a (capacity, 8) u64 numpy view
        # over the same bytes for the flush.
        self._bytes = bytearray(self.capacity * TRACE_REC_WORDS * 8)
        self._buf = np.frombuffer(self._bytes, dtype="<u8").reshape(
            self.capacity, TRACE_REC_WORDS)
        # Precomputed staging pointers: when the ring is native, flush
        # is ONE C call with no per-flush pointer marshalling.
        self._bufp = None
        self._fc_flush = None
        if ring._fc is not None:
            self._fc_flush = (ring._fc.trace_emit_many, ring._addr,
                              self._buf.ctypes.data)
        elif ring._nat is not None:
            from pbs_tpu.runtime import native as native_mod

            self._bufp = native_mod.as_u64p(self._buf.reshape(-1))
        self._n = 0
        self._t0 = -1  # ts of the oldest staged record; -1 = empty
        self.emitted = 0
        self.flushes = 0

    def emit(self, ts_ns: int, event: int, *args: int) -> None:
        off = self._n * (TRACE_REC_WORDS * 8)
        n = len(args)
        if n > 6:
            args = args[:6]
            n = 6
        b = self._bytes
        try:
            struct.pack_into(_PACK_FMTS[n], b, off, ts_ns, event, *args)
        except struct.error:
            struct.pack_into(
                _PACK_FMTS[n], b, off, int(ts_ns) & _U64_MASK,
                int(event) & _U64_MASK,
                *[int(x) & _U64_MASK for x in args])
        if n < 6:
            b[off + (2 + n) * 8:off + TRACE_REC_WORDS * 8] = _ZERO_TAIL[n]
        self._n += 1
        ts_ns = int(ts_ns)
        if self._t0 < 0:
            self._t0 = ts_ns
        if self._n >= self.capacity or ts_ns - self._t0 >= self.flush_ns:
            self.flush()

    def pending(self) -> int:
        return self._n

    def drop_pending(self) -> int:
        """Discard staged records WITHOUT writing them — the kill-9
        model (gateway/chaos.py): records staged in a dead process's
        batch never reached the ring and must not leak into the
        recovered process's stream. Returns the count dropped."""
        n, self._n = self._n, 0
        self._t0 = -1
        return n

    def flush(self) -> int:
        """Push staged records to the ring; returns records written
        (staged minus any the full ring dropped). One
        ``pbst_trace_emit_many`` C call when the ring is native."""
        n, self._n = self._n, 0
        self._t0 = -1
        if not n:
            return 0
        self.flushes += 1
        if self._fc_flush is not None:
            f, ring_addr, buf_addr = self._fc_flush
            written = f(ring_addr, buf_addr, n)
        elif self._bufp is not None:
            ring = self.ring
            written = int(ring._nat.pbst_trace_emit_many(
                ring._ptr, self._bufp, n))
        else:
            written = self.ring.emit_many(self._buf[:n])
        self.emitted += written
        return written


# -- the process's live rings --------------------------------------------

#: owner name -> ring, weakly held: a ring lives exactly as long as its
#: owner does, and a reader in the same process (a benchmark's per-layer
#: reader, a postmortem) reaches every live one without a handle to the
#: tenants that own them.
_live: "weakref.WeakValueDictionary[str, TraceBuffer]" = (
    weakref.WeakValueDictionary())


def register_ring(owner: str, ring: TraceBuffer) -> str:
    """Publish ``ring`` under ``owner`` (``partition:<name>#<lane>``,
    ``gateway:<name>``, ``engine:<name>``, ``exec:<n>``, ``host``). A
    second live ring of the same owner name gets ``~2``, ``~3``...
    appended; the name it was published under is returned."""
    name, n = owner, 1
    while _live.get(name) not in (None, ring):
        n += 1
        name = f"{owner}~{n}"
    _live[name] = ring
    return name


def live_rings() -> list[tuple[str, TraceBuffer]]:
    """The process's rings by owner name, sorted by name. For readers
    that look and do not drain: ``peek``, never ``consume`` (a consume
    would make the reader the ring's consumer and end its flight-
    recorder contract)."""
    return sorted(_live.items())


_host: TraceBuffer | None = None
_gc_t0 = 0
# The host ring has many producers (a full collection, a compile and a
# constructor's span may each end on any thread), a ring one: they take
# turns. Re-entrant, because a collection can start inside an emit.
_host_lock = threading.RLock()
# Records of the host ring, counted on the chip (PERF.md section 6,
# PR 39): a benchmark process writes 19 (the solo trainer) to 73 (the
# co-located cell) before its window, three HOST_COMPILE a program it
# builds, eager jax.numpy calls included, some ten HOST_PHASE and the
# HOST_START, cold as warm, and a handful of HOST_GC in it. 2048 is 28
# times the busiest cell's count, 128 KiB: a process that builds a dozen
# engines (chip_smoke.py's four legs, a long-lived server that reloads)
# still loses none; one that builds hundreds keeps its newest.
HOST_RING_CAPACITY = 2048
#: CLOCK_BOOTTIME and the ring clock (CLOCK_MONOTONIC) count from the
#: same boot; they part by what the host spent suspended. Within the
#: grain of /proc's start time they have not.
_CLOCKS_AGREE_NS = 10_000_000


def _on_gc(phase: str, info: dict) -> None:
    # Generation 2 only: the young generations run hundreds of times a
    # second and take microseconds; a full collection over a serving
    # process's heap is what can stall a tick.
    if info["generation"] < 2:
        return
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.monotonic_ns()
    elif _host is not None and _gc_t0:
        host_emit(_gc_t0, Ev.HOST_GC, time.monotonic_ns() - _gc_t0,
                  info["generation"], info.get("collected", 0))
        _gc_t0 = 0


def host_ring() -> TraceBuffer:
    """The process-wide ``host`` ring, made on first use, and with it
    the process's two taps: full Python collections land there as
    ``HOST_GC`` (start, dur_ns), so that a long gap between a tenant's
    records names its cause or rules one out (docs/TRACING.md "Finding
    a stall"), and every program JAX traces, lowers and compiles or
    loads as ``HOST_COMPILE`` (``telemetry.compile.CompileMeter``)."""
    global _host
    if _host is None:
        with _host_lock:
            if _host is None:
                ring = TraceBuffer(HOST_RING_CAPACITY)
                register_ring("host", ring)
                gc.callbacks.append(_on_gc)
                _host = ring
        # Imported here: telemetry's package reaches back into obs.
        from pbs_tpu.telemetry.compile import CompileMeter

        CompileMeter.install()
    return _host


def host_emit(ts_ns: int, event: int, *args: int) -> None:
    """One record into the ``host`` ring, from whatever thread."""
    ring = host_ring()
    with _host_lock:
        ring.emit(ts_ns, event, *args)


def process_start_ns() -> tuple[int, int]:
    """``(ns, flags)``: when this process began, on the ring clock.
    ``/proc/self/stat`` field 22 counts clock ticks from boot, which is
    where ``time.monotonic()`` counts from on a host that was never
    suspended. Where ``CLOCK_BOOTTIME`` says it was, or ``/proc`` cannot
    be read, the stamp of ``pbs_tpu``'s first import stands in and
    ``flags`` says so (``START_FROM_IMPORT``)."""
    import pbs_tpu

    t_import = pbs_tpu.T_IMPORT_NS
    try:
        with open("/proc/self/stat", "rb") as f:
            # The command's name may hold spaces and parentheses:
            # field 3 follows its last ')'.
            fields = f.read().rsplit(b")", 1)[1].split()
        start = int(fields[19]) * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        parted = abs(time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                     - time.monotonic_ns())
    except (OSError, ValueError, IndexError, AttributeError):
        return t_import, START_FROM_IMPORT
    if parted > _CLOCKS_AGREE_NS or start > t_import:
        return t_import, START_FROM_IMPORT
    return start, 0


@contextlib.contextmanager
def host_phase(name: str, size: int = 0,
               scope: str | None = None) -> Iterator[types.SimpleNamespace]:
    """One ``HOST_PHASE`` record for a named span of a constructor,
    written when it ends: its wall, the compile wall the meter saw
    inside it on this thread (wall less compile is what ran), and one
    size (what is yielded holds it: a span that learns its size inside
    sets ``.size``). With ``scope`` the span's compiles are attributed
    to it (``CompileMeter.attribute``), so that each ``HOST_COMPILE``
    inside names the span it belongs to."""
    from pbs_tpu.telemetry.compile import CompileMeter

    meter = CompileMeter.install()
    with meter.attribute(scope) if scope is not None \
            else contextlib.nullcontext():
        span = types.SimpleNamespace(size=size)
        c0, t0 = meter.thread_wall_ns(), time.monotonic_ns()
        try:
            yield span
        finally:
            host_emit(t0, Ev.HOST_PHASE, job_tag(name),
                      time.monotonic_ns() - t0,
                      meter.thread_wall_ns() - c0, int(span.size),
                      job_tag(scope) if scope is not None else 0)


def merge_records(chunks: list[np.ndarray]) -> np.ndarray:
    """Merge per-ring record batches into one time-sorted stream (the
    xentrace multi-CPU merge). Stable sort keeps same-timestamp records
    in ring order."""
    chunks = [c for c in chunks if len(c)]
    if not chunks:
        return np.empty((0, TRACE_REC_WORDS), dtype="<u8")
    allr = np.concatenate(chunks, axis=0)
    return allr[np.argsort(allr[:, 0], kind="stable")]


def format_records(recs: np.ndarray) -> list[str]:
    """xentrace_format analog: human-readable lines."""
    out = []
    # tolist() converts the whole batch to Python ints in one C pass —
    # per-element numpy scalar boxing dominates the scalar version.
    for ts, ev, *args in np.asarray(recs).tolist():
        try:
            name = Ev(ev).name
        except ValueError:
            name = f"0x{ev:04x}"
        if ev == Ev.HOST_COMPILE:  # kind, wall, function, scope, cache
            args[0] = COMPILE_KINDS[args[0]]
            args[2], args[3] = tag_name(args[2]), tag_name(args[3])
        elif ev == Ev.HOST_PHASE:  # span, wall, compile, size, scope
            args[0], args[4] = tag_name(args[0]), tag_name(args[4])
        out.append(f"[{ts / 1e9:.6f}] {name} {' '.join(map(str, args))}")
    return out


def chrome_trace(recs: np.ndarray, labels: dict[int, str] | None = None,
                 pid: int = 0) -> dict:
    """Convert drained records to the Chrome trace-event format (load
    in chrome://tracing or Perfetto) — the graphical leg of the
    xentrace_format analog. SCHED_PICK/SCHED_DESCHED pairs become
    duration ('X') events on a per-context track (tid = ctx slot, dur
    from the desched's device-true ran_ns); everything else becomes an
    instant event on its slot's track. ``labels`` maps ctx slots to
    display names (e.g. from the ledger sidecar meta)."""
    labels = labels or {}
    events: list[dict] = []
    open_pick: dict[int, int] = {}  # slot -> pick ts
    for ts, ev, *a in np.asarray(recs).tolist():
        slot = a[0] if a else 0
        try:
            name = Ev(ev).name
        except ValueError:
            name = f"0x{ev:04x}"
        if ev == Ev.SCHED_PICK:
            open_pick[slot] = ts
        elif ev == Ev.SCHED_DESCHED and slot in open_pick:
            t0 = open_pick.pop(slot)
            ran_ns = a[1] if len(a) > 1 else ts - t0
            events.append({
                "name": labels.get(slot, f"ctx{slot}"),
                "ph": "X", "cat": "sched",
                "ts": t0 / 1e3, "dur": max(ran_ns, 1) / 1e3,
                "pid": pid, "tid": slot,
                "args": {"ran_ns": ran_ns},
            })
        else:
            events.append({
                "name": name, "ph": "i", "s": "t",
                "cat": name.split("_")[0].lower(),
                "ts": ts / 1e3, "pid": pid, "tid": slot,
                "args": {f"a{i}": v for i, v in enumerate(a)},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
