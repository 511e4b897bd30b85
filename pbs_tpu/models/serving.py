"""Continuous batching: the slot engine, TPU-first.

A fixed pool of decode slots advances one token a step for ALL active
requests; new requests are admitted into free slots at step boundaries
and finished ones retire at once (the lockstep tenant,
``make_serve_step``, makes a late request wait for a whole batch).

- **Static everything**: admission and retirement change DATA
  (per-slot cursors and masks), never shapes, so two XLA programs exist
  (slot-prefill, once a rung, and slot-decode) whatever the traffic,
  all compiled at construction. What they compute (the layer stack, its
  per-slot cache and cursors, the lanes that ride along idle) is
  ``models/slot_programs.py``'s: the engine asks ``slot_program(cfg,
  mlp_fn, mesh)`` for a cache, a decode and an ingest, knows no layer.
- **Host admission between dispatches**: ``step()`` is a
  scheduler-quantum-sized unit (one token across slots), so a serving
  Job under the credit scheduler interleaves with training at token
  granularity.
- **One tick in flight**: ``ContinuousBatcher``'s docstring, and
  docs/SERVING.md "The pipelined tick".
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import Counter, OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.models.generate import _sample
from pbs_tpu.models.mla import ingest_pairs
from pbs_tpu.models.slot_programs import prefill_rungs, slot_program
# benchmarks/families/dense-gqa.py (``sizing``) imports these three from
# here; the next ``benchmark`` PR points it at models/slot_programs.py
# and this line goes (ROADMAP D21).
from pbs_tpu.models.slot_programs import (  # noqa: F401
    _slot_forward, ingest_slot_prompt, init_slot_cache)
from pbs_tpu.models.transformer import TransformerConfig
from pbs_tpu.obs.trace import (
    Ev, TraceBuffer, host_phase, host_ring, register_ring,
)
from pbs_tpu.utils.stats import nearest_rank


# Ring stamps and span durations are host wall time whatever clock the
# latency accounting runs on: they measure host work, and they share
# time.monotonic_ns with every other ring of the process.
_ns = time.monotonic_ns
# A span also opens a profiler annotation of its name: free while no
# profile is being captured, and an operator's xprof capture then shows
# the engine's spans against the device lanes.
_span = jax.profiler.TraceAnnotation

# What the host says of a lane in a decode dispatch, in the place of its
# last token: the lane is off, or its last token is the one the previous
# decode left on the device (not on the host yet). Any value >= 0 is the
# lane's last token itself, where the host has it.
_LANE_OFF = -2
_LANE_CARRY = -1


@dataclasses.dataclass
class Completion:
    request_id: int
    tokens: list[int]
    prompt_len: int
    steps_waited: int  # engine ticks queued before admission
    ttft_s: float = 0.0  # wall time submit -> first token
    latency_s: float = 0.0  # wall time submit -> completion


@dataclasses.dataclass
class _InFlight:
    """A decode that is enqueued and not yet read: what it will send to
    the host, and the lanes it ran with the request each one held at
    dispatch (a lane whose slot has changed hands by the time the
    tokens arrive books nothing)."""
    out: jax.Array    # (n_slots,) tokens, the program's route behind them
    extra: jax.Array  # the FFN's auxiliary sum over layers
    lanes: list[tuple[int, int]]  # (slot, request id)


@dataclasses.dataclass
class _Admission:
    """A request seated in its slot whose first token is enqueued and
    not yet read: what the prompt forward will send to the host, and
    the stamps its ``ENG_PREFILL`` and ``ENG_ADMIT`` are written from
    once the token is there."""
    slot: int
    rid: int
    plen: int
    rows: int         # the rung the forward ran at; 0: a prefix hit
    first: jax.Array  # the token, the program's route behind it
    extra: jax.Array | None  # the FFN's auxiliary sum (None: a hit)
    wait_ns: int      # submit -> slot, on the engine's latency clock
    t_admit: int
    t_prefill: int
    t_dispatched: int


class ContinuousBatcher:
    """The slot engine. Host-side control, two compiled programs.

    ``submit`` enqueues; ``step()`` admits into free slots, advances
    one decode token for every active slot, and returns finished
    :class:`Completion`s. All shapes static: ``n_slots`` lanes, caches
    sized ``max_len``, a prompt padded to the smallest of ``rungs``
    that holds it (``prefill_rungs``: ``prompt_bucket``, the longest
    prompt the engine takes, and its half where a shorter forward is
    a faster one), each rung one compiled instance of the prefill.

    The engine OWNS its cache: every program that takes it donates it
    and writes the new positions in place, so after a call the handle
    that went in is dead and ``self.cache`` is the one that came out.
    A caller that wants to keep K/V slices it out before the next call
    (the prefix cache's windows are such slices: arrays of their own).

    The decode is pipelined one deep (docs/SERVING.md "The pipelined
    tick"): a ``step()`` enqueues its decode and then books the decode
    of the ``step()`` before it, so what it returns, and what
    ``slot_tokens`` holds when it returns, is one dispatch behind what
    the device has run. An admission rides in that pipeline: its prompt
    forward is enqueued behind the decode in flight, leaves the first
    token on the device for the decode this call enqueues behind it,
    and the host reads it (and books it, so ``slot_tokens`` holds it
    when the call returns) once both are on the queue. ``has_work()``
    stays true until the last tick is booked; ``settle()`` books it
    now; ``step_settled()`` is a tick that leaves nothing in flight.

    **A plan with a drafting block drafts for itself**
    (``program.drafts``; docs/SERVING.md "The drafting tick"): the
    decode verifies two positions a lane (the lane's last token and
    the token drafted to follow it) and a lane advances by one token or
    two, which the host learns when it reads. The lanes' cursors, last
    tokens and drafts stay on the device (in the cache), so the next
    dispatch does not wait for that; the host's lane mask allows for a
    decode in flight having spent up to two of a lane's budget, and a
    second token past a budget or behind an EOS is dropped when it is
    booked, never served. Greedy only. Nothing switches it but the
    plan.
    """

    def __init__(self, cfg: TransformerConfig, params: dict,
                 n_slots: int = 4, prompt_bucket: int = 64,
                 max_len: int | None = None, temperature: float = 0.0,
                 eos_id: int | None = None, seed: int = 0,
                 mesh=None, prefix_cache_size: int = 0,
                 clock=None, mlp_fn=None, submit_hook=None):
        self.cfg = cfg
        # Front-door seam (pbs_tpu.gateway): called as
        # ``submit_hook(rid, prompt_len, max_new)`` on EVERY accepted
        # submit — through the gateway or around it — so a gateway-
        # managed engine can count admission bypasses (the runtime twin
        # of the ``gateway-discipline`` static pass, docs/GATEWAY.md).
        self.submit_hook = submit_hook
        # Latency-stat clock: seconds, monotonic. Injectable so TTFT /
        # completion latencies can be accounted in virtual time —
        # deterministic SLO tests and replayable traces (the xentop
        # analog reads the same stats either way).
        self._now = clock or time.monotonic
        # FFN swap (same seam as generate._forward_with_cache_impl):
        # the MoE family serves through this engine via moe_slot_mlp.
        self.mlp_fn = mlp_fn
        # What the configuration's layer stack gives the engine: its
        # cache, a decode position for every slot, a prompt's ingestion.
        self.program = slot_program(cfg, mlp_fn, mesh)
        #: tokens a lane can emit a decode: 2 where the plan has a
        #: drafting block (the program verifies a window of two), else 1
        self._window = 2 if self.program.drafts else 1
        if self._window > 1 and temperature != 0.0:
            raise ValueError(
                "a plan that drafts for itself is served greedy "
                "(temperature=0): a draft is accepted where it is the "
                "stack's own argmax")
        self.n_slots = n_slots
        self.bucket = prompt_bucket
        self.rungs = prefill_rungs(prompt_bucket)
        self.max_len = max_len or cfg.max_seq
        if self.bucket >= self.max_len:
            raise ValueError("prompt_bucket must be < max_len")
        self.temperature = temperature
        self.eos_id = eos_id
        self.mesh = mesh
        if prefix_cache_size and not self.program.windows:
            raise ValueError(
                "prefix_cache_size > 0 needs a prompt window that can be "
                "cut from and installed into every layer's cache: "
                + self.program.no_windows)
        # Set-up from the inside (docs/TRACING.md "Where a start-up
        # goes"): the allocation here and every program warmed below is
        # a HOST_PHASE span of the host ring, each waited for, so that
        # its wall is its own.
        with host_phase("eng.cache") as span:
            cache = self.program.init_cache(n_slots, self.max_len)
            if mesh is not None:
                # Tensor-parallel serving by PLACEMENT: ``params`` came
                # laid out on ``mesh`` (serve.partition.place), the
                # program lays its cache there, and the jitted programs
                # below are unchanged: XLA propagates the shardings.
                cache = self.program.place_cache(cache, mesh)
            span.size = sum(x.nbytes for x in jax.tree.leaves(
                jax.block_until_ready(cache)))
        self.params = params
        self.cache = cache
        #: the layers whose decode attention runs as the kernel that
        #: streams a lane's live blocks, as ``{(kind, positions kept,
        #: positions a block): layers}`` (``live_layers``, lowered):
        #: ``ENG_SELECT`` and ``ENG_ATTEND`` count a tick's blocks by
        #: it; empty where the ``jax.numpy`` forms run
        self._live = dict(sorted(Counter(
            self.program.live_layers(cache, lowered=True).values()).items()))
        #: the rungs at which a prefill's latent layers attend through
        #: the kernel that streams the key blocks a block of queries can
        #: see (``live_ingest``, lowered): a prefill's ``ENG_SELECT``
        #: counts its blocks by it
        self._ingest_live = frozenset(
            rung for rung in self.rungs
            if self.program.live_ingest(rung, lowered=True))
        #: what ``ENG_SELECT`` gives as the most positions a query
        #: attends: a selecting layer's ``topk``; where latent layers
        #: choose nothing, all the cache keeps; None: no latent layer
        self._select_topk = self.program.select_topk or (
            self.max_len if self.program.latent else None)
        # What a drafting engine counts (``stats()``, ``ENG_DRAFT``).
        self.drafts_proposed = 0
        self.drafts_accepted = 0
        self.draft_tokens_dropped = 0
        self._key = jax.random.PRNGKey(seed)
        self._ids = itertools.count()
        self.queue: deque = deque()
        # host-side slot table
        self.slot_req: list[int | None] = [None] * n_slots
        self.slot_tokens: list[list[int]] = [[] for _ in range(n_slots)]
        self.slot_remaining = np.zeros(n_slots, np.int32)
        self.slot_prompt_len = np.zeros(n_slots, np.int32)
        self.slot_waited = np.zeros(n_slots, np.int32)
        self.slot_ttft = np.zeros(n_slots, np.float64)
        self.slot_submit_t = np.zeros(n_slots, np.float64)
        self._submitted_step: dict[int, int] = {}
        self._submitted_t: dict[int, float] = {}
        # completed-request latency record (SLO surface): bounded
        self._ttfts: deque = deque(maxlen=1024)
        self._latencies: deque = deque(maxlen=1024)
        self.active = np.zeros(n_slots, bool)
        self.last_tok = np.zeros(n_slots, np.int32)
        # The pipeline: the decode not yet read (None: settled), and
        # how many dispatches found one (overlapped) or none (settled).
        self._inflight: _InFlight | None = None
        self._settling = False  # inside step_settled()
        self.ticks_overlapped = 0
        self.ticks_settled = 0
        self.admissions_overlapped = 0  # forwards enqueued behind a decode
        self.steps = 0
        self.tokens_emitted = 0
        self.requests_completed = 0
        # This tick's admissions (subclass hook; see _admit).
        self._admitted: list = []
        # Flight recorder (docs/TRACING.md "Engine and executed-step
        # records"): the tick, its admissions, key splits, prefills,
        # decode and retirements, one record a span. The engine's own
        # ring unless its driver hands it one (bind_trace); None = off.
        self.trace: TraceBuffer | None = TraceBuffer()
        register_ring("engine", self.trace)
        # Full collections, and every program JAX builds, land beside
        # the ticks they stall.
        host_ring()
        self._tick_seq = 0  # ``steps`` at this tick's entry
        # Slot seams for a backend's span wiring, called as
        # ``hook(rid, slot)`` when a request wins a decode slot and when
        # it retires (serve/backend.py turns them into SPAN_EXEC).
        self.admit_hook = None
        self.retire_hook = None
        # FFN auxiliary mean over forwards (``stats()``'s last entry)
        self._mlp_extra_sum = 0.0
        self._mlp_extra_n = 0
        # Exact-prompt prefix cache (system-prompt reuse): LRU of
        # {prompt bytes -> prompt-window KV + last-position logits}.
        # Entries are DEVICE arrays — storing the lazy slot slice
        # costs bounded HBM instead of a synchronous device-to-host
        # copy on every miss (which would inflate every unique
        # prompt's TTFT). A hit installs the KV into the slot and
        # samples the first token from the cached logits — zero
        # prefill compute. 0 = off. (Under a tp mesh: the program's
        # ``install_window``.)
        self.prefix_cache_size = prefix_cache_size
        self._prefix_cache: "OrderedDict[bytes, dict]" = OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefill_count = 0  # real prefill dispatches (cache misses)
        self.prefill_rows = 0  # rows they ran at (each one's rung)
        self.prefill_prompt_tokens = 0  # rows of those that were prompt

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def _prefill(params, cache, prev_tok, slot, prompt, plen, key):
            """Write one request's prompt into ``slot`` and sample its
            first token. prompt: (rung,) padded; plen: real length.
            ``prev_tok`` is the lanes' token vector as the decode keeps
            it on the device: it comes back with the first token at
            ``slot``, where the decode enqueued behind this forward
            takes it (``_LANE_CARRY``) before the host has read it.
            Also returns the first token as what goes to the host (a
            routing program's ``route`` rides behind it) and the
            last-position logits (for the prefix cache)."""
            last_logits, cache, extra, route = self.program.ingest(
                params, cache, slot, prompt, plen)
            if self._window > 1:
                # a drafting program took the (greedy) first token
                # itself, to draft the one behind it: both are in the
                # cache, where its decode reads them
                first, tok = cache["cur"][slot], prev_tok
            else:
                first = _sample(
                    last_logits[None, :], key, self.temperature)[0]
                tok = prev_tok.at[slot].set(first.astype(prev_tok.dtype))
            if route is not None:  # rides to the host with the token
                first = jnp.concatenate([first[None], route])
            return tok, first, last_logits, cache, extra

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _install(cache, slot, kwin, vwin, plen):
            """Prefix-cache hit: the cached prompt window into ``slot``
            (the program's ``install_window``); no forward at all."""
            return self.program.install_window(
                cache, slot, kwin, vwin, plen, mesh)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _decode(params, cache, prev_tok, lanes, key):
            """One token for every slot; inactive lanes masked.
            ``lanes`` is the host's word on each lane (``_LANE_OFF``,
            ``_LANE_CARRY`` or its last token), ``prev_tok`` the tokens
            the previous decode returned, still on the device. Returns
            the tokens twice: alone, to be the next call's ``prev_tok``,
            and as what goes to the host, where a routing program's
            ``route`` rides behind them."""
            active = lanes > _LANE_OFF
            last_tok = jnp.where(lanes > _LANE_CARRY, lanes, prev_tok)
            logits, new_cache, extra, route = self.program.decode(
                params, cache, last_tok, active)
            keys = jax.random.split(key, self.n_slots)
            nxt = jax.vmap(
                lambda lg, k: _sample(lg[None, :], k,
                                      self.temperature)[0]
            )(logits[:, 0, :], keys)
            nxt = jnp.where(active, nxt, 0)
            new_cache["pos"] = cache["pos"] + active.astype(jnp.int32)
            out = nxt if route is None else jnp.concatenate([nxt, route])
            return nxt, out, new_cache, extra

        def _decode_window(params, cache, prev_tok, lanes, key):
            """The decode of a plan that drafts for itself, under the
            same name and arguments: one token or two for every slot
            (``program.draft_tick``). A lane's last token and its draft
            are in ``cache`` (the prefill and the tick before left them
            there), so ``lanes`` says only which lanes run and
            ``prev_tok`` passes through; greedy, so ``key`` is not
            drawn from. What goes to the host is ``(slots, 2)`` tokens
            flat, -1 where a lane emitted none, the route behind."""
            del key
            toks, new_cache, route, *_ = self.program.draft_tick(
                params, cache, lanes > _LANE_OFF)
            out = toks.reshape(-1)
            if route is not None:
                out = jnp.concatenate([out, route])
            return prev_tok, out, new_cache, jnp.zeros((), jnp.float32)

        if self._window > 1:
            _decode = jax.jit(_decode_window, donate_argnums=(1,))
        self._prefill_fn = _prefill
        self._install_fn = _install
        self._decode_fn = _decode
        # Warm the programs NOW: compilation belongs to engine
        # construction, not to the first unlucky request's TTFT — a
        # multi-second jit landing in the SLO percentiles would read
        # as a false violation for the next ~1024 completions. Each
        # call donates the cache, so each rebinds it; a zero-length
        # prompt and no active lane leave every cursor at 0, and what
        # they write (slot 0's bucket, position 0 of each lane) the
        # first tenant's prefill or decode overwrites before reading.
        # A recurrent state has no cursor to hide behind: a prompt is
        # ingested from a zero state over whatever the slot held, and a
        # lane that is not active keeps its state bit for bit.
        wk = jax.random.PRNGKey(0)
        # The decode twice, the second on the first's own tokens: what
        # every tick after the first is handed (an output of a program,
        # not an array the host made) is then a signature this warm-up
        # has met, whatever sharding or commitment it carries. The
        # prefills below take that vector and hand it back in turn.
        off = jnp.full((n_slots,), _LANE_OFF, jnp.int32)

        def _warm_decode():
            tok, _, cache, _ = _decode(self.params, self.cache, off, off, wk)
            return _decode(self.params, cache, tok, off, wk)

        self._dev_tok, _, self.cache, _ = self._build(
            "eng.decode", n_slots, _warm_decode)
        # Every rung is its own instance of the prefill: all of them
        # now, so that none compiles under a request.
        for rung in self.rungs:
            self._dev_tok, _, _, self.cache, _ = self._build(
                f"eng.prefill@{rung}", rung, lambda: _prefill(
                    self.params, self.cache, self._dev_tok, 0,
                    jnp.zeros((rung,), jnp.int32), 0, wk))
        if prefix_cache_size:
            win = jnp.zeros((cfg.n_layers, 1, self.bucket,
                             cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
            self.cache = self._build(
                "eng.install", self.bucket,
                lambda: _install(self.cache, 0, win, win, 0))
        # The key split of every tick and admission is two small eager
        # programs: without this the first request built them.
        self._build("eng.keysplit", 2, lambda: tuple(jax.random.split(wk)))

    @staticmethod
    def _build(scope: str, size: int, call):
        """One program of the warm-up as an ``eng.build`` span of the
        host ring: the call and the wait for what it returns, so that
        one build's execution is not billed to the next one's. ``scope``
        names the build on each ``HOST_COMPILE`` inside it; ``size`` is
        its rows (a prefill's rung, an install's window) or lanes."""
        with host_phase("eng.build", size, scope):
            return jax.block_until_ready(call())

    _pct = staticmethod(nearest_rank)  # ``stats()``'s percentiles

    # -- flight recorder --------------------------------------------------

    def bind_trace(self, ring: TraceBuffer | None) -> None:
        """Write to ``ring`` from now on: the driver's own (so the
        engine's records interleave with its driver's in one ring), or
        ``None`` to record nothing."""
        self.trace = ring

    def _ev(self, ts_ns: int, event: int, *args: int) -> None:
        tr = self.trace
        if tr is not None:
            tr.emit(ts_ns, event, *args)

    def _route_ev(self, ts_ns: int, route: np.ndarray) -> None:
        """What an expert-routing program sent behind its tokens
        (``_plan_forward``'s ``route``) as the ``ENG_ROUTE`` record of
        that prefill or decode, stamped like the ``ENG_PREFILL`` /
        ``ENG_DECODE`` of the call that read it."""
        if len(route):
            self._ev(ts_ns, Ev.ENG_ROUTE, self._tick_seq,
                     *(int(c) for c in route))

    def _blocks_fetched(self, live: np.ndarray, kind: str) -> list:
        """The one count both records read: for each entry of the live
        table of ``kind``, ``(blocks a layer fetches this tick, blocks
        a layer has, layers)``. A busy lane streams the blocks up to
        the one its cursor (``live - 1``, the positions its query sees
        less one) is in; of keys and values an idle lane streams one
        besides (its cursor rests at 0 in the kernel's eyes)."""
        idle = (self.n_slots - len(live)) * (kind == "kv")
        return [(int((np.minimum(live - 1, kept - 1) // block + 1).sum())
                 + idle, kept // block * self.n_slots, layers)
                for (k, kept, block), layers in self._live.items()
                if k == kind]

    def _select_ev(self, ts_ns: int, live: np.ndarray,
                   rung: int = 0, lanes: np.ndarray | None = None) -> None:
        """``ENG_SELECT``: how many positions each of this call's
        queries sees (``live``, one entry a busy lane or a prompt
        token) and how many of them a layer that chooses attends, from
        what the host knows of its slots; stamped like the call's
        ``ENG_DECODE`` or ``ENG_PREFILL``. Last, the blocks one latent
        layer's one-pass attention streams: a decode's (``rung`` 0) the
        (lane, block) pairs of :meth:`_blocks_fetched`, a prefill's at
        ``rung`` rows the (query block, key block) pairs of
        ``mla.ingest_pairs``; 0 where the ``jax.numpy`` form runs.
        ``lanes``: what each busy lane's *last* query sees, where a
        lane has more than one (a drafting tick's window: ``live``
        then has an entry a query, and a lane's blocks are streamed
        once, up to that last one). Nothing for a program without a
        latent layer; one whose latent layers choose nothing gives
        chosen = seen and, for ``topk``, the cache's length."""
        topk = self._select_topk
        if topk is not None:
            if rung:
                blocks = ingest_pairs(rung, len(live)) \
                    if rung in self._ingest_live else 0
            else:
                rows = self._blocks_fetched(
                    live if lanes is None else lanes, "latent")
                blocks = rows[-1][0] if rows else 0
            self._ev(ts_ns, Ev.ENG_SELECT, self._tick_seq, len(live),
                     int(live.sum()), int(np.minimum(live, topk).sum()),
                     topk, blocks)

    def _attend_ev(self, ts_ns: int, live: np.ndarray) -> None:
        """``ENG_ATTEND``: the blocks of keys and values this decode's
        one-pass attention fetches against the blocks its caches have
        (:meth:`_blocks_fetched`, summed over the layers), from what
        the host knows of its slots (``live``: the positions each busy
        lane's query sees); stamped like the call's ``ENG_DECODE``.
        Nothing where the ``jax.numpy`` form runs."""
        rows = self._blocks_fetched(live, "kv") \
            if self.trace is not None else []
        if rows:
            self._ev(ts_ns, Ev.ENG_ATTEND, self._tick_seq, len(live),
                     int(live.sum()), sum(f * n for f, _, n in rows),
                     sum(h * n for _, h, n in rows),
                     sum(n for _, _, n in rows))

    def _split_key(self) -> jax.Array:
        """Advance the sampling key (two tiny device programs a call)."""
        t = _ns()
        with _span("pbst.eng.keysplit"):
            self._key, sub = jax.random.split(self._key)
        self._ev(t, Ev.ENG_KEYSPLIT, self._tick_seq, _ns() - t)
        return sub

    # -- request intake ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 0 < len(prompt) <= self.bucket:
            raise ValueError(
                f"prompt length {len(prompt)} not in (0, {self.bucket}]")
        if max_new_tokens < 1:
            # prefill always samples one token; a zero-budget request
            # would still emit it and break caller-side accounting
            raise ValueError("max_new_tokens must be >= 1")
        # a drafting tick writes the rows of its whole window, accepted
        # or not: that many rows of room stay behind the last token
        room = self._window if self._window > 1 else 0
        if len(prompt) + max_new_tokens + room > self.max_len:
            raise ValueError(
                "prompt + max_new_tokens exceeds max_len" + (
                    f" less the {room} rows a drafting tick's window "
                    f"writes" if room else ""))
        rid = next(self._ids)
        self.queue.append((rid, prompt, int(max_new_tokens)))
        self._submitted_step[rid] = self.steps
        self._submitted_t[rid] = self._now()
        if self.submit_hook is not None:
            self.submit_hook(rid, len(prompt), int(max_new_tokens))
        return rid

    # -- the engine tick --------------------------------------------------

    def _admit(self, unread: list,
               done: list[Completion]) -> list[np.ndarray]:
        """Seat waiting requests in free slots and enqueue their prompt
        forwards behind whatever is ``unread`` (the decode in flight).
        The last admission is left unread, appended to ``unread``: the
        caller enqueues its decode behind it and lands them together.
        An earlier one of the same call is landed before the next is
        dispatched, so that each forward's execution lies inside its
        own ``ENG_PREFILL`` record and no other's
        (``benchmarks/readers/bucket_prefill_ms.py`` joins them so);
        so is a prefix hit, whose first token no program leaves on the
        device. Returns the routes of the decodes it landed."""
        # (slot, padded_prompt, plen) of this tick's admissions, each
        # padded to its rung: the hook subclasses use to mirror work
        # per new tenant (the speculative engine draft-prefills the
        # same prompt, at the same rung by its shape).
        self._admitted = []
        routes: list[np.ndarray] = []
        for slot in np.flatnonzero(~self.active)[:len(self.queue)]:
            if unread and isinstance(unread[-1], _Admission):
                routes += self._land(unread, done)[1]
            behind = bool(unread)  # the decode in flight, if any
            with _span("pbst.eng.admit"):
                unread.append(self._admit_one(int(slot)))
            if unread[-1].rows:
                self.admissions_overlapped += behind
            else:
                routes += self._land(unread, done)[1]
        return routes

    def _rung(self, plen: int) -> int:
        """The padded length a prompt of ``plen`` tokens runs at."""
        return next(r for r in self.rungs if r >= plen)

    def _admit_one(self, slot: int) -> _Admission:
        """Seat the next request in ``slot`` and enqueue its prompt
        forward (a prefix hit: the cached window's install). Its first
        token is not waited for: ``_seat`` books it once it is read."""
        t_admit = _ns()
        rid, prompt, max_new = self.queue.popleft()
        t_slot = self._now()
        if self.admit_hook is not None:
            self.admit_hook(rid, slot)
        rows = self._rung(len(prompt))
        padded = np.zeros(rows, np.int32)
        padded[:len(prompt)] = prompt
        self._admitted.append((slot, padded, len(prompt)))
        sub = self._split_key()
        pkey = prompt.tobytes()
        ent = (self._prefix_cache.get(pkey)
               if self.prefix_cache_size else None)
        extra = None
        t_prefill = _ns()
        with _span("pbst.eng.prefill"):
            if ent is not None:
                # hit: the cached logits sample, no prompt forward
                self._prefix_cache.move_to_end(pkey)
                self.prefix_hits += 1
                self.cache = self._install_fn(
                    self.cache, slot, ent["k"], ent["v"],
                    int(ent["plen"]))
                first = _sample(
                    ent["logits"][None, :], sub, self.temperature)
                rows = 0
            else:
                self._dev_tok, first, last_logits, self.cache, extra = \
                    self._prefill_fn(
                        self.params, self.cache, self._dev_tok, slot,
                        jnp.asarray(padded), len(prompt), sub)
        t_dispatched = _ns()
        if ent is None:
            self.prefill_count += 1
            self.prefill_rows += rows
            self.prefill_prompt_tokens += len(prompt)
            if self.prefix_cache_size:
                self.prefix_misses += 1
                # Device arrays: lazy slices, no host sync here. A
                # window is ``bucket`` positions whatever the rung was
                # (one shape for _install); past the rung it holds an
                # earlier tenant's, which no cursor reaches.
                self._prefix_cache[pkey] = dict(
                    self.program.cut_window(self.cache, slot, self.bucket),
                    logits=last_logits, plen=len(prompt))
                while len(self._prefix_cache) > self.prefix_cache_size:
                    self._prefix_cache.popitem(last=False)
        # The slot is the request's from here; its tokens follow.
        self.slot_req[slot] = rid
        self.slot_tokens[slot] = []
        self.slot_prompt_len[slot] = len(prompt)
        self.slot_remaining[slot] = max_new - 1
        self.slot_waited[slot] = (
            self.steps - self._submitted_step.pop(rid, self.steps))
        t_submit = self._submitted_t.pop(rid, t_slot)
        self.slot_submit_t[slot] = t_submit
        self.active[slot] = True
        return _Admission(
            slot, rid, len(prompt), rows, first, extra,
            max(0, round((t_slot - t_submit) * 1e9)),
            t_admit, t_prefill, t_dispatched)

    def _seat(self, adm: _Admission, first: np.ndarray, t_host: int,
              done: list[Completion]) -> None:
        """Book an admission whose first token is on the host since
        ``t_host``: its records (the ``ENG_PREFILL``'s wait and the
        ``ENG_ADMIT``'s span both end at that stamp), its token, and
        its retirement where that token was all it had to say (a
        budget of one, an EOS)."""
        slot, tick = adm.slot, self._tick_seq
        if adm.rows:
            self._route_ev(adm.t_prefill, first[1:])
            self._select_ev(adm.t_prefill, np.arange(1, adm.plen + 1),
                            adm.rows)
        self._ev(adm.t_prefill, Ev.ENG_PREFILL, tick, adm.rid, slot,
                 adm.t_dispatched - adm.t_prefill,
                 t_host - adm.t_dispatched, adm.rows)
        tok = int(first[0])
        self.slot_tokens[slot] = [tok]
        self.last_tok[slot] = tok
        self.tokens_emitted += 1
        # first token on the host
        self.slot_ttft[slot] = self._now() - self.slot_submit_t[slot]
        self._ev(adm.t_admit, Ev.ENG_ADMIT, tick, adm.rid, slot, adm.plen,
                 adm.wait_ns, t_host - adm.t_admit)
        if self.slot_remaining[slot] <= 0 or tok == self.eos_id:
            done.append(self._retire(slot))

    def _retire(self, slot: int) -> Completion:
        lat = self._now() - float(self.slot_submit_t[slot])
        ttft = float(self.slot_ttft[slot])
        comp = Completion(
            request_id=self.slot_req[slot],
            tokens=list(self.slot_tokens[slot]),
            prompt_len=int(self.slot_prompt_len[slot]),
            steps_waited=int(self.slot_waited[slot]),
            ttft_s=ttft,
            latency_s=lat,
        )
        self._ttfts.append(ttft)
        self._latencies.append(lat)
        self.requests_completed += 1
        # The exact values engine.stats() keeps a rounded window of.
        self._ev(_ns(), Ev.ENG_RETIRE, self._tick_seq, comp.request_id,
                 slot, len(comp.tokens), round(ttft * 1e9),
                 round(lat * 1e9))
        if self.retire_hook is not None:
            self.retire_hook(comp.request_id, slot)
        self.slot_req[slot] = None
        self.slot_tokens[slot] = []
        self.active[slot] = False
        return comp

    def _emit(self, slot: int, tok: int) -> bool:
        """Book one decoded token into ``slot``; True if the slot just
        finished (budget or EOS) — the ONE copy of the retire
        condition both engines' emit loops use."""
        self.slot_tokens[slot].append(tok)
        self.last_tok[slot] = tok
        self.slot_remaining[slot] -= 1
        self.tokens_emitted += 1
        return bool(
            self.slot_remaining[slot] <= 0
            or (self.eos_id is not None and tok == self.eos_id))

    def step(self) -> list[Completion]:
        """Admit waiting requests, enqueue one decode token for every
        active slot, book the decode the call before this one enqueued,
        retire finished requests. Returns completions.

        The tick every engine shares: ``_step`` is the engine's own
        (the pipelined decode here, synchronous speculation in the
        subclass); the ``ENG_TICK`` record and its annotation wrap
        whichever runs."""
        t0 = _ns()
        self._tick_seq = self.steps
        with _span("pbst.eng.tick"):
            done = self._step()
        if self.trace is not None:
            self.trace.emit(
                t0, Ev.ENG_TICK, _ns() - t0, self._tick_seq,
                int(self.active.sum()) + len(done), len(self._admitted),
                len(done), len(self.queue))
        return done

    def step_settled(self) -> list[Completion]:
        """``step()``, and the decode it enqueued booked before it
        returns: nothing is left on the device, every token the tick
        computed is in ``slot_tokens`` and every request it finished
        is returned. For a driver that is billed for what its call
        leaves running (a scheduler's quantum) or reads the slot table
        between ticks. It goes through ``step`` so that whatever wraps
        that (a backend's spans, a benchmark's stamps) sees this tick
        too."""
        self._settling = True
        try:
            return self.step()
        finally:
            self._settling = False

    def settle(self) -> list[Completion]:
        """Book the decode in flight, if there is one, and return the
        requests it finished: after it the slot table and the counters
        say all the device has done."""
        done: list[Completion] = []
        fl, self._inflight = self._inflight, None
        if fl is not None:
            t = _ns()
            for route in self._land([fl], done)[1]:
                self._route_ev(t, route)
        return done

    def _decoded(self, t_pre: int, t_enqueued: int, t_host: int,
                 overlapped: int = 0) -> None:
        """Close the tick's decode span (both engines): ``pre`` runs
        from ``t_pre`` (the admissions' dispatches and the key split
        are over) until the program is enqueued, host-to-device copies
        included; ``sync`` until everything this call books is on the
        host; ``post`` until now, the emit and retire loops.
        ``overlapped``: the program was enqueued behind work the host
        had not waited for (the decode before it, a prompt forward)."""
        self._ev(t_pre, Ev.ENG_DECODE, self._tick_seq,
                 t_enqueued - t_pre, t_host - t_enqueued, _ns() - t_host,
                 overlapped)

    def _read(self, fl: _InFlight) -> tuple[np.ndarray, np.ndarray]:
        """Wait for a decode's tokens; ``(tokens, route)`` on the host
        (``route`` empty unless the program routes tokens to experts)."""
        self._mlp_extra_sum += float(fl.extra) / self.cfg.n_layers
        self._mlp_extra_n += 1
        out = np.asarray(fl.out)
        n = self.n_slots * self._window
        toks = out[:n]
        if self._window > 1:  # (slots, window), -1 where none emitted
            toks = toks.reshape(self.n_slots, self._window)
        return toks, out[n:]

    def _book(self, fl: _InFlight, toks: np.ndarray,
              done: list[Completion]) -> None:
        """Emit a decode's tokens to the lanes it ran that still hold
        the request they held then, and retire those that finished."""
        if self._window > 1:
            return self._book_window(fl, toks, done)
        for slot, rid in fl.lanes:
            if self.slot_req[slot] == rid and \
                    self._emit(slot, int(toks[slot])):
                done.append(self._retire(slot))

    def _book_window(self, fl: _InFlight, toks: np.ndarray,
                     done: list[Completion]) -> None:
        """:meth:`_book` for a drafting decode: a lane emitted one
        token or two (``toks`` (slots, 2), -1 for none). They are
        booked in order, and one behind the token that finished its
        request (the budget's last, or an EOS) is dropped, never
        served. ``ENG_DRAFT`` says what this decode proposed, what was
        accepted and what was booked and dropped."""
        proposed = accepted = booked = dropped = 0
        for slot, rid in fl.lanes:
            if self.slot_req[slot] != rid:
                continue
            row = [int(t) for t in toks[slot] if t >= 0]
            proposed += 1
            accepted += len(row) - 1
            for i, tok in enumerate(row):
                booked += 1
                if self._emit(slot, tok):
                    dropped += len(row) - 1 - i
                    done.append(self._retire(slot))
                    break
        self.drafts_proposed += proposed
        self.drafts_accepted += accepted
        self.draft_tokens_dropped += dropped
        self._ev(_ns(), Ev.ENG_DRAFT, self._tick_seq, len(fl.lanes),
                 proposed, accepted, booked, dropped)

    def _read_first(self, adm: _Admission) -> np.ndarray:
        """Wait for an admission's first token; the token on the host,
        the program's route behind it."""
        if adm.extra is not None:
            self._mlp_extra_sum += float(adm.extra) / self.cfg.n_layers
            self._mlp_extra_n += 1
        return np.asarray(adm.first).ravel()

    def _land(self, unread: list,
              done: list[Completion]) -> tuple[int, list[np.ndarray]]:
        """Wait for everything in ``unread`` (decodes and admissions,
        in the order the device runs them), take ONE stamp when all of
        it is on the host, and book it in that order: a decode's tokens
        to its lanes, an admission's first token to its slot. Every
        wait this call's records carry ends at that stamp. Empties
        ``unread``; returns the stamp and the decodes' routes."""
        with _span("pbst.eng.sync"):
            got = [self._read(u) if isinstance(u, _InFlight)
                   else self._read_first(u) for u in unread]
        t_host = _ns()
        routes = []
        for u, read in zip(unread, got):
            if isinstance(u, _InFlight):
                toks, route = read
                self._book(u, toks, done)
                routes.append(route)
            else:
                self._seat(u, read, t_host, done)
        unread.clear()
        return t_host, routes

    def _step(self) -> list[Completion]:
        done: list[Completion] = []
        # What is enqueued and not read, in the device's order: the
        # decode in flight, then this call's admission, then (a settled
        # tick) this call's own decode. Nothing of it is waited for
        # until this call's programs are on the queue behind it, so the
        # device runs while the host pads, splits keys and dispatches.
        unread: list = []
        if self._inflight is not None:
            unread.append(self._inflight)
            self._inflight = None
        routes = self._admit(unread, done)  # of the decodes this call reads
        # The lanes of this dispatch, decided ahead: a lane whose
        # budget the decode in flight exhausts runs no further token.
        # (An EOS the host has not seen yet cannot stop its lane: that
        # lane runs one token more, which ``_book`` drops; so does a
        # lane whose unread first token is one.) A drafting decode in
        # flight may have spent up to two of a lane's budget: a lane it
        # may have finished sits this dispatch out, and runs in the
        # next if the host then finds that it has not.
        flying = np.zeros(self.n_slots, bool)  # a token in flight
        carry = np.zeros(self.n_slots, bool)   # last token not on the host
        for u in unread:
            if isinstance(u, _InFlight):
                flying[[slot for slot, _ in u.lanes]] = True
            else:
                carry[u.slot] = True
        carry |= flying
        mask = self.active & (self.slot_remaining > flying * self._window)
        overlapped = int(bool(unread))
        seen = None
        if self._select_topk is not None or (
                self._live and self.trace is not None):
            # a lane's new position sees its prompt, the tokens the host
            # has booked and the one it has not read yet (a drafting
            # decode in flight counts as one: whether it accepted is
            # not known)
            booked = np.fromiter(map(len, self.slot_tokens), np.int64,
                                 self.n_slots)
            seen = (self.slot_prompt_len + booked + carry)[mask]
        t_pre = t_enqueued = _ns()
        if mask.any():
            sub = self._split_key()
            t_pre = _ns()
            with _span("pbst.eng.decode"):
                lanes = np.where(mask, np.where(
                    carry, _LANE_CARRY, self.last_tok), _LANE_OFF)
                self._dev_tok, out, self.cache, extra = self._decode_fn(
                    self.params, self.cache, self._dev_tok,
                    jnp.asarray(lanes, jnp.int32), sub)
            t_enqueued = _ns()
            self._inflight = _InFlight(out, extra, [
                (int(slot), self.slot_req[slot])
                for slot in np.flatnonzero(mask)])
            self.ticks_overlapped += overlapped
            self.ticks_settled += 1 - overlapped
            if self._settling:  # this tick reads its own decode
                unread.append(self._inflight)
                self._inflight = None
        t_host, landed = self._land(unread, done)
        self.steps += 1
        for route in routes + landed:  # stamped like this call's ENG_DECODE
            self._route_ev(t_pre, route)
        if mask.any():
            if seen is not None and self._window > 1:
                # a query a position of the window, each seeing one
                # row more than the one before it
                self._select_ev(t_pre, np.concatenate(
                    [seen + i for i in range(self._window)]),
                    lanes=seen + self._window - 1)
            elif seen is not None:
                self._select_ev(t_pre, seen)
                self._attend_ev(t_pre, seen)
            self._decoded(t_pre, t_enqueued, t_host, overlapped)
        return done

    def has_work(self) -> bool:
        return (bool(self.queue) or bool(self.active.any())
                or self._inflight is not None)

    def stats(self) -> dict:
        """Engine + SLO surface: time-to-first-token and completion
        latency percentiles over the last 1024 completions — the
        numbers a serving tenant's latency SLO is written against
        (and what the feedback policy's BOOST class protects)."""
        return {
            "steps": self.steps,
            # Decodes enqueued behind work the host had not waited for
            # (the decode before, a prompt forward), and onto a drained
            # device (ENG_DECODE's flag, counted); prompt forwards
            # enqueued behind a decode in flight.
            "ticks_overlapped": self.ticks_overlapped,
            "ticks_settled": self.ticks_settled,
            "admissions_overlapped": self.admissions_overlapped,
            "active_slots": int(self.active.sum()),
            "queued": len(self.queue),
            "tokens_emitted": self.tokens_emitted,
            "completed": self.requests_completed,
            "window": len(self._latencies),
            "ttft_p50_s": round(self._pct(self._ttfts, 0.50), 6),
            "ttft_p99_s": round(self._pct(self._ttfts, 0.99), 6),
            "latency_p50_s": round(self._pct(self._latencies, 0.50), 6),
            "latency_p99_s": round(self._pct(self._latencies, 0.99), 6),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            # Real prefills, the rows they ran at and how many of those
            # were prompt: 1 - tokens / rows is the padding paid for.
            "prefill_count": self.prefill_count,
            "prefill_rows": self.prefill_rows,
            "prefill_prompt_tokens": self.prefill_prompt_tokens,
            # FFN auxiliary mean (MoE: drop fraction; 0 for dense) —
            # nonzero under capacity starvation means engine routing
            # has diverged from the dropless/lockstep contract.
            "mlp_extra_mean": round(
                self._mlp_extra_sum / self._mlp_extra_n, 6)
            if self._mlp_extra_n else 0.0,
            # A drafting engine (``ENG_DRAFT``, summed): drafts the
            # booked decodes proposed, those the stack's own argmax
            # confirmed, and tokens computed past a budget or behind an
            # EOS and not served. All zero without a drafting block.
            "drafts_proposed": self.drafts_proposed,
            "drafts_accepted": self.drafts_accepted,
            "draft_tokens_dropped": self.draft_tokens_dropped,
        }


def make_continuous_serve_step(engine: ContinuousBatcher,
                               next_requests=None):
    """Job-shaped wrapper: one engine tick per step (one token across
    slots — a quantum-sized unit, so the credit scheduler interleaves
    serving with training at token granularity). ``next_requests(step)``
    optionally feeds new (prompt, max_new) pairs each tick. The
    ``tokens`` metric is the tick's DELTA of the engine's emitted
    counter, so the TOKENS ledger slot is exact goodput.

    The tick is ``step_settled()``: a quantum that left a decode on
    the device would have it billed to the next tenant's wait and would
    change what the credit scheduler burns, so the step returns with
    its own tokens booked and nothing in flight."""

    def serve_step(state):
        step = int(state["step"])
        if next_requests is not None:
            for prompt, max_new in next_requests(step):
                engine.submit(prompt, max_new)
        before = engine.tokens_emitted
        done = engine.step_settled()
        state = {"step": step + 1,
                 "completed": state["completed"] + len(done)}
        return state, {"tokens": engine.tokens_emitted - before}

    return serve_step
