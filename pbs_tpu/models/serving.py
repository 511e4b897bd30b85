"""Continuous batching: slot-based serving engine, TPU-first.

The reference has no serving story at all (SURVEY.md §0); PBS-T's
batch-inference tenant (``make_serve_step``) generates request batches
in lockstep — a late request waits for the whole previous batch. This
module adds the serving engine modern LLM systems use: **continuous
batching** — a fixed pool of decode slots advancing one token per step
for ALL active requests, with new requests admitted into free slots at
step boundaries and finished ones retired immediately.

TPU-first expression of the idea:

- **Static everything**: ``n_slots`` decode lanes, one shared KV slab
  ``(L, n_slots, T, nkv, hd)``, prompts padded to one of two
  static lengths (``prefill_rungs``). Admission/retirement
  changes DATA (per-slot cursors and masks), never shapes — so two
  XLA programs exist (slot-prefill, once a rung, and slot-decode)
  regardless of traffic, all compiled at construction.
- **Per-slot cursors**: unlike ``forward_with_cache`` (one scalar
  position for the whole batch), every slot carries its own ``pos``;
  rope tables are gathered per row, cache writes scatter per row, and
  the causal mask compares against each row's own position.
- **Inactive lanes ride along**: an empty slot still computes (masked
  to self-attention on garbage it never emits). Wasted FLOPs on idle
  lanes buy shape stability — the standard TPU trade.
- **Host admission between dispatches**: the engine's ``step()`` is
  a scheduler-quantum-sized unit (one token across slots), so a
  serving Job under the credit scheduler interleaves with training at
  token granularity — the latency story the reference's BOOST class
  exists for.
- **One tick in flight**: ``step()`` enqueues the decode of tick n+1
  before it reads tick n's tokens, and books tick n (emit, retire,
  records) while the device runs tick n+1. The token vector goes from
  one decode to the next on the device; the host decides the lane mask
  ahead from the budgets it already knows. ``step_settled()`` is the
  tick that reads what it dispatched before it returns, for a driver
  whose quantum has to be its own (``make_continuous_serve_step``).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import time
from collections import OrderedDict, deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from pbs_tpu.models.kda import kda_decode, kda_ingest
from pbs_tpu.models.mamba import mamba_decode, mamba_ingest
from pbs_tpu.models.mamba2 import mamba2_decode, mamba2_ingest
from pbs_tpu.models.mla import mla_decode, mla_ingest, streamed_block
from pbs_tpu.models.quant import embed_rows, wload
from pbs_tpu.models.generate import _sample
from pbs_tpu.obs.trace import (
    Ev, TraceBuffer, host_phase, host_ring, register_ring,
)
from pbs_tpu.models.plan import (
    KdaKind, Mamba2Kind, MambaKind, MlaKind, block_name, init_plan_params,
    plan_of, rope_table, uniform_plan)
from pbs_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    rms_norm,
    rope_tables,
)
from pbs_tpu.ops.kv_attend import attend_block, kv_attend, kv_attend_tiles
from pbs_tpu.parallel.sharding import slot_cache_kv_sharding


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


def _rope_rows(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Per-row rope: x (B, S, H, hd); cos/sin (B, S, half)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def init_slot_cache(cfg: TransformerConfig, n_slots: int,
                    max_len: int) -> dict:
    shape = (cfg.n_layers, n_slots, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((n_slots,), jnp.int32),  # per-slot cursors
    }


def _write_rows(rows, new, at, layer=None):
    """Slot b's S new entries (``new``: (B, S, nkv, hd)) go to
    ``rows[b, at[b]:]``, or with ``layer`` to ``rows[layer, b,
    at[b]:]`` of a cache stacked by layer: one dynamic_update_slice a
    slot into the WHOLE cache, so a layer moves its new positions and
    nothing else. A DUS, not a scatter: GSPMD partitions it on an
    unsharded axis natively, where the equivalent scatter made tp>2
    compiles blow up. Not vmapped over the slot axis either (a batched
    DUS is a scatter, and XLA then re-lays the carried cache
    slot-major: whole-cache copies in and out of every call), nor
    unrolled (the same re-layout)."""
    B = new.shape[0]
    if layer is None:
        # ``new`` has the rank of ``rows`` here, and sliced at that rank
        # an XLA:TPU pass takes it for ``rows`` (RET_CHECK, jax 0.9.0):
        # slice it flat. (Sliced flat under ``layer`` too, the dense
        # decode compiles to other fusions than it always has.)
        flat = new.reshape(B, -1)

    def one(b, rows):
        if layer is None:
            return jax.lax.dynamic_update_slice(
                rows, jax.lax.dynamic_slice_in_dim(flat, b, 1).reshape(
                    (1,) + new.shape[1:]), (b, at[b], 0, 0))
        return jax.lax.dynamic_update_slice(
            rows, jax.lax.dynamic_slice_in_dim(new, b, 1)[None],
            (layer, b, at[b], 0, 0))

    return jax.lax.fori_loop(0, B, one, rows)


def _grouped_attention(q, k, v, mask, dt):
    """q (B, S, H, hd) against k, v (B, K, nkv, hd); query head g reads
    kv head g // (H / nkv); ``mask`` (B or 1, S, K) says what a query
    sees. Softmax in float32. Returns (B, S, H, hd)."""
    B, S, H, hd = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, S, nkv, H // nkv, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)  # (B, nkv, K, hd)
    vt = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bngqh,bnkh->bngqk", qg, kt) / np.sqrt(hd)
    mask = jnp.broadcast_to(mask[:, None, None, :, :], scores.shape)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
    attn = jnp.einsum("bngqk,bnkh->bngqh", probs, vt)
    return attn.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


# One trace and one lowered function a cache shape, whatever the layers
# (``models/mamba.py::_kernel_scan`` says why).
_kernel_attend = jax.jit(kv_attend)


def _cursor_attention(q, k, v, at, layer, dt):
    """A decode tick's attention, one query position a lane: q (B, 1,
    H, hd) over the positions ``<= at[b]`` of the layer's k and v (B,
    K, nkv, hd), all of them where the cursor is past the last (a ring
    that has lapped); with ``layer`` (an int32 scalar) k and v are the
    caches of every layer, (L, B, K, nkv, hd). By the platform the
    program is lowered for: on a TPU the one-pass kernel over each
    lane's live blocks (``ops/kv_attend.py``; its tiling has to take
    the shapes, :func:`_streams_live`), anywhere else
    :func:`_grouped_attention` over the whole cache under the mask.
    Returns (B, 1, H, hd)."""
    index = () if layer is None else (layer,)

    def numpy_way(q, k, v, at, *index):
        if index:
            k, v = (jax.lax.dynamic_index_in_dim(t, index[0], 0,
                                                 keepdims=False)
                    for t in (k, v))
        seen = jnp.arange(k.shape[1])[None, :] <= at[:, None]
        return _grouped_attention(q, k, v, seen[:, None, :], dt)

    return jax.lax.platform_dependent(
        q, k, v, at, *index,
        tpu=lambda q, k, v, at, *index: _kernel_attend(
            q[:, 0], k, v, at, *index)[:, None],
        default=numpy_way)


def _streams_live(S: int, k: jax.Array, window=None) -> bool:
    """Whether a forward of S positions a lane over the cache ``k``
    (..., K, nkv, hd) of a full layer, or of a ring of ``window``, can
    run its attention as the one-pass kernel: a decode tick, shapes the
    kernel's tiling takes (more than one KV head among them), and no
    ring (all of a lapped ring is live, and its ``jax.numpy`` form is
    the faster: PERF.md 6, PR 45). (Which lowering then runs is the
    platform's, :func:`_cursor_attention`; a cache laid over a mesh
    never gets here, the programs see to that.)"""
    K, nkv, hd = k.shape[-3:]
    return S == 1 and not window and kv_attend_tiles(nkv, hd, K)


def _placed_on(mesh) -> tuple:
    """The devices a program's cache lies on: the mesh's, or without
    one the default device."""
    return tuple(mesh.devices.flat) if mesh is not None \
        else tuple(jax.devices()[:1])


def _live_blocks(k, devices: tuple, layers: int = 1,
                 window=None) -> list[tuple[int, int]]:
    """(positions kept, positions a block) of each of the ``layers``
    whose decode attention over the cache ``k`` (an array, traced or
    not) on ``devices`` runs as the one-pass kernel: traced so
    (:func:`_streams_live`, one device) and lowered so (a TPU); none
    where the ``jax.numpy`` form runs. Both records of the form go by
    it, a traced decode's ``HOST_PHASE`` (:func:`_say_attention`) and
    the engine's ``ENG_ATTEND``, which counts a tick's blocks by it."""
    if not (len(devices) == 1 and devices[0].platform == "tpu"
            and _streams_live(1, k, window)):
        return []
    K, nkv = k.shape[-3:-1]
    return [(K, attend_block(K, nkv))] * layers


def _say_attention(kernel: int, layers: int) -> None:
    """A traced decode program's ``HOST_PHASE`` records of no length,
    ``attn.live-kernel`` and ``attn.jnp``, size the softmax layers over
    keys and values that run in that form (``kernel`` of ``layers``
    through ``ops/kv_attend.py``), as ``experts.<form>`` says an expert
    layer's."""
    for form, n in (("attn.live-kernel", kernel),
                    ("attn.jnp", layers - kernel)):
        if n:
            with host_phase(form, n):
                pass


def _slot_forward(cfg: TransformerConfig, params: dict, tokens: jax.Array,
                  cache: dict, row_pos: jax.Array, mlp_fn=None,
                  active=None) -> tuple[jax.Array, dict]:
    """Forward (B, S) tokens where row b sits at absolute position
    ``row_pos[b]`` (S static; per-row cursors). Writes K/V at
    ``row_pos[b] + s``; row b's query s attends cols <= row_pos[b]+s.
    Returns (logits (B, S, vocab) fp32, updated cache slabs, extra).
    Only the B x S new positions of each layer are written: under a
    jit that donates ``cache`` the update is in place.

    ``mlp_fn(lp, h) -> (y, extra)`` swaps the FFN block — the SAME
    contract as ``generate._forward_with_cache_impl``, so the MoE
    closure serves both paths. ``extra`` is the FFN's auxiliary scalar
    (MoE: drop fraction) SUMMED over layers — callers divide by
    ``cfg.n_layers``, exactly as generate's impl callers do. Caveat the
    MoE caller owns: routing shares expert capacity across every
    co-resident lane of the forward (slots, bucket padding, garbage
    lanes), so engine decode only matches the lockstep path under
    DROPLESS capacity — watch the returned drop telemetry.

    ``active`` (B,) bool, the decode tick's alone (S == 1, the cache on
    one device): the lanes that hold a request. Given, a lane's query
    attends through :func:`_cursor_attention`, which on a TPU streams
    the lane's live blocks out of the stacked cache and slices no
    layer; a lane that holds none attends its first position alone (its
    cursor rests where its last request ended, and nothing up to there
    is its to read)."""
    B, S = tokens.shape
    T = cache["k"].shape[2]
    dt = cfg.dtype
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    x = embed_rows(params["embed"], tokens, dt)
    cos_full, sin_full = rope_tables(cfg, T)
    # absolute position of every (row, s) element: (B, S)
    abs_pos = row_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    abs_pos = jnp.minimum(abs_pos, T - 1)  # clamp: masked rows only
    cos = cos_full[abs_pos]  # (B, S, half)
    sin = sin_full[abs_pos]
    live = active is not None and _streams_live(S, cache["k"])

    def body(carry, layer):
        # The K/V slabs (L, B, T, nkv, hd) ride in the CARRY, not as
        # xs/ys: a scan's ys is a fresh array written slab by slab,
        # whatever the body changed; a carry is updated in place.
        x, extra, ks, vs = carry
        lp, i = layer
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        # The three products come out flat, behind a barrier, and the
        # split into heads reads that small result. Without the barrier
        # XLA:TPU moves the reshape through the product onto the weight
        # ((d, H * hd) -> (H, hd, d)), which no tiled layout of the
        # stacked leaf gives without moving it: it then slices wq, wk
        # and wv out of the stack and copies them transposed in every
        # layer of every call, and dequantises an int8 leaf whole
        # besides (tests/test_tpu_compile.py). wo and the MLP's three
        # have no such reshape behind their products.
        q, k, v = jax.lax.optimization_barrier(
            (h @ wload(lp["wq"], dt), h @ wload(lp["wk"], dt),
             h @ wload(lp["wv"], dt)))
        q = q.reshape(B, S, nh, hd)
        k = k.reshape(B, S, nkv, hd)
        v = v.reshape(B, S, nkv, hd)
        q = _rope_rows(q, cos, sin)
        k = _rope_rows(k, cos, sin)
        ks = _write_rows(ks, k, row_pos, layer=i)
        vs = _write_rows(vs, v, row_pos, layer=i)
        if live:
            with jax.named_scope("attn.full"):
                attn = _cursor_attention(
                    q, ks, vs, jnp.where(active, abs_pos[:, 0], 0), i, dt)
        else:
            ck = jax.lax.dynamic_index_in_dim(ks, i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(vs, i, 0, keepdims=False)
            # per-row causal horizon: row b's query s sees cols <= abs_pos
            reach = (jnp.arange(T)[None, None, :]
                     <= abs_pos[:, :, None])  # (B, S, T)
            attn = _grouped_attention(q, ck, cv, reach, dt)
        x = x + attn.reshape(B, S, nh * hd) @ wload(lp["wo"], dt)
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if mlp_fn is None:
            gate = jax.nn.silu(h @ wload(lp["w1"], dt))
            up = h @ wload(lp["w3"], dt)
            y = (gate * up) @ wload(lp["w2"], dt)
            e = jnp.zeros((), jnp.float32)
        else:
            y, e = mlp_fn(lp, h)
        x = x + y
        return (x, extra + e, ks, vs), None

    zero = jnp.zeros((), jnp.float32)
    (x, extra, new_k, new_v), _ = jax.lax.scan(
        body, (x, zero, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ wload(params["head"], dt)).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v, "pos": cache["pos"]}, extra


def ingest_slot_prompt(cfg: TransformerConfig, params: dict, cache: dict,
                       slot, prompt: jax.Array, plen, mlp_fn=None):
    """The ONE copy of slot-prompt ingestion (trace-safe): gather the
    slot's slabs as a B=1 view, forward the padded prompt from
    position 0, write the slabs back (a DUS on the unsharded slot axis
    — load-bearing for tp compiles, see _slot_forward — and in place
    where the caller's jit donates ``cache``), set the slot cursor.
    Returns ``(last_logits (V,), cache, extra)``; samplers layer on
    top."""
    sub = {
        "k": jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1),
        "v": jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1),
        "pos": jnp.zeros((1,), jnp.int32),
    }
    logits, sub, extra = _slot_forward(
        cfg, params, prompt[None, :], sub, jnp.zeros((1,), jnp.int32),
        mlp_fn=mlp_fn)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], sub["k"], slot, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], sub["v"], slot, axis=1)
    cache["pos"] = cache["pos"].at[slot].set(plen)
    return logits[0, plen - 1], cache, extra


# -- a planned stack: layers that differ ------------------------------------


def _rope_leading(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Per-row rope on the leading ``2 * cos.shape[-1]`` dims of each
    head; the rest pass through (partial rotary)."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return _rope_rows(x, cos, sin)
    return jnp.concatenate(
        [_rope_rows(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def init_plan_cache(cfg: TransformerConfig, n_slots: int,
                    max_len: int) -> dict:
    """Every kind of per-slot state in one cache, a layer at a time (a
    layer's keys are a buffer of their own: read out of a stack they
    would be copied first): a full layer keeps every position,
    ``(slots, max_len, nkv, hd)``; a window layer keeps a ring of its
    window, ``(slots, W, nkv, hd)``, position p at ``p mod W``; a
    delta-rule layer keeps no positions at all but ``state``, one
    float32 ``(hd, hd)`` matrix a head, ``(slots, H, hd, hd)``, and
    ``conv``, the last ``kernel - 1`` inputs of its short convolution
    (q, k and v side by side), ``(slots, kernel - 1, 3 * H * hd)``; a
    state-space layer keeps ``ssm``, one float32 ``(slots, d_state,
    d_inner)`` matrix (the channels last, where they fill the lanes),
    and its own ``conv``, ``(slots, kernel - 1, d_inner)``; one whose
    state is a matrix a head keeps ``ssm`` too, float32 ``(slots,
    n_heads, head_dim, d_state)`` (the states last, where they fill the
    lanes), and ``conv`` over x, B and C side by side, ``(slots, kernel
    - 1, d_inner + 2 groups d_state)``; a latent
    layer keeps every position too, but nothing a head: its RMS-normed
    latent row ``ckv``, ``(slots, max_len, kv_rank)``, the one rotary
    key every head shares ``kr``, ``(slots, max_len, rope_dim)``, and
    its indexer's key ``ik``, ``(slots, max_len, index_dim)``. One
    cursor a slot serves all: which ring entries and which latent rows
    are live follows from it alone, and a state needs none. ``state``,
    ``ssm``, ``conv``, ``ckv``, ``kr`` and ``ik`` are there only where
    some layer has them; a block without a mixer keeps nothing."""
    plan = plan_of(cfg)
    out: dict = {"k": {}, "v": {},
                 "pos": jnp.zeros((n_slots,), jnp.int32)}
    for layer in range(len(plan.layers)):
        a, _ = plan.kinds(layer)
        name = block_name(layer)
        if a is None:
            continue
        if isinstance(a, KdaKind):
            out.setdefault("state", {})[name] = jnp.zeros(
                (n_slots, a.n_heads, a.head_dim, a.head_dim), jnp.float32)
            out.setdefault("conv", {})[name] = jnp.zeros(
                (n_slots, a.conv - 1, 3 * a.n_heads * a.head_dim), cfg.dtype)
            continue
        if isinstance(a, MambaKind):
            out.setdefault("ssm", {})[name] = jnp.zeros(
                (n_slots, a.d_state, a.d_inner), jnp.float32)
            out.setdefault("conv", {})[name] = jnp.zeros(
                (n_slots, a.conv - 1, a.d_inner), cfg.dtype)
            continue
        if isinstance(a, Mamba2Kind):
            out.setdefault("ssm", {})[name] = jnp.zeros(
                (n_slots, a.n_heads, a.head_dim, a.d_state), jnp.float32)
            out.setdefault("conv", {})[name] = jnp.zeros(
                (n_slots, a.conv - 1, a.d_conv), cfg.dtype)
            continue
        if isinstance(a, MlaKind):
            for key, width in (("ckv", a.kv_rank), ("kr", a.rope_dim),
                               ("ik", a.index_dim)):
                out.setdefault(key, {})[name] = jnp.zeros(
                    (n_slots, max_len, width), cfg.dtype)
            continue
        for kv in ("k", "v"):
            out[kv][name] = jnp.zeros(
                (n_slots, min(a.window, max_len) if a.window else max_len,
                 cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    return out


#: A layer kind that keeps a recurrent state, not positions: the scope
#: its ops carry, the cache entry that holds the state (its tail is
#: ``conv``), its decode step and its prompt ingestion.
_RECURRENT = {
    KdaKind: ("attn.kda", "state", kda_decode, kda_ingest),
    MambaKind: ("attn.mamba", "ssm", mamba_decode, mamba_ingest),
    Mamba2Kind: ("attn.mamba2", "ssm", mamba2_decode, mamba2_ingest)}
#: A layer kind that keeps rows of its own a position, not keys and
#: values a head: the scope its ops carry, the cache entries that hold
#: the rows, its decode step and its prompt ingestion.
_LATENT = {
    MlaKind: ("attn.mla", ("ckv", "kr", "ik"), mla_decode, mla_ingest)}


def _plan_forward(cfg: TransformerConfig, params: dict, tokens: jax.Array,
                  cache: dict, row_pos: jax.Array, valid: jax.Array,
                  slot=None):
    """The planned stack over (B, S) tokens, layer by layer (a layer's
    kinds are static, so each layer is its own code over its own
    parameters and its own cache). A block that is a mixer alone or an
    MLP alone runs its one norm and its one half, and adds once.

    ``slot`` None is the decode tick: S == 1, row b at position
    ``row_pos[b]``; each layer writes its one new position (full: at
    the cursor; window: at cursor mod W, rotary already applied) and
    attends over its cache; a delta-rule or state-space layer takes one
    recurrent step in every lane that ``valid`` marks and leaves the
    others' state as it was. With a ``slot`` it is the ingestion of one
    prompt from position 0 (B == 1): attention stays inside the prompt
    (banded in a window layer) and the layer leaves the prompt's keys
    and values in that slot (a window layer its last W positions, each
    where the ring keeps it); a delta-rule or state-space layer leaves
    the prompt's state, built from zero, over whatever the slot held. A
    latent layer writes the rows it keeps a position (at the cursor of
    every lane ``valid`` marks; the prompt's real positions into the
    slot) and attends the positions its indexer picks.

    ``valid`` (B, S) marks real tokens: the expert layers route nothing
    else, and no state folds anything else in. Returns (logits fp32:
    (B, 1, V), or (V,) at the prompt's last position; the cache's new
    entries, every key of it but ``pos``; ``route``: int32 [tokens
    routed, assignments to held experts, to absent experts, held
    experts touched (both summed over expert layers), largest load of
    one expert], None for a stack without experts). Donated, the cache
    is updated in place."""
    from pbs_tpu.models.moe import (
        expert_form, expert_piece, held_expert_ffn, mlp_ffn,
        shared_expert_ffn)

    plan = plan_of(cfg)
    B, S = tokens.shape
    dt, hd, nkv = cfg.dtype, cfg.head_dim, cfg.n_kv_heads
    decode = slot is None
    if decode and S != 1:
        raise NotImplementedError(
            "a planned stack decodes one position a tick: a window "
            "layer's ring cannot take a multi-token verify window")
    new = {key: dict(entries) for key, entries in cache.items()
           if key != "pos"}
    ks, vs = new["k"], new["v"]
    T = max([cfg.max_seq] + [c.shape[1] for key in ("k", "ckv")
                             for c in new.get(key, {}).values()])
    abs_pos = jnp.minimum(
        row_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :], T - 1)
    tables = {a.rope: rope_table(a.rope, hd, T) for a in plan.attn
              if getattr(a, "rope", None) is not None}
    x = embed_rows(params["embed"], tokens, dt)
    flat_valid = valid.reshape(-1)
    counts = jnp.zeros((4,), jnp.int32)
    forms = set()

    for layer in range(len(plan.layers)):
        a, m = plan.kinds(layer)
        name = block_name(layer)
        block = params["blocks"][name]
        if a is not None:
            ap = block["attn"]
            h = rms_norm(x, ap["attn_norm"], cfg.norm_eps)
            if type(a) in _RECURRENT:
                scope, key, step, ingest = _RECURRENT[type(a)]
                with jax.named_scope(scope):
                    if decode:
                        y, new[key][name], new["conv"][name] = step(
                            a, ap, h, new[key][name], new["conv"][name],
                            valid[:, 0], cfg.norm_eps, dt)
                    else:
                        y, state, tail = ingest(a, ap, h, valid,
                                                cfg.norm_eps, dt)
                        new[key][name] = jax.lax.dynamic_update_slice(
                            new[key][name], state,
                            (slot,) + (0,) * (state.ndim - 1))
                        new["conv"][name] = jax.lax.dynamic_update_slice(
                            new["conv"][name], tail, (slot, 0, 0))
                x = x + y
            elif type(a) in _LATENT:
                x = x + _latent_layer(a, ap, h, new, name, row_pos, valid,
                                      abs_pos, tables, slot, cfg.norm_eps,
                                      dt)
            else:
                x = x + _softmax_layer(
                    a, ap, h, ks, vs, name, row_pos, valid, abs_pos, tables,
                    slot, nkv, hd, dt,
                    decode and _streams_live(S, ks[name], a.window))
        if m is None:
            continue

        mp = block["mlp"]
        h = rms_norm(x, mp["mlp_norm"], cfg.norm_eps)
        if not m.n_experts:
            with jax.named_scope("mlp.dense"):
                y = mlp_ffn(h, mp["w1"], mp.get("w3"), mp["w2"], m.form,
                            lambda rows, w: rows @ wload(w, dt))
        else:
            hf = h.reshape(B * S, -1)
            forms.add(expert_form(expert_piece(B * S), hf.shape[1], m, dt))
            y, c = held_expert_ffn(hf, mp, m, flat_valid, dt)
            if m.shared_d_ff:
                y = y + shared_expert_ffn(hf, mp, dt, m.form)
            y = y.reshape(B, S, -1)
            counts = jnp.concatenate(
                [counts[:3] + c[:3], jnp.maximum(counts[3:], c[3:])])
        x = x + y

    # One record a form of this program's expert products, as it is
    # traced: ``experts.every | grouped-kernel | ragged_dot``, size the
    # rows one product is over (docs/TRACING.md, ``HOST_PHASE``).
    for form, rows in sorted(forms):
        with host_phase(f"experts.{form}", rows):
            pass
    if not decode:
        x = jax.lax.dynamic_index_in_dim(
            x[0], jnp.maximum(valid.sum() - 1, 0), 0, keepdims=False)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        # the embedding read where it lies, rows against rows: no
        # (d, vocab) copy of it is made
        logits = jnp.einsum("...d,vd->...v", x, wload(params["embed"], dt))
    else:
        logits = x @ wload(params["head"], dt)
    logits = logits.astype(jnp.float32)
    route = jnp.concatenate(
        [valid.sum().astype(jnp.int32)[None], counts]) \
        if plan.routed else None
    return logits, new, route


def _softmax_layer(a, ap: dict, h: jax.Array, ks: dict, vs: dict, name: str,
                   row_pos, valid, abs_pos, tables: dict, slot, nkv: int,
                   hd: int, dt, live: bool = False) -> jax.Array:
    """A full or window attention layer of the planned stack on its
    normed input h (B, S, d): writes the layer's new keys and values
    into ``ks[name]`` / ``vs[name]`` (replaced in the dicts) and
    returns what the layer adds to the stream. ``live``: a decode tick
    whose attention goes through :func:`_cursor_attention`."""
    B, S, _ = h.shape
    H, decode = a.n_heads, slot is None
    q = (h @ wload(ap["wq"], dt)).reshape(B, S, H, hd)
    k = (h @ wload(ap["wk"], dt)).reshape(B, S, nkv, hd)
    v = (h @ wload(ap["wv"], dt)).reshape(B, S, nkv, hd)
    if a.rope is not None:
        cos, sin = (t[abs_pos] for t in tables[a.rope])
        q, k = _rope_leading(q, cos, sin), _rope_leading(k, cos, sin)
    K = ks[name].shape[1]
    with jax.named_scope("attn.window" if a.window else "attn.full"):
        if decode:
            at = row_pos % K if a.window else row_pos
            ks[name] = _write_rows(ks[name], k, at)
            vs[name] = _write_rows(vs[name], v, at)
            if live:
                # an idle lane's cursor rests where its last request
                # ended, and nothing up to there is its to read: it
                # attends its first entry
                attn = _cursor_attention(
                    q, ks[name], vs[name],
                    jnp.where(valid[:, 0], row_pos, 0), None, dt)
            else:
                col = jnp.arange(K)[None, :]
                # Ring entry j holds the largest p <= cursor with
                # p = j mod W: live once written, always after a lap.
                seen = (col <= row_pos[:, None]) | (
                    (row_pos[:, None] >= K) if a.window else False)
                attn = _grouped_attention(q, ks[name], vs[name],
                                          seen[:, None, :], dt)
        else:
            i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
            seen = (j <= i) & ((i - j < a.window) if a.window else True)
            attn = _grouped_attention(q, k, v, seen[None], dt)
            if a.window:
                # Entry j of the ring: the prompt's last position
                # that is j mod W (an entry with none is not live).
                last = row_pos[0] + valid.sum() - 1
                src = last - (last - jnp.arange(K)) % K
                k, v = (t[:, jnp.clip(src, 0, S - 1)] for t in (k, v))
            else:
                k, v = k[:, :K], v[:, :K]
            ks[name] = jax.lax.dynamic_update_slice(
                ks[name], k, (slot, 0, 0, 0))
            vs[name] = jax.lax.dynamic_update_slice(
                vs[name], v, (slot, 0, 0, 0))
        if a.gate == "per_head":
            attn = attn * jax.nn.sigmoid(h @ wload(ap["wg"], dt))[..., None]
        elif a.gate == "elementwise":
            attn = attn * jax.nn.sigmoid(
                h @ wload(ap["wg"], dt)).reshape(B, S, H, hd)
    return attn.reshape(B, S, H * hd) @ wload(ap["wo"], dt)


def _latent_layer(a, ap: dict, h: jax.Array, new: dict, name: str,
                  row_pos, valid, abs_pos, tables: dict, slot, eps: float,
                  dt) -> jax.Array:
    """A latent layer of the planned stack on its normed input h (B, S,
    d): writes the rows the layer keeps a position into its entries of
    ``new`` (replaced in the dicts) and returns what the layer adds to
    the stream. Padding and idle lanes change no row."""
    scope, keys, step, ingest = _LATENT[type(a)]
    cos, sin = (t[abs_pos] for t in tables[a.rope])
    rows = [new[key][name] for key in keys]
    with jax.named_scope(scope):
        if slot is None:
            out, *rows = step(a, ap, h, *rows, row_pos, valid[:, 0], cos,
                              sin, eps, dt)
        else:
            out, *prompt = ingest(a, ap, h, valid, cos, sin, eps, dt)
            at = (slot, 0, 0)
            for i, fresh in enumerate(prompt):
                K = min(fresh.shape[1], rows[i].shape[1])
                held = jax.lax.dynamic_slice(
                    rows[i], at, (1, K) + rows[i].shape[2:])
                rows[i] = jax.lax.dynamic_update_slice(
                    rows[i], jnp.where(valid[0, :K, None], fresh[:, :K],
                                       held), at)
        for key, r in zip(keys, rows):
            new[key][name] = r
        return out @ wload(ap["wo"], dt)


class _ScanProgram:
    """Every layer alike (any dense configuration, and the ``mlp_fn``
    fixture): the layer ``lax.scan`` of ``_slot_forward`` over one
    ``(L, slots, max_len, nkv, hd)`` cache. Routes nothing."""

    #: a window of positions can be cut from, installed into and
    #: verified over every layer's cache (prefix cache, speculation)
    windows = True
    #: no layer chooses among the positions it keeps
    select_topk = None

    def __init__(self, cfg: TransformerConfig, mlp_fn=None, mesh=None):
        self.cfg, self.mlp_fn = cfg, mlp_fn
        #: where the cache lies: on one device the decode's attention
        #: may be the one-pass kernel, which takes a cache whole
        self.devices = _placed_on(mesh)

    def select_block(self, cache: dict) -> int:
        return 0

    def attend_blocks(self, cache: dict) -> list[tuple[int, int]]:
        """(positions kept, positions a block) of every layer whose
        decode attention streams a lane's live blocks out of ``cache``
        (``_cursor_attention``'s kernel); none where the ``jax.numpy``
        form runs."""
        return _live_blocks(cache["k"], self.devices, self.cfg.n_layers)

    def init_params(self, key: jax.Array) -> dict:
        return init_params(self.cfg, key)

    def init_cache(self, n_slots: int, max_len: int) -> dict:
        return init_slot_cache(self.cfg, n_slots, max_len)

    def place_cache(self, cache: dict, mesh) -> dict:
        """KV slabs cut over the kv heads on the mesh's tensor axis,
        cursors replicated."""
        kv = slot_cache_kv_sharding(mesh)
        heads, ways = cache["k"].shape[-2], mesh.shape[kv.spec[-2]]
        if heads % ways:
            raise ValueError(
                f"n_kv_heads={heads} not divisible by the {ways} devices "
                f"of the mesh's tensor axis")
        return {
            "k": jax.device_put(cache["k"], kv),
            "v": jax.device_put(cache["v"], kv),
            "pos": jax.device_put(
                cache["pos"], NamedSharding(mesh, PartitionSpec(None))),
        }

    def decode(self, params, cache, last_tok, active):
        _say_attention(len(self.attend_blocks(cache)), self.cfg.n_layers)
        live = len(self.devices) == 1 and _streams_live(1, cache["k"])
        logits, new, extra = _slot_forward(
            self.cfg, params, last_tok[:, None], cache, cache["pos"],
            mlp_fn=self.mlp_fn, active=active if live else None)
        return logits, new, extra, None

    def ingest(self, params, cache, slot, prompt, plen):
        return ingest_slot_prompt(self.cfg, params, cache, slot, prompt,
                                  plen, mlp_fn=self.mlp_fn) + (None,)


class _PlannedProgram:
    """Layers that differ (``cfg.layer_plan``): every kind of per-slot
    state in one manager (positions, a ring of them, a recurrent
    state), the grouped expert layer, ``route`` counters."""

    #: neither a ring nor a state hands out or takes in a window of
    #: positions (``no_windows`` says which, for the error)
    windows = False

    def __init__(self, cfg: TransformerConfig, mesh=None):
        self.cfg = cfg
        #: where the cache lies (one device: ``place_cache``)
        self.devices = _placed_on(mesh)
        plan = plan_of(cfg)
        #: the most positions a selecting layer's query attends (the
        #: engine's ``ENG_SELECT`` counts by it); None: no such layer
        self.select_topk = plan.select_topk
        self.no_windows = (
            "a matrix-state layer keeps one float32 (head_dim, d_state) "
            "state a head and a convolution tail a slot, not positions, "
            "and every token of a prompt is folded into it: a prefix hit, "
            "a preemption or a verify window would need a snapshot of "
            "that state (megabytes a layer) at the window's end (ROADMAP "
            "R23)"
            if any(isinstance(a, Mamba2Kind) for a in plan.attn) else
            "a delta-rule or state-space layer keeps one recurrent state "
            "a slot, not positions: a prefix hit or a verify window would "
            "need a snapshot of that state at the window's end (ROADMAP "
            "R6, R23)"
            if plan.recurrent else
            "a latent layer keeps a latent row, a rotary key and an "
            "indexer key a position, not keys and values a head: a "
            "window is cut from and installed into k and v alone, and a "
            "verify window would need the indexer's choice for k + 1 "
            "queries a lane (ROADMAP R5, R25)"
            if plan.select_topk is not None else
            "a window layer's ring takes one position a tick, and "
            "cutting a window from it or installing one is not written "
            "(ROADMAP R4)")

    def select_block(self, cache: dict) -> int:
        """Positions a block of the one-pass attention a selecting
        layer's decode runs over its rows of ``cache``
        (``mla.streamed_block``); 0: none does."""
        plan = plan_of(self.cfg)
        return max((streamed_block(plan.kinds(int(name))[0], ckv)
                    for name, ckv in cache.get("ckv", {}).items()),
                   default=0)

    def attend_blocks(self, cache: dict) -> list[tuple[int, int]]:
        """(positions kept, positions a block) of every full layer
        whose decode attention streams a lane's live blocks out of
        ``cache`` (``_cursor_attention``'s kernel); none where the
        ``jax.numpy`` form runs."""
        plan = plan_of(self.cfg)
        return [pair for name, k in cache["k"].items()
                for pair in _live_blocks(
                    k, self.devices,
                    window=plan.kinds(int(name))[0].window)]

    def init_params(self, key: jax.Array) -> dict:
        return init_plan_params(self.cfg, key)

    def init_cache(self, n_slots: int, max_len: int) -> dict:
        return init_plan_cache(self.cfg, n_slots, max_len)

    def place_cache(self, cache: dict, mesh) -> dict:
        """One device: how a ring and a share of experts divide over a
        tensor axis is not written."""
        if mesh.devices.size != 1:
            raise NotImplementedError(
                f"a planned layer stack serves on one device, not on a "
                f"mesh of {dict(mesh.shape)}: neither the window ring's, "
                f"the recurrent state's, the latent rows' nor the held "
                f"experts' division over a tensor axis is written "
                f"(ROADMAP R4, R5, R6, R23)")
        return jax.device_put(cache, NamedSharding(mesh, PartitionSpec()))

    def decode(self, params, cache, last_tok, active):
        _say_attention(len(self.attend_blocks(cache)), len(cache["k"]))
        logits, new, route = _plan_forward(
            self.cfg, params, last_tok[:, None], cache, cache["pos"],
            active[:, None])
        return (logits, dict(new, pos=cache["pos"]),
                jnp.zeros((), jnp.float32), route)

    def ingest(self, params, cache, slot, prompt, plen):
        valid = (jnp.arange(prompt.shape[0]) < plen)[None, :]
        last_logits, new, route = _plan_forward(
            self.cfg, params, prompt[None, :], cache,
            jnp.zeros((1,), jnp.int32), valid, slot=slot)
        cache = dict(new, pos=cache["pos"].at[slot].set(plen))
        return last_logits, cache, jnp.zeros((), jnp.float32), route


def slot_program(cfg: TransformerConfig, mlp_fn=None, mesh=None):
    """What a configuration's layer stack gives the engine and the
    serve backend, and the one place that chooses between the two
    forms: its parameter tree (``init_params``), its cache
    (``init_cache``) and where that lies on a mesh (``place_cache``;
    the weights are the caller's to place, ``serve.partition.place``),
    one decode position for every slot (``decode``)
    and the ingestion of one prompt (``ingest``), both returning
    ``(logits, cache, mlp extra, route)``, and whether its caches take
    windows of positions (``windows``). A configuration whose layers
    are all alike, said by its widths or by a plan, gets the stacked
    tree and the layer scan it always had (with an untied head: a tied
    one is the planned program's to read). ``mesh``: the one the cache
    will be placed on (none: the default device), which a decode has to
    know when it is traced."""
    if plan_of(cfg) == uniform_plan(cfg) and not cfg.tie_embeddings:
        return _ScanProgram(cfg, mlp_fn, mesh)
    if mlp_fn is not None:
        raise ValueError("a planned layer stack names its own MLP kinds; "
                         "mlp_fn swaps the FFN of a uniform stack only")
    return _PlannedProgram(cfg, mesh)


def prefill_rungs(bucket: int) -> tuple[int, ...]:
    """The padded lengths a prompt forward is compiled at, ascending:
    ``bucket`` and its half, if a prefill of that half is still bound
    by arithmetic on this chip. The chip's balance (peak FLOP/s over
    peak bytes/s: 240 rows of bf16 on a v5e), rounded up to a power of
    two, is where that stops; below it a prefill reads every weight
    once, as a decode tick does, and a shorter rung would buy a
    compile and no time. A bucket that small (every test's) is its own
    only rung. One halving, not a ladder down to that floor: every
    rung is one more program to trace, lower and load at construction
    (half a second of set-up each, measured), and a quarter-bucket
    rung bought a third of a percent where it was tried (PERF.md 6,
    PR 29)."""
    from pbs_tpu.telemetry.peaks import device_peaks

    peaks = device_peaks()
    floor = 1 << math.ceil(math.log2(peaks.flops / peaks.hbm_bw))
    half = bucket // 2
    return (half, bucket) if bucket % 2 == 0 and half >= floor \
        else (bucket,)


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
    the device has run. ``has_work()`` stays true until the last tick
    is booked; ``settle()`` books it now; ``step_settled()`` is a tick
    that leaves nothing in flight.
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
                # Tensor-parallel serving by PLACEMENT (the GSPMD
                # recipe): the caller handed ``params`` already laid
                # out on ``mesh`` (serve.partition.place) and the engine
                # lays the KV slabs over the kv heads; the two jitted
                # programs below are unchanged — XLA propagates the
                # shardings and inserts the collectives.
                cache = self.program.place_cache(cache, mesh)
            span.size = sum(x.nbytes for x in jax.tree.leaves(
                jax.block_until_ready(cache)))
        self.params = params
        self.cache = cache
        #: positions a block of a selecting layer's one-pass attention
        #: over this cache (``ENG_SELECT`` counts a tick's blocks by
        #: it); 0: no such layer, or its ``jax.numpy`` form runs
        self._select_block = self.program.select_block(cache)
        #: the layers whose decode attention streams live blocks, by
        #: (positions kept, positions a block), and how many layers of
        #: each (``ENG_ATTEND`` counts a tick's blocks by them); empty:
        #: the ``jax.numpy`` form runs
        pairs, self._attend_layers = np.unique(
            np.array(self.program.attend_blocks(cache),
                     np.int64).reshape(-1, 2), axis=0, return_counts=True)
        self._attend_kept, self._attend_block = pairs[:, :1], pairs[:, 1:]
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
        # FFN auxiliary telemetry (MoE: drop fraction), averaged over
        # forwards — the capacity-starvation signal the lockstep MoE
        # serving path reports, preserved through the engine.
        self._mlp_extra_sum = 0.0
        self._mlp_extra_n = 0
        # Exact-prompt prefix cache (system-prompt reuse): LRU of
        # {prompt bytes -> prompt-window KV + last-position logits}.
        # Entries are DEVICE arrays — storing the lazy slot slice
        # costs bounded HBM instead of a synchronous device-to-host
        # copy on every miss (which would inflate every unique
        # prompt's TTFT). A hit installs the KV into the slot and
        # samples the first token from the cached logits — zero
        # prefill compute. 0 = off.
        # Under a tp serving mesh the cached windows are sliced from
        # the tp-sharded slot cache, so they arrive ALREADY sharded
        # over the kv heads (the sliced dims — layer/slot/seq — are
        # unsharded); _install re-pins the canonical layout with a
        # sharding constraint below, so hits keep the KV on-device and
        # tp-aligned (r5: the former mesh restriction is lifted — tp
        # serving no longer loses the TTFT optimization).
        self.prefix_cache_size = prefix_cache_size
        self._prefix_cache: "OrderedDict[bytes, dict]" = OrderedDict()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefill_count = 0  # real prefill dispatches (cache misses)
        self.prefill_rows = 0  # rows they ran at (each one's rung)
        self.prefill_prompt_tokens = 0  # rows of those that were prompt

        cfg_ = cfg

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _prefill(params, cache, slot, prompt, plen, key):
            """Write one request's prompt into ``slot`` and sample its
            first token. prompt: (rung,) padded; plen: real length.
            Also returns the last-position logits (for the prefix
            cache)."""
            last_logits, cache, extra, route = self.program.ingest(
                params, cache, slot, prompt, plen)
            first = _sample(last_logits[None, :], key,
                            self.temperature)[0]
            if route is not None:  # rides to the host with the token
                first = jnp.concatenate([first[None], route])
            return first, last_logits, cache, extra

        _kv_sharding = slot_cache_kv_sharding(mesh) \
            if mesh is not None else None

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _install(cache, slot, kwin, vwin, plen):
            """Prefix-cache hit: write the cached prompt-window KV
            (L, 1, bucket, nkv, hd) into ``slot``; no forward at all.
            Under a tp mesh the constraint pins the updated slabs back
            to the canonical kv-head sharding (the window arrives
            sharded the same way — the constraint is a no-op reshard
            in the common case, a guard against layout drift always)."""
            cache = dict(cache)
            k = jax.lax.dynamic_update_slice(
                cache["k"], kwin, (0, slot, 0, 0, 0))
            v = jax.lax.dynamic_update_slice(
                cache["v"], vwin, (0, slot, 0, 0, 0))
            if _kv_sharding is not None:
                k = jax.lax.with_sharding_constraint(k, _kv_sharding)
                v = jax.lax.with_sharding_constraint(v, _kv_sharding)
            cache["k"] = k
            cache["v"] = v
            cache["pos"] = cache["pos"].at[slot].set(plen)
            return cache

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
        # Every rung is its own instance of the prefill: all of them
        # now, so that none compiles under a request.
        wk = jax.random.PRNGKey(0)
        for rung in self.rungs:
            self.cache = self._build(
                f"eng.prefill@{rung}", rung, lambda: _prefill(
                    self.params, self.cache, 0,
                    jnp.zeros((rung,), jnp.int32), 0, wk)[2])
        if prefix_cache_size:
            win = jnp.zeros((cfg.n_layers, 1, self.bucket,
                             cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
            self.cache = self._build(
                "eng.install", self.bucket,
                lambda: _install(self.cache, 0, win, win, 0))
        # The decode twice, the second on the first's own tokens: what
        # every tick after the first is handed (an output of the
        # program, not an array the host made) is then a signature this
        # warm-up has met, whatever sharding or commitment it carries.
        off = jnp.full((n_slots,), _LANE_OFF, jnp.int32)

        def _warm_decode():
            tok, _, cache, _ = _decode(self.params, self.cache, off, off, wk)
            return _decode(self.params, cache, tok, off, wk)

        self._dev_tok, _, self.cache, _ = self._build(
            "eng.decode", n_slots, _warm_decode)
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

    def _select_ev(self, ts_ns: int, live: np.ndarray,
                   block: int = 0) -> None:
        """``ENG_SELECT``: how many positions each of this call's
        queries sees (``live``, one entry a busy lane or a prompt
        token) and how many of them a layer that chooses attends, from
        what the host knows of its slots; stamped like the call's
        ``ENG_DECODE`` or ``ENG_PREFILL``. ``block``: the positions a
        block of the decode's one-pass attention, which streams a
        lane's blocks up to the one its cursor (``live - 1``) is in; 0
        where no such kernel runs (a prefill; the ``jax.numpy`` form).
        Nothing for a program in which no layer chooses."""
        topk = self.program.select_topk
        if topk is not None:
            self._ev(ts_ns, Ev.ENG_SELECT, self._tick_seq, len(live),
                     int(live.sum()), int(np.minimum(live, topk).sum()),
                     topk, int(((live - 1) // block + 1).sum())
                     if block else 0)

    def _attend_ev(self, ts_ns: int, live: np.ndarray) -> None:
        """``ENG_ATTEND``: the blocks of keys and values this decode's
        one-pass attention fetches against the blocks its caches have,
        from what the host knows of its slots (``live``: the positions
        each busy lane's query sees); stamped like the call's
        ``ENG_DECODE``. A busy lane streams the blocks up to the one
        its cursor (``live - 1``) is in, a ring that has lapped all of
        them; an idle lane one. Nothing where the ``jax.numpy`` form
        runs."""
        layers = self._attend_layers
        if len(layers) and self.trace is not None:
            cursor = np.minimum(live[None, :] - 1, self._attend_kept - 1)
            fetched = (cursor // self._attend_block + 1).sum(axis=1) \
                + self.n_slots - len(live)
            have = (self._attend_kept // self._attend_block)[:, 0] \
                * self.n_slots
            self._ev(ts_ns, Ev.ENG_ATTEND, self._tick_seq, len(live),
                     int(live.sum()), int(fetched @ layers),
                     int(have @ layers), int(layers.sum()))

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
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError("prompt + max_new_tokens exceeds max_len")
        rid = next(self._ids)
        self.queue.append((rid, prompt, int(max_new_tokens)))
        self._submitted_step[rid] = self.steps
        self._submitted_t[rid] = self._now()
        if self.submit_hook is not None:
            self.submit_hook(rid, len(prompt), int(max_new_tokens))
        return rid

    # -- the engine tick --------------------------------------------------

    def _admit(self) -> None:
        # (slot, padded_prompt, plen) of this tick's admissions, each
        # padded to its rung — the hook subclasses use to mirror work
        # per new tenant (the speculative engine draft-prefills the
        # same prompt, at the same rung by its shape).
        # Initialized in __init__ too, so it is safe to read pre-tick.
        self._admitted = []
        for slot in range(self.n_slots):
            if self.active[slot] or not self.queue:
                continue
            with _span("pbst.eng.admit"):
                self._admit_one(slot)

    def _rung(self, plen: int) -> int:
        """The padded length a prompt of ``plen`` tokens runs at."""
        return next(r for r in self.rungs if r >= plen)

    def _admit_one(self, slot: int) -> None:
        t_admit = _ns()
        tick = self._tick_seq
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
        t_prefill = _ns()
        with _span("pbst.eng.prefill"):
            if ent is not None:
                # Hit: install cached KV, sample from cached logits —
                # the prompt forward is skipped entirely.
                self._prefix_cache.move_to_end(pkey)
                self.prefix_hits += 1
                self.cache = self._install_fn(
                    self.cache, slot, ent["k"], ent["v"],
                    int(ent["plen"]))
                t_dispatched = _ns()
                first = int(_sample(
                    ent["logits"][None, :], sub, self.temperature)[0])
            else:
                first, last_logits, self.cache, extra = \
                    self._prefill_fn(
                        self.params, self.cache, slot,
                        jnp.asarray(padded), len(prompt), sub)
                t_dispatched = _ns()
                first = np.asarray(first).ravel()
                self._route_ev(t_prefill, first[1:])
                self._select_ev(t_prefill, np.arange(1, len(prompt) + 1))
                first = int(first[0])
                self._mlp_extra_sum += float(extra) / self.cfg.n_layers
        t_synced = _ns()
        self._ev(t_prefill, Ev.ENG_PREFILL, tick, rid, slot,
                 t_dispatched - t_prefill, t_synced - t_dispatched,
                 rows if ent is None else 0)
        if ent is None:
            self._mlp_extra_n += 1
            self.prefill_count += 1
            self.prefill_rows += rows
            self.prefill_prompt_tokens += len(prompt)
            if self.prefix_cache_size:
                self.prefix_misses += 1
                # Device arrays: lazy slices, no host sync here. A
                # window is ``bucket`` positions whatever the rung was
                # (one shape for _install); past the rung it holds an
                # earlier tenant's, which no cursor reaches.
                self._prefix_cache[pkey] = {
                    "k": self.cache["k"][:, slot:slot + 1,
                                         :self.bucket],
                    "v": self.cache["v"][:, slot:slot + 1,
                                         :self.bucket],
                    "logits": last_logits,
                    "plen": len(prompt),
                }
                while len(self._prefix_cache) > self.prefix_cache_size:
                    self._prefix_cache.popitem(last=False)
        self.slot_req[slot] = rid
        self.slot_tokens[slot] = [first]
        self.slot_prompt_len[slot] = len(prompt)
        self.slot_remaining[slot] = max_new - 1
        self.slot_waited[slot] = (
            self.steps - self._submitted_step.pop(rid, self.steps))
        now = self._now()
        t_submit = self._submitted_t.pop(rid, now)
        self.slot_submit_t[slot] = t_submit
        self.slot_ttft[slot] = now - t_submit  # first token sampled
        self.active[slot] = True
        self.last_tok[slot] = first
        self.tokens_emitted += 1
        self._ev(t_admit, Ev.ENG_ADMIT, tick, rid, slot, len(prompt),
                 max(0, round((t_slot - t_submit) * 1e9)),
                 _ns() - t_admit)

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

    def _pre_decode(self) -> tuple[list[Completion], bool]:
        """The tick prologue every engine shares: admit waiting
        requests, retire already-finished slots (prefill-only budgets,
        EOS sampled at admission). Returns (completions, any_active);
        when nothing is active the tick is already accounted."""
        self._admit()
        done: list[Completion] = []
        for slot in range(self.n_slots):
            if self.active[slot] and (
                    self.slot_remaining[slot] <= 0
                    or (self.eos_id is not None
                        and self.last_tok[slot] == self.eos_id)):
                done.append(self._retire(slot))
        if not self.active.any():
            self.steps += 1
            return done, False
        return done, True

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
            self._route_ev(t, self._read_and_book(fl, done))
        return done

    def _decoded(self, t_pre: int, t_enqueued: int, t_host: int,
                 overlapped: int = 0) -> None:
        """Close the tick's decode span (both engines): ``pre`` runs
        from ``t_pre`` (admission and the key split are over) until the
        program is enqueued, host-to-device copies included; ``sync``
        until the tokens this call books are on the host; ``post``
        until now, the emit and retire loops. ``overlapped``: the
        program was enqueued while the decode before it was unread."""
        self._ev(t_pre, Ev.ENG_DECODE, self._tick_seq,
                 t_enqueued - t_pre, t_host - t_enqueued, _ns() - t_host,
                 overlapped)

    def _read(self, fl: _InFlight) -> tuple[np.ndarray, np.ndarray]:
        """Wait for a decode's tokens; ``(tokens, route)`` on the host
        (``route`` empty unless the program routes tokens to experts)."""
        self._mlp_extra_sum += float(fl.extra) / self.cfg.n_layers
        self._mlp_extra_n += 1
        out = np.asarray(fl.out)
        return out[:self.n_slots], out[self.n_slots:]

    def _book(self, fl: _InFlight, toks: np.ndarray,
              done: list[Completion]) -> None:
        """Emit a decode's tokens to the lanes it ran that still hold
        the request they held then, and retire those that finished."""
        for slot, rid in fl.lanes:
            if self.slot_req[slot] == rid and \
                    self._emit(slot, int(toks[slot])):
                done.append(self._retire(slot))

    def _read_and_book(self, fl: _InFlight,
                       done: list[Completion]) -> np.ndarray:
        """Both at once, where no stamp lies between them; the route."""
        toks, route = self._read(fl)
        self._book(fl, toks, done)
        return route

    def _step(self) -> list[Completion]:
        done: list[Completion] = []
        fl, self._inflight = self._inflight, None
        routes = []  # of the decodes this call reads
        if fl is not None and (self._settling or (
                self.queue and not self.active.all())):
            # An admission ends in a host read of the prefill's first
            # token, which waits for everything enqueued before it:
            # book the decode in flight first, so that its tokens are
            # not stamped behind a prefill they did not wait for. The
            # wait lies in the tick's rest, not in ``sync``. (A settled
            # tick that finds a decode in flight books it here too.)
            routes.append(self._read_and_book(fl, done))
            fl = None
        retired, any_active = self._pre_decode()
        done += retired
        # The lanes of this dispatch, decided ahead: a lane whose
        # budget the decode in flight exhausts runs no further token.
        # (An EOS the host has not seen yet cannot stop its lane: that
        # lane runs one token more, which ``_book`` drops.)
        carry = np.zeros(self.n_slots, bool)
        if fl is not None:
            carry[[slot for slot, _ in fl.lanes]] = True
        mask = self.active & (self.slot_remaining > carry)
        overlapped = int(fl is not None)
        seen = None
        if self.program.select_topk is not None or (
                len(self._attend_layers) and self.trace is not None):
            # a lane's new position sees its prompt, the tokens the host
            # has booked and the one still in flight
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
                fl, self._inflight = self._inflight, None
        if fl is not None:
            with _span("pbst.eng.sync"):
                toks, route = self._read(fl)
        t_host = _ns()
        if fl is not None:
            self._book(fl, toks, done)
            routes.append(route)
        if any_active:  # else _pre_decode has counted the tick
            self.steps += 1
        for route in routes:  # stamped like this call's ENG_DECODE
            self._route_ev(t_pre, route)
        if mask.any():
            if seen is not None:
                self._select_ev(t_pre, seen, self._select_block)
                self._attend_ev(t_pre, seen)
            self._decoded(t_pre, t_enqueued, t_host, overlapped)
        return done

    def has_work(self) -> bool:
        return (bool(self.queue) or bool(self.active.any())
                or self._inflight is not None)

    @staticmethod
    def _pct(values, q: float) -> float:
        # Nearest-rank (utils.stats): the old int(q*n) indexed one rank
        # high — p50 of two samples returned the max, inflating every
        # reported percentile by up to one rank.
        from pbs_tpu.utils.stats import nearest_rank

        return nearest_rank(values, q)

    def stats(self) -> dict:
        """Engine + SLO surface: time-to-first-token and completion
        latency percentiles over the last 1024 completions — the
        numbers a serving tenant's latency SLO is written against
        (and what the feedback policy's BOOST class protects)."""
        return {
            "steps": self.steps,
            # Decodes enqueued while the one before was unread, and
            # with nothing in flight (ENG_DECODE's flag, counted).
            "ticks_overlapped": self.ticks_overlapped,
            "ticks_settled": self.ticks_settled,
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
        }


class SpeculativeBatcher(ContinuousBatcher):
    """Continuous batching WITH speculative decoding: every engine
    tick, a draft model proposes ``k`` tokens per slot and the target
    verifies all ``k+1`` positions in ONE forward; each slot advances
    by its own accepted prefix (the per-row cursors of
    ``speculative.make_per_row_speculative_generate``, which this
    engine shares its slot-cache machinery with).

    Combines the two serving accelerations that matter: continuous
    batching hides admission/retirement latency, speculation
    multiplies decode throughput by the acceptance rate — per
    engine tick a slot emits 1..k+1 tokens instead of exactly 1.
    Greedy-only (``temperature=0``): acceptance is exact token match,
    so outputs are bit-identical to the plain engine's (pinned by
    test). Static shapes throughout: the tick runs a fixed
    (n_slots, k) draft scan + one (n_slots, k+1) verify regardless of
    acceptance; finished/inactive lanes ride along masked.

    Truncation safety: a slot that hits EOS or its token budget
    mid-window retires immediately, so the device cursor (which
    advanced past the truncation) is never decoded from again — the
    next tenant's prefill rewrites it.
    """

    def __init__(self, cfg: TransformerConfig, params: dict,
                 draft_cfg: TransformerConfig, draft_params: dict,
                 k: int = 4, draft_mlp_fn=None, **kw):
        if kw.get("temperature", 0.0) != 0.0:
            raise ValueError(
                "SpeculativeBatcher is greedy-only (temperature=0): "
                "exact-match acceptance is the correctness contract")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if cfg.vocab != draft_cfg.vocab:
            raise ValueError("draft vocab != target vocab")
        for c in (cfg, draft_cfg):
            if not slot_program(c).windows:
                raise NotImplementedError(
                    "speculation verifies k + 1 positions a tick, over "
                    "uniform layer stacks only: "
                    + slot_program(c).no_windows)
        super().__init__(cfg, params, **kw)
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_mlp_fn = draft_mlp_fn
        self.k = k
        self.dcache = init_slot_cache(draft_cfg, self.n_slots,
                                      self.max_len)
        if self.mesh is not None:
            # r5: speculative serving composes with the tp mesh — the
            # caller placed both trees; the draft's slot cache lies
            # like the target's. (The prefix cache also composes: a hit
            # installs the TARGET window, and the _admitted hook below
            # draft-prefills hits and misses alike, preserving the pos
            # invariant.)
            self.dcache = slot_program(draft_cfg).place_cache(
                self.dcache, self.mesh)
        self.spec_proposed = 0
        self.spec_accepted = 0
        # Draft-side FFN telemetry (a starved MoE draft collapses
        # acceptance silently; this is its alarm).
        self._draft_extra_sum = 0.0
        self._draft_extra_n = 0
        dcfg_, cfg_, n_slots = draft_cfg, cfg, self.n_slots

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _draft_prefill(dparams, dcache, slot, prompt, plen):
            """Mirror of the target prefill for the draft cache: the
            shared ingest, logits discarded (the target picks tokens)."""
            _, dcache, extra = ingest_slot_prompt(
                dcfg_, dparams, dcache, slot, prompt, plen,
                mlp_fn=self.draft_mlp_fn)
            return dcache, extra

        kk = self.k

        @functools.partial(jax.jit, donate_argnums=(2, 3))
        def _spec_decode(params, dparams, tcache, dcache, cur, active):
            """One speculation round across all slots at their own
            cursors. Returns (toks (B, k+1), counts (B,), caches,
            n_proposed, n_accepted)."""
            pos = tcache["pos"]  # (B,), == dcache["pos"] by invariant

            def dstep(c, _):
                tok, dc, dp, de = c
                logits, dc, e = _slot_forward(
                    dcfg_, dparams, tok[:, None], dc, dp,
                    mlp_fn=self.draft_mlp_fn)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (nxt, dc, dp + 1, de + e), nxt

            zero_e = jnp.zeros((), jnp.float32)
            (last, dcache, dp, d_extra), props = jax.lax.scan(
                dstep, (cur, dcache, pos, zero_e), None, length=kk)
            t = props.T  # (B, k)
            # Ingest t_k so draft KV reaches pos+k whatever acceptance.
            _, dcache, e2 = _slot_forward(
                dcfg_, dparams, last[:, None], dcache, dp,
                mlp_fn=self.draft_mlp_fn)
            d_extra = d_extra + e2

            x = jnp.concatenate([cur[:, None], t], axis=1)  # (B, k+1)
            logits, tcache, extra = _slot_forward(
                cfg_, params, x, tcache, pos, mlp_fn=self.mlp_fn)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            from pbs_tpu.models.speculative import greedy_accept_window

            toks, m_row, _bonus = greedy_accept_window(t, g)
            adv = jnp.where(active, m_row + 1, 0)
            tcache = dict(tcache, pos=pos + adv)
            dcache = dict(dcache, pos=pos + adv)
            n_act = jnp.sum(active.astype(jnp.int32))
            return (toks, adv, tcache, dcache, kk * n_act,
                    jnp.sum(jnp.where(active, m_row, 0)), extra, d_extra)

        self._draft_prefill_fn = _draft_prefill
        self._spec_decode_fn = _spec_decode
        # Warm both programs at construction (same SLO reasoning, same
        # rebinding and same untouched cursors as the parent's warm-up).
        for rung in self.rungs:
            self.dcache = self._build(
                f"eng.draft_prefill@{rung}", rung, lambda: _draft_prefill(
                    self.draft_params, self.dcache, 0,
                    jnp.zeros((rung,), jnp.int32), 0)[0])
        self.cache, self.dcache = self._build(
            "eng.spec_decode", n_slots, lambda: _spec_decode(
                self.params, self.draft_params, self.cache, self.dcache,
                jnp.zeros((n_slots,), jnp.int32),
                jnp.zeros((n_slots,), bool))[2:4])

    def submit(self, prompt, max_new_tokens: int) -> int:
        # The verify window writes up to k+1 positions past the
        # accepted frontier; reserve that slack in the slab.
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens + self.k + 1 > self.max_len:
            raise ValueError(
                "prompt + max_new_tokens + k + 1 exceeds max_len "
                "(speculation needs overshoot room)")
        return super().submit(prompt, max_new_tokens)

    def _step(self) -> list[Completion]:
        done, any_active = self._pre_decode()
        t_pre = _ns()
        for slot, padded, plen in self._admitted:
            self.dcache, d_extra = self._draft_prefill_fn(
                self.draft_params, self.dcache, slot,
                jnp.asarray(padded), plen)
            self._draft_extra_sum += \
                float(d_extra) / self.draft_cfg.n_layers
            self._draft_extra_n += 1
        if not any_active:
            return done
        with _span("pbst.eng.decode"):
            (toks, counts, self.cache, self.dcache, prop, acc, extra,
             d_extra) = (
                self._spec_decode_fn(
                    self.params, self.draft_params, self.cache,
                    self.dcache, jnp.asarray(self.last_tok),
                    jnp.asarray(self.active)))
        t_enqueued = _ns()
        with _span("pbst.eng.sync"):
            self._mlp_extra_sum += float(extra) / self.cfg.n_layers
            self._mlp_extra_n += 1
            # kk+1 draft forwards per tick, each a per-layer sum.
            self._draft_extra_sum += (float(d_extra)
                                      / (self.draft_cfg.n_layers
                                         * (self.k + 1)))
            self._draft_extra_n += 1
            toks = np.asarray(toks)
            counts = np.asarray(counts)
            self.spec_proposed += int(prop)
            self.spec_accepted += int(acc)
        t_host = _ns()
        for slot in range(self.n_slots):
            if not self.active[slot]:
                continue
            for j in range(int(counts[slot])):
                if self._emit(slot, int(toks[slot, j])):
                    # Truncate mid-window: the device cursor is ahead,
                    # but this slot retires NOW, so it is never decoded
                    # from again.
                    done.append(self._retire(slot))
                    break
        self.steps += 1
        self._decoded(t_pre, t_enqueued, t_host)
        return done

    def stats(self) -> dict:
        st = super().stats()
        st["spec_proposed"] = self.spec_proposed
        st["spec_accepted"] = self.spec_accepted
        st["spec_acceptance"] = round(
            self.spec_accepted / self.spec_proposed, 4) \
            if self.spec_proposed else 0.0
        st["draft_mlp_extra_mean"] = round(
            self._draft_extra_sum / self._draft_extra_n, 6) \
            if self._draft_extra_n else 0.0
        return st


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
