"""The slot programs: what a configuration's layer stack is to the
slot engine.

``slot_program(cfg, mlp_fn, mesh)`` gives the engine
(``models/serving.py``) and the serve backend a parameter tree, a
per-slot cache, one decode position for every slot and the ingestion of
one prompt, and is the one place that chooses between the two forms: a
stack whose layers are all alike runs the layer ``lax.scan`` of
:func:`_slot_forward` over one stacked cache (:class:`_ScanProgram`), a
stack whose layers differ (``cfg.layer_plan``: full and window softmax
layers, delta-rule, state-space and latent layers, expert layers) runs
:func:`_plan_forward` layer by layer over a cache a layer
(:class:`_PlannedProgram`).

- **Per-slot cursors**: unlike ``forward_with_cache`` (one scalar
  position for the whole batch), every slot carries its own ``pos``;
  rope tables are gathered per row, cache writes are one
  dynamic_update_slice a row, and the causal mask compares against each
  row's own position.
- **Inactive lanes ride along**: an empty slot still computes (masked
  to self-attention on garbage it never emits). Wasted FLOPs on idle
  lanes buy shape stability.
- **Which layers' decode attention streams a lane's live blocks**
  (``ops/kv_attend.py``, ``ops/mla_attend.py``) is decided once, by
  :func:`live_layers`: the forwards, the ``attn.*`` records of a traced
  decode and the engine's block counts all read its answer.

Nothing here knows the engine: this module imports nothing from
``models/serving.py``, ``serve/`` or ``gateway/``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from pbs_tpu.models.kda import kda_decode, kda_ingest
from pbs_tpu.models.mamba import mamba_decode, mamba_ingest
from pbs_tpu.models.mamba2 import mamba2_decode, mamba2_ingest
from pbs_tpu.models.mla import ingest_tiles, mla_decode, mla_ingest
from pbs_tpu.models.plan import (
    DRAFT_BLOCK, AttnKind, ConvKind, KdaKind, Mamba2Kind, MambaKind, MlaKind,
    block_name, init_plan_params, plan_of, rope_table, uniform_plan)
from pbs_tpu.models.quant import embed_rows, wload
from pbs_tpu.models.shortconv import conv_decode, conv_ingest
from pbs_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    rms_norm,
    rope_tables,
)
from pbs_tpu.obs.trace import host_phase
from pbs_tpu.ops.kv_attend import attend_block as kv_attend_block
from pbs_tpu.ops.kv_attend import kv_attend, kv_attend_tiles
from pbs_tpu.ops.mla_attend import attend_block as mla_attend_block
from pbs_tpu.ops.mla_attend import mla_attend_tiles
from pbs_tpu.models.speculative import greedy_accept_window
from pbs_tpu.parallel.sharding import slot_cache_kv_sharding


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


def _grouped_attention(q, k, v, mask, dt, width=None):
    """q (B, S, H, hd) against k, v (B, K, nkv, hd); query head g reads
    kv head g // (H / nkv); ``mask`` (B or 1, S, K) says what a query
    sees. Softmax in float32. Returns (B, S, H, hd). ``width``: the
    head width the scores are scaled by where it is not ``hd`` (the
    rows of a packed cache hold several heads: :func:`kv_pack`)."""
    B, S, H, hd = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, S, nkv, H // nkv, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)  # (B, nkv, K, hd)
    vt = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bngqh,bnkh->bngqk", qg, kt) / np.sqrt(width or hd)
    mask = jnp.broadcast_to(mask[:, None, None, :, :], scores.shape)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
    attn = jnp.einsum("bngqk,bnkh->bngqh", probs, vt)
    return attn.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


# One trace and one lowered function a cache shape, whatever the layers
# (``models/mamba.py::_kernel_scan`` says why).
_kernel_attend = jax.jit(kv_attend, static_argnames=("scale",))


def _cursor_attention(q, k, v, at, layer, dt, width=None):
    """A decode tick's attention, one query position a lane: q (B, 1,
    H, hd) over the positions ``<= at[b]`` of the layer's k and v (B,
    K, nkv, hd), all of them where the cursor is past the last (a ring
    that has lapped); with ``layer`` (an int32 scalar) k and v are the
    caches of every layer, (L, B, K, nkv, hd). By the platform the
    program is lowered for: on a TPU the one-pass kernel over each
    lane's live blocks (``ops/kv_attend.py``; its tiling has to take
    the shapes, :func:`live_layers`), anywhere else
    :func:`_grouped_attention` over the whole cache under the mask.
    ``width`` as :func:`_grouped_attention` takes it. Returns (B, 1, H,
    hd)."""
    index = () if layer is None else (layer,)
    scale = {} if width is None else {"scale": 1.0 / math.sqrt(width)}

    def numpy_way(q, k, v, at, *index):
        if index:
            k, v = (jax.lax.dynamic_index_in_dim(t, index[0], 0,
                                                 keepdims=False)
                    for t in (k, v))
        seen = jnp.arange(k.shape[1])[None, :] <= at[:, None]
        return _grouped_attention(q, k, v, seen[:, None, :], dt, width)

    return jax.lax.platform_dependent(
        q, k, v, at, *index,
        tpu=lambda q, k, v, at, *index: _kernel_attend(
            q[:, 0], k, v, at, *index, **scale)[:, None],
        default=numpy_way)


def kv_pack(nkv: int, hd: int) -> int:
    """KV heads a row of a planned softmax layer's cache holds side by
    side. XLA:TPU lays the last axis of an array over rows of 128 lanes:
    a cache ``(slots, T, nkv, 64)`` would pay a row of 128 for every
    head of 64, twice what it holds. So where a head is narrower than a
    row and whole heads fill it, the cache keeps ``128 / hd`` KV heads
    to a row, ``(slots, T, nkv / pack, pack * hd)``: a position costs
    what it holds, and a row is what ``ops/kv_attend.py`` streams. 1:
    a head a row, the cache as it always lay."""
    per = 128 // hd if hd < 128 and 128 % hd == 0 else 1
    return per if nkv % per == 0 else 1


def _own_part(H: int, nkv: int, pack: int) -> np.ndarray:
    """(H, pack) bool: the part of a packed row query head h's KV head
    lies in (KV head ``h // (H / nkv)``, the ``% pack``-th of its
    row)."""
    part = (np.arange(H) // (H // nkv)) % pack
    return part[:, None] == np.arange(pack)[None, :]


def _to_packed(q: jax.Array, nkv: int, pack: int) -> jax.Array:
    """q (B, S, H, hd) as rows of ``pack * hd``, each head in the part
    its KV head has of a packed row and zeros in the others: its dot
    product with a packed row of keys is its score with its own KV
    head, to the bit (the other parts add exact zeros)."""
    B, S, H, hd = q.shape
    own = jnp.asarray(_own_part(H, nkv, pack), q.dtype)
    return (q[..., None, :] * own[:, :, None]).reshape(B, S, H, pack * hd)


def _from_packed(o: jax.Array, nkv: int, pack: int) -> jax.Array:
    """The part of each head's (B, S, H, pack * hd) output that its own
    KV head's values gave: (B, S, H, hd)."""
    B, S, H, w = o.shape
    own = jnp.asarray(_own_part(H, nkv, pack), o.dtype)
    return jnp.sum(o.reshape(B, S, H, pack, w // pack) * own[:, :, None],
                   axis=-2)


def _placed_on(mesh) -> tuple:
    """The devices a program's cache lies on: the mesh's, or without
    one the default device."""
    return tuple(mesh.devices.flat) if mesh is not None \
        else tuple(jax.devices()[:1])


def live_layers(plan, cache: dict, devices: tuple,
                lowered: bool = False) -> dict[int, tuple[str, int, int]]:
    """Which layers' decode attention streams a lane's live blocks, and
    by what block: ``{layer: (kind, positions kept, positions a
    block)}``, kind ``"kv"`` (keys and values a head through
    ``ops/kv_attend.py``) or ``"latent"`` (a latent layer's rows
    through ``ops/mla_attend.py``), over the layers of ``plan`` whose
    state is in ``cache`` (arrays or their shapes; ``k`` and ``v`` one
    array a layer, or stacked by layer). The one place that decides it,
    from what can be seen when a program is built:

    - the cache lies on one device (the kernels take a cache whole:
      how blocks divide over a tensor axis is not written);
    - the kernel's tiling takes the layer's shapes
      (``kv_attend_tiles``, more than one KV head among them;
      ``mla_attend_tiles``);
    - the layer keeps no ring (all of a lapped ring is live, and its
      ``jax.numpy`` form is the faster: PERF.md section 6, PR 45).

    That is how a decode is *traced*: such a layer's attention goes
    through ``jax.lax.platform_dependent``, the kernel where the
    program is lowered for a TPU and the ``jax.numpy`` form anywhere
    else, so a program compiled for a described chip holds the kernel
    with nothing passed. ``lowered`` asks for the layers that *run* the
    kernel besides: the one device a TPU. The records go by that (a
    traced decode's ``attn.*`` marks, the engine's ``ENG_SELECT`` and
    ``ENG_ATTEND`` block counts); the forwards by the first. A
    drafting block's mixer is one more layer, numbered behind the
    stack's last."""
    if len(devices) != 1 or (lowered and devices[0].platform != "tpu"):
        return {}
    out = {}
    for layer, name, a in _mixers(plan):
        if isinstance(a, MlaKind):
            kept, rank = cache["ckv"][name].shape[1:]
            if mla_attend_tiles(a.n_heads, kept, rank):
                out[layer] = ("latent", kept, mla_attend_block(kept))
        elif isinstance(a, AttnKind) and not a.window:
            k = cache["k"][name] if isinstance(cache["k"], dict) \
                else cache["k"]
            kept, nkv, hd = k.shape[-3:]
            if kv_attend_tiles(nkv, hd, kept):
                out[layer] = ("kv", kept, kv_attend_block(kept, nkv))
    return out


def _mixers(plan):
    """(layer, name, mixer kind) of every block that has a mixer: the
    stack's by their number, then the drafting block's (where the plan
    has one) as one more layer behind the last, under its own name."""
    out = [(layer, block_name(layer), plan.kinds(layer)[0])
           for layer in range(len(plan.layers))]
    if plan.draft is not None:
        out.append((len(plan.layers), DRAFT_BLOCK, plan.draft_kinds[0]))
    return [entry for entry in out if entry[2] is not None]


def live_ingest(plan, rung: int, devices: tuple,
                lowered: bool = False) -> frozenset[int]:
    """Which latent layers' attention of a prompt forward at ``rung``
    rows streams the key blocks a block of queries can see
    (``ops/mla_ingest_attend.py``), decided as :func:`live_layers`
    decides a decode's: the cache (and so the prompt's keys and values)
    on one device, shapes the kernel's tiling takes
    (``mla.ingest_tiles``: head dims whole rows of lanes, the rung's
    spans whole key blocks). Traced so, a layer's attention goes
    through ``jax.lax.platform_dependent``; ``lowered`` asks for the
    layers that *run* the kernel besides, the one device a TPU
    (``ENG_SELECT``'s ``blocks`` of a prefill go by that)."""
    if len(devices) != 1 or (lowered and devices[0].platform != "tpu"):
        return frozenset()
    return frozenset(layer for layer, _, a in _mixers(plan)
                     if isinstance(a, MlaKind) and ingest_tiles(a, rung))


def _say_attention(live: dict, layers: int) -> None:
    """A traced decode program's ``HOST_PHASE`` records of no length,
    ``attn.live-kernel`` and ``attn.jnp``, size the softmax layers over
    keys and values that run in that form (those of ``live``, the
    program's :func:`live_layers` as lowered, of ``layers``), as
    ``experts.<form>`` says an expert layer's."""
    kernel = sum(kind == "kv" for kind, _, _ in live.values())
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

    ``active`` (B,) bool, the decode tick's alone, and only where
    :func:`live_layers` says the layers stream live blocks (S == 1, the
    cache on one device, shapes the kernel takes): the lanes that hold
    a request. Given, a lane's query attends through
    :func:`_cursor_attention`, which on a TPU streams the lane's live
    blocks out of the stacked cache and slices no layer; a lane that
    holds none attends its first position alone (its cursor rests where
    its last request ended, and nothing up to there is its to read)."""
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
    live = active is not None

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
    under an indexer its key ``ik``, ``(slots, max_len, index_dim)``. A
    plan's drafting block keeps its mixer's rows beside the layers',
    under ``plan.DRAFT_BLOCK``, and what a lane carries between ticks
    beside the cursors: ``cur`` the last token it emitted and ``dr``
    the token drafted to follow it, int32 a slot. One
    cursor a slot serves all: which ring entries and which latent rows
    are live follows from it alone, and a state needs none. A gated
    convolution keeps its ``conv`` tail alone, ``(slots, kernel - 1,
    channels)``. ``state``, ``ssm``, ``conv``, ``ckv``, ``kr`` and
    ``ik`` are there only where some layer has them; a block without a
    mixer keeps nothing. Heads narrower than a row of 128 lanes lie
    several to a row (:func:`kv_pack`): ``(slots, max_len or W, nkv /
    pack, pack * hd)``."""
    plan = plan_of(cfg)
    out: dict = {"k": {}, "v": {},
                 "pos": jnp.zeros((n_slots,), jnp.int32)}
    if plan.draft is not None:
        out["cur"] = jnp.zeros((n_slots,), jnp.int32)
        out["dr"] = jnp.zeros((n_slots,), jnp.int32)
    for _, name, a in _mixers(plan):
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
        if isinstance(a, ConvKind):
            out.setdefault("conv", {})[name] = jnp.zeros(
                (n_slots, a.conv - 1, a.channels), cfg.dtype)
            continue
        if isinstance(a, MlaKind):
            for key, width in zip(a.rows, (a.kv_rank, a.rope_dim,
                                           a.index_dim)):
                out.setdefault(key, {})[name] = jnp.zeros(
                    (n_slots, max_len, width), cfg.dtype)
            continue
        pack = kv_pack(cfg.n_kv_heads, cfg.head_dim)
        for kv in ("k", "v"):
            out[kv][name] = jnp.zeros(
                (n_slots, min(a.window, max_len) if a.window else max_len,
                 cfg.n_kv_heads // pack, pack * cfg.head_dim), cfg.dtype)
    return out


#: A layer kind that every token is folded into, not positions a
#: cursor can mask: the scope its ops carry, the cache entries that
#: hold what it keeps a slot (a state and the tail of its short
#: convolution, or the tail alone), its decode step and its prompt
#: ingestion, which take and return the entries in that order.
_RECURRENT = {
    KdaKind: ("attn.kda", ("state", "conv"), kda_decode, kda_ingest),
    MambaKind: ("attn.mamba", ("ssm", "conv"), mamba_decode, mamba_ingest),
    Mamba2Kind: ("attn.mamba2", ("ssm", "conv"), mamba2_decode,
                 mamba2_ingest),
    ConvKind: ("attn.conv", ("conv",), conv_decode, conv_ingest)}
#: A layer kind that keeps rows of its own a position, not keys and
#: values a head (the cache entries that hold them are the kind's own
#: ``rows``): the scope its ops carry, its decode step and its prompt
#: ingestion.
_LATENT = {MlaKind: ("attn.mla", mla_decode, mla_ingest)}
#: What a drafting plan's cache carries a slot beside its cursor.
_LANE_STATE = ("pos", "cur", "dr")


def _plan_forward(cfg: TransformerConfig, params: dict, tokens: jax.Array,
                  cache: dict, row_pos: jax.Array, valid: jax.Array,
                  slot=None, live=(), hidden: bool = False):
    """The planned stack over (B, S) tokens, layer by layer (a layer's
    kinds are static, so each layer is its own code over its own
    parameters and its own cache). A block that is a mixer alone or an
    MLP alone runs its one norm and its one half, and adds once.

    ``slot`` None is the decode tick: S == 1, row b at position
    ``row_pos[b]`` (or, where every mixer is latent attention without
    an indexer, ``plan.takes_window``, a verify window of S positions
    a lane, row b's query s at ``row_pos[b] + s``, each seeing the rows
    the ones before it wrote); each layer writes its one new position (full: at
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

    ``live``: the layers whose attention streams live blocks: a decode
    tick's over a lane's (:func:`live_layers`' answer for this program
    and cache), a prompt's latent layers' over a block of queries'
    (:func:`live_ingest`'s for this program and rung).

    ``valid`` (B, S) marks real tokens: the expert layers route nothing
    else, and no state folds anything else in. Returns (logits fp32:
    (B, 1, V), or (V,) at the prompt's last position; the cache's new
    entries, every key of it but ``pos``; ``route``: int32 [tokens
    routed, assignments to held experts, to absent experts, held
    experts touched (both summed over expert layers), largest load of
    one expert], None for a stack without experts). Donated, the cache
    is updated in place.

    ``hidden`` (a plan that drafts for itself asks): a fourth result,
    the stack's output after its final norm at every position, (B, S,
    d), which the drafting block reads (:func:`_draft_forward`)."""
    plan = plan_of(cfg)
    B, S = tokens.shape
    dt, hd, nkv = cfg.dtype, cfg.head_dim, cfg.n_kv_heads
    decode = slot is None
    if decode and S != 1 and not plan.takes_window:
        raise NotImplementedError(
            "this planned stack decodes one position a tick: a verify "
            "window of several is written for a stack whose mixers are "
            "all latent attention without an indexer (a ring takes one "
            "position a tick, a recurrent state folds every token in, "
            "an indexer chooses for one query a lane)")
    new = {key: dict(entries) for key, entries in cache.items()
           if key not in _LANE_STATE}
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
                scope, keys, step, ingest = _RECURRENT[type(a)]
                with jax.named_scope(scope):
                    if decode:
                        y, *kept = step(
                            a, ap, h, *(new[key][name] for key in keys),
                            valid[:, 0], cfg.norm_eps, dt)
                    else:
                        y, *fresh = ingest(a, ap, h, valid, cfg.norm_eps, dt)
                        kept = [jax.lax.dynamic_update_slice(
                            new[key][name], entry,
                            (slot,) + (0,) * (entry.ndim - 1))
                            for key, entry in zip(keys, fresh)]
                    for key, entry in zip(keys, kept):
                        new[key][name] = entry
                x = x + y
            elif type(a) in _LATENT:
                x = x + _latent_layer(a, ap, h, new, name, row_pos, valid,
                                      abs_pos, tables, slot, cfg.norm_eps,
                                      dt, layer in live)
            else:
                x = x + _softmax_layer(
                    a, ap, h, ks, vs, name, row_pos, valid, abs_pos, tables,
                    slot, nkv, hd, cfg.norm_eps, dt, layer in live)
        if m is None:
            continue

        y, counts = _mlp_half(m, block["mlp"], x, flat_valid, counts,
                              forms, cfg.norm_eps, dt)
        x = x + y

    # One record a form of this program's expert products, as it is
    # traced: ``experts.every | grouped-kernel | ragged_dot``, size the
    # rows one product is over (docs/TRACING.md, ``HOST_PHASE``).
    for form, rows in sorted(forms):
        with host_phase(f"experts.{form}", rows):
            pass
    if hidden:
        # normed at every position, for the drafting block; the logits
        # of a prompt are still its last position's alone
        out = x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if not decode:
        x = jax.lax.dynamic_index_in_dim(
            x[0], jnp.maximum(valid.sum() - 1, 0), 0, keepdims=False)
    if not hidden:
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(cfg, params, x)
    route = jnp.concatenate(
        [valid.sum().astype(jnp.int32)[None], counts]) \
        if plan.routed else None
    return (logits, new, route, out) if hidden else (logits, new, route)


def _head_logits(cfg: TransformerConfig, params: dict, x: jax.Array):
    """The head's float32 logits of normed rows x (..., d)."""
    dt = cfg.dtype
    if cfg.tie_embeddings:
        # the embedding read where it lies, rows against rows: no
        # (d, vocab) copy of it is made
        logits = jnp.einsum("...d,vd->...v", x, wload(params["embed"], dt))
    else:
        logits = x @ wload(params["head"], dt)
    return logits.astype(jnp.float32)


def _mlp_half(m, mp: dict, x: jax.Array, flat_valid: jax.Array, counts,
              forms: set, eps: float, dt):
    """A block's MLP half on the stream x (B, S, d): its norm, then the
    dense MLP or the held experts (with the shared one where the kind
    has it) over the rows ``flat_valid`` (B * S,) marks. Returns (what
    it adds to the stream, ``counts`` with this layer's folded in:
    :func:`_plan_forward`'s ``route`` less its first entry); the form
    an expert layer's products take is added to ``forms``."""
    from pbs_tpu.models.moe import (
        expert_form, expert_piece, held_expert_ffn, mlp_ffn,
        shared_expert_ffn)

    B, S, _ = x.shape
    h = rms_norm(x, mp["mlp_norm"], eps)
    if not m.n_experts:
        with jax.named_scope("mlp.dense"):
            return mlp_ffn(h, mp["w1"], mp.get("w3"), mp["w2"], m.form,
                           lambda rows, w: rows @ wload(w, dt)), counts
    hf = h.reshape(B * S, -1)
    forms.add(expert_form(expert_piece(B * S), hf.shape[1], m, dt))
    y, c = held_expert_ffn(hf, mp, m, flat_valid, dt)
    if m.shared_d_ff:
        y = y + shared_expert_ffn(hf, mp, dt, m.form)
    return y.reshape(B, S, -1), jnp.concatenate(
        [counts[:3] + c[:3], jnp.maximum(counts[3:], c[3:])])


def _draft_forward(cfg: TransformerConfig, params: dict, hidden: jax.Array,
                   tokens: jax.Array, new: dict, row_pos: jax.Array,
                   valid: jax.Array, slot=None, live: bool = False):
    """The plan's drafting block (``LayerPlan.draft``; DeepSeek-V3's
    multi-token-prediction module, depth one) over (B, S) pairs: row
    (b, s) is the stack's normed output ``hidden`` (B, S, d) at
    position ``row_pos[b] + s`` beside ``tokens`` (B, S), the token
    that *follows* that position:

        u = [rmsnorm_e(embed(token)) ; rmsnorm_h(hidden)] W_eh
        y = block(u)    (the plan's own mixer and MLP kinds, the mixer's
                         rows under ``plan.DRAFT_BLOCK`` in the cache,
                         row i at rotary position i, one cursor a slot)
        draft logits = head(rmsnorm_s(y))

    which predict the token after ``tokens``. ``slot`` None: a tick's
    window at each lane's cursor; else one prompt's pairs from position
    0 into that slot. ``valid`` (B, S) marks the pairs that are real
    (the others change no row and route nowhere). ``new`` is
    :func:`_plan_forward`'s cache dict, whose entries are replaced.
    Returns (float32 logits (B, S, V), ``counts``: the block's expert
    layer's, as :func:`_mlp_half` folds them from zero). The form its
    expert products take is the stack's own at these rows, and is not
    said again. Everything under the scope ``mtp.draft``."""
    plan = plan_of(cfg)
    a, m = plan.draft_kinds
    B, S = tokens.shape
    dt, eps = cfg.dtype, cfg.norm_eps
    block = params["blocks"][DRAFT_BLOCK]
    with jax.named_scope("mtp.draft"):
        T = max(cfg.max_seq, new["ckv"][DRAFT_BLOCK].shape[1])
        abs_pos = jnp.minimum(
            row_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :], T - 1)
        tables = {a.rope: rope_table(a.rope, cfg.head_dim, T)}
        u = jnp.concatenate(
            [rms_norm(embed_rows(params["embed"], tokens, dt),
                      block["enorm"], eps),
             rms_norm(hidden, block["hnorm"], eps)], axis=-1) \
            @ wload(block["eh_proj"], dt)
        ap = block["attn"]
        x = u + _latent_layer(
            a, ap, rms_norm(u, ap["attn_norm"], eps), new, DRAFT_BLOCK,
            row_pos, valid, abs_pos, tables, slot, eps, dt, live)
        y, counts = _mlp_half(m, block["mlp"], x, valid.reshape(-1),
                              jnp.zeros((4,), jnp.int32), set(), eps, dt)
        x = rms_norm(x + y, block["head_norm"], eps)
        return _head_logits(cfg, params, x), counts


def _softmax_layer(a, ap: dict, h: jax.Array, ks: dict, vs: dict, name: str,
                   row_pos, valid, abs_pos, tables: dict, slot, nkv: int,
                   hd: int, eps: float, dt, live: bool = False) -> jax.Array:
    """A full or window attention layer of the planned stack on its
    normed input h (B, S, d): writes the layer's new keys and values
    into ``ks[name]`` / ``vs[name]`` (replaced in the dicts) and
    returns what the layer adds to the stream. ``live``: a decode tick
    whose attention goes through :func:`_cursor_attention`.

    Where the cache lies packed (:func:`kv_pack`: the entries' last
    axis holds ``pack`` heads), a tick's queries go against the rows as
    they lie, each head zero but for the part its KV head has of a row
    (:func:`_to_packed`: the same scores and, off its own part of the
    output, the same values as a head at a time), so no view of the
    cache a head is ever made; a prompt attends its own keys and values
    a head, and they are written as the rows."""
    B, S, _ = h.shape
    H, decode = a.n_heads, slot is None
    q = (h @ wload(ap["wq"], dt)).reshape(B, S, H, hd)
    k = (h @ wload(ap["wk"], dt)).reshape(B, S, nkv, hd)
    v = (h @ wload(ap["wv"], dt)).reshape(B, S, nkv, hd)
    if a.qk_norm:
        q = rms_norm(q, ap["q_norm"], eps)
        k = rms_norm(k, ap["k_norm"], eps)
    if a.rope is not None:
        cos, sin = (t[abs_pos] for t in tables[a.rope])
        q, k = _rope_leading(q, cos, sin), _rope_leading(k, cos, sin)
    K, rows = ks[name].shape[1], ks[name].shape[2:]
    pack = rows[1] // hd
    with jax.named_scope("attn.window" if a.window else "attn.full"):
        if decode:
            at = row_pos % K if a.window else row_pos
            ks[name] = _write_rows(ks[name], k.reshape((B, S) + rows), at)
            vs[name] = _write_rows(vs[name], v.reshape((B, S) + rows), at)
            # the scores' scale is the head's, whatever a row holds
            width = hd if pack > 1 else None
            if pack > 1:
                q = _to_packed(q, nkv, pack)
            if live:
                # an idle lane's cursor rests where its last request
                # ended, and nothing up to there is its to read: it
                # attends its first entry
                attn = _cursor_attention(
                    q, ks[name], vs[name],
                    jnp.where(valid[:, 0], row_pos, 0), None, dt, width)
            else:
                col = jnp.arange(K)[None, :]
                # Ring entry j holds the largest p <= cursor with
                # p = j mod W: live once written, always after a lap.
                seen = (col <= row_pos[:, None]) | (
                    (row_pos[:, None] >= K) if a.window else False)
                attn = _grouped_attention(q, ks[name], vs[name],
                                          seen[:, None, :], dt, width)
            if pack > 1:
                attn = _from_packed(attn, nkv, pack)
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
                ks[name], k.reshape(k.shape[:2] + rows), (slot, 0, 0, 0))
            vs[name] = jax.lax.dynamic_update_slice(
                vs[name], v.reshape(v.shape[:2] + rows), (slot, 0, 0, 0))
        if a.gate == "per_head":
            attn = attn * jax.nn.sigmoid(h @ wload(ap["wg"], dt))[..., None]
        elif a.gate == "elementwise":
            attn = attn * jax.nn.sigmoid(
                h @ wload(ap["wg"], dt)).reshape(B, S, H, hd)
    return attn.reshape(B, S, H * hd) @ wload(ap["wo"], dt)


def _latent_layer(a, ap: dict, h: jax.Array, new: dict, name: str,
                  row_pos, valid, abs_pos, tables: dict, slot, eps: float,
                  dt, live: bool = False) -> jax.Array:
    """A latent layer of the planned stack on its normed input h (B, S,
    d): writes the rows the layer keeps a position into its entries of
    ``new`` (replaced in the dicts) and returns what the layer adds to
    the stream. Padding and idle lanes change no row. ``live``: the
    layer's attention streams live blocks (a decode tick's the lane's
    live rows, a prompt's the key blocks a block of queries sees)."""
    scope, step, ingest = _LATENT[type(a)]
    keys = a.rows
    cos, sin = (t[abs_pos] for t in tables[a.rope])
    rows = [new[key][name] for key in keys]
    with jax.named_scope(scope):
        if slot is None:
            # a tick's one position a lane, or a verify window's rows
            active = valid[:, 0] if valid.shape[1] == 1 else valid
            out, *rows = step(a, ap, h, rows, row_pos, active, cos, sin,
                              eps, dt, live)
        else:
            out, *prompt = ingest(a, ap, h, valid, cos, sin, eps, dt, live)
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


class _LiveLayers:
    """What both forms of a program know of their decode's attention:
    where the cache lies, and from that and a cache's shapes the layers
    that stream live blocks (:func:`live_layers`)."""

    def __init__(self, cfg: TransformerConfig, mesh=None):
        self.cfg = cfg
        #: where the cache lies (the mesh's devices, or the default one)
        self.devices = _placed_on(mesh)

    def live_layers(self, cache: dict, lowered: bool = False) -> dict:
        """:func:`live_layers` of this program over ``cache``: as a
        decode is traced, or with ``lowered`` as it runs (the records'
        question)."""
        return live_layers(plan_of(self.cfg), cache, self.devices, lowered)

    def live_ingest(self, rung: int, lowered: bool = False) -> frozenset:
        """:func:`live_ingest` of this program at a prompt of ``rung``
        rows: as a prefill is traced, or with ``lowered`` as it
        runs."""
        return live_ingest(plan_of(self.cfg), rung, self.devices, lowered)


class _ScanProgram(_LiveLayers):
    """Every layer alike (any dense configuration, and the ``mlp_fn``
    fixture): the layer ``lax.scan`` of ``_slot_forward`` over one
    ``(L, slots, max_len, nkv, hd)`` cache. Routes nothing."""

    #: a window of positions can be cut from, installed into and
    #: verified over every layer's cache (prefix cache, speculation)
    windows = True
    #: no layer chooses among the positions it keeps, none keeps
    #: latent rows, and no drafting block rides behind the stack
    select_topk = None
    latent = False
    drafts = False

    def __init__(self, cfg: TransformerConfig, mlp_fn=None, mesh=None):
        super().__init__(cfg, mesh)
        self.mlp_fn = mlp_fn

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

    def cut_window(self, cache: dict, slot: int, rows: int) -> dict:
        """The first ``rows`` positions of ``slot`` in every layer,
        ``k`` and ``v`` (L, 1, rows, nkv, hd): device arrays of their
        own (lazy slices, no host sync), sharded as the cache is."""
        return {kv: cache[kv][:, slot:slot + 1, :rows] for kv in ("k", "v")}

    def install_window(self, cache: dict, slot, kwin, vwin, plen,
                       mesh=None) -> dict:
        """A cut window written into ``slot`` and the slot's cursor set
        to ``plen`` (trace-safe; in place where the caller's jit donates
        ``cache``). Under a tp mesh the constraint pins the updated
        slabs back to the canonical kv-head sharding (the window
        arrives sharded the same way: a no-op reshard in the common
        case, a guard against layout drift always)."""
        k = jax.lax.dynamic_update_slice(
            cache["k"], kwin, (0, slot, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], vwin, (0, slot, 0, 0, 0))
        if mesh is not None:
            kv = slot_cache_kv_sharding(mesh)
            k = jax.lax.with_sharding_constraint(k, kv)
            v = jax.lax.with_sharding_constraint(v, kv)
        return {"k": k, "v": v, "pos": cache["pos"].at[slot].set(plen)}

    def decode(self, params, cache, last_tok, active):
        _say_attention(self.live_layers(cache, lowered=True),
                       self.cfg.n_layers)
        # every layer alike: all of them stream, or none
        live = self.live_layers(cache)
        logits, new, extra = _slot_forward(
            self.cfg, params, last_tok[:, None], cache, cache["pos"],
            mlp_fn=self.mlp_fn, active=active if live else None)
        return logits, new, extra, None

    def ingest(self, params, cache, slot, prompt, plen):
        return ingest_slot_prompt(self.cfg, params, cache, slot, prompt,
                                  plen, mlp_fn=self.mlp_fn) + (None,)


class _PlannedProgram(_LiveLayers):
    """Layers that differ (``cfg.layer_plan``): every kind of per-slot
    state in one manager (positions, a ring of them, a recurrent
    state), the grouped expert layer, ``route`` counters."""

    #: neither a ring nor a state hands out or takes in a window of
    #: positions (``no_windows`` says which, for the error)
    windows = False

    def __init__(self, cfg: TransformerConfig, mesh=None):
        super().__init__(cfg, mesh)
        plan = plan_of(cfg)
        #: the most positions a selecting layer's query attends (the
        #: engine's ``ENG_SELECT`` counts by it); None: no such layer
        self.select_topk = plan.select_topk
        #: some layer keeps latent rows a position (``ENG_SELECT`` is
        #: written for such a program whether it chooses or not)
        self.latent = plan.latent
        #: the plan has a drafting block: a tick verifies two positions
        #: a lane and advances it by one token or two
        #: (:meth:`draft_tick`), and the lanes' last tokens and drafts
        #: live in the cache beside the cursors
        self.drafts = plan.draft is not None
        if self.drafts and not (plan.takes_window
                                and plan.draft_kinds[1] is not None):
            raise NotImplementedError(
                "a plan drafts for itself where every mixer, its "
                "drafting block's too, is latent attention without an "
                "indexer (a verify window of two positions a lane: a "
                "ring takes one position a tick, a recurrent state folds "
                "every token in, an indexer chooses for one query a "
                "lane; ROADMAP R7)")
        self.no_windows = (
            "a matrix-state layer keeps one float32 (head_dim, d_state) "
            "state a head and a convolution tail a slot, not positions, "
            "and every token of a prompt is folded into it: a prefix hit, "
            "a preemption or a verify window would need a snapshot of "
            "that state (megabytes a layer) at the window's end (ROADMAP "
            "R23)"
            if any(isinstance(a, Mamba2Kind) for a in plan.attn) else
            "a gated convolution keeps the last rows of its gated input "
            "a slot (two of them at three taps), not positions, and every "
            "token of a prompt is shifted through them: a prefix hit, a "
            "preemption or a verify window would need that tail as it "
            "stood at the window's end (ROADMAP R23)"
            if any(isinstance(a, ConvKind) for a in plan.attn) else
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
            "a latent layer keeps a latent row and a rotary key a "
            "position, not keys and values a head: a window is cut from "
            "and installed into k and v alone (a decode's verify window "
            "over latent rows is written, ``draft_tick``; a prompt "
            "window's cut and install are not: ROADMAP R5)"
            if plan.latent else
            "a window layer's ring takes one position a tick, and "
            "cutting a window from it or installing one is not written "
            "(ROADMAP R4)")

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
        _say_attention(self.live_layers(cache, lowered=True),
                       len(cache["k"]))
        logits, new, route = _plan_forward(
            self.cfg, params, last_tok[:, None], cache, cache["pos"],
            active[:, None], live=self.live_layers(cache))
        return (logits, dict(new, **{key: cache[key] for key in _LANE_STATE
                                     if key in cache}),
                jnp.zeros((), jnp.float32), route)

    def ingest(self, params, cache, slot, prompt, plen):
        if self.drafts:
            last_logits, cache, route, _ = self.ingest_drafts(
                params, cache, slot, prompt, plen)
            return last_logits, cache, jnp.zeros((), jnp.float32), route
        valid = (jnp.arange(prompt.shape[0]) < plen)[None, :]
        last_logits, new, route = _plan_forward(
            self.cfg, params, prompt[None, :], cache,
            jnp.zeros((1,), jnp.int32), valid, slot=slot,
            live=self.live_ingest(prompt.shape[0]))
        cache = dict(new, pos=cache["pos"].at[slot].set(plen))
        return last_logits, cache, jnp.zeros((), jnp.float32), route

    def ingest_drafts(self, params, cache, slot, prompt, plen):
        """The ingestion of one prompt by a plan that drafts: the
        stack, the request's first token from its last position
        (greedy), then the drafting block over the pairs ``(h_i,
        t_{i+1})``, the prompt shifted by one with the first token
        last; its rows stay in the slot beside the stack's and its last
        row gives the first draft. The slot's cursor, first token and
        draft are set in the cache. Returns (the stack's logits at the
        last position, the cache, ``route`` with the drafting block's
        expert layer counted in, the drafting block's logits at every
        row of the prompt (S, V))."""
        valid = (jnp.arange(prompt.shape[0]) < plen)[None, :]
        live = self.live_ingest(prompt.shape[0])
        zero = jnp.zeros((1,), jnp.int32)
        last_logits, new, route, hidden = _plan_forward(
            self.cfg, params, prompt[None, :], cache, zero, valid,
            slot=slot, live=live, hidden=True)
        first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        last = jnp.maximum(plen - 1, 0)
        follows = jnp.roll(prompt, -1).at[last].set(first)
        draft_logits, counts = _draft_forward(
            self.cfg, params, hidden, follows[None, :], new, zero, valid,
            slot=slot, live=len(plan_of(self.cfg).layers) in live)
        draft = jnp.argmax(jax.lax.dynamic_index_in_dim(
            draft_logits[0], last, 0, keepdims=False), -1).astype(jnp.int32)
        cache = dict(new, pos=cache["pos"].at[slot].set(plen),
                     cur=cache["cur"].at[slot].set(first),
                     dr=cache["dr"].at[slot].set(draft))
        return (last_logits, cache, _route_with(route, counts),
                draft_logits[0])

    def draft_tick(self, params, cache, active):
        """One tick of a plan that drafts for itself, greedy, every
        shape static. A lane carries its cursor ``p`` (rows ``0..p-1``
        valid in every layer and in the drafting block alike), its last
        emitted token ``cur = t_p`` and its draft ``dr`` for
        ``t_{p+1}``, all in ``cache``; ``active`` (B,) the lanes that
        hold a request.

        1. verify: the stack over ``[cur, dr]`` at ``p, p + 1`` (both
           rows written in every layer, the second query seeing the
           first's row): logits ``g0, g1``, normed outputs ``h_p,
           h_{p+1}``;
        2. accept (``mtp.verify``): ``a = argmax g0 == dr``; the lane
           emits ``t_{p+1} = argmax g0`` and, where ``a``, ``t_{p+2} =
           argmax g1``; its cursor moves to ``p + 1 + a`` (a rejected
           row ``p + 1`` is not counted, and the next tick writes over
           it);
        3. draft (``mtp.draft``): the drafting block over the pairs
           ``(h_p, t_{p+1})`` at row ``p`` and ``(h_{p+1}, t_{p+2})``
           at row ``p + 1`` (the second out of its cache and its
           experts where not accepted); the new ``dr`` is its argmax
           at row ``p + a``.

        Returns (``toks`` (B, 2) int32: what each lane emits, -1 where
        it emits nothing: a lane that is not active, and the second of
        a lane that did not accept; the cache, lane state advanced;
        ``route`` as :func:`_plan_forward`'s with the drafting block's
        expert layer counted in; the window's logits (B, 2, V) and the
        drafting block's (B, 2, V), float32: what the tests hold to
        the reference, and a caller that drops them pays nothing)."""
        cfg, pos = self.cfg, cache["pos"]
        live = self.live_layers(cache)
        _say_attention(self.live_layers(cache, lowered=True),
                       len(cache["k"]))
        cur, dr = cache["cur"], cache["dr"]
        window = active[:, None] & jnp.ones((1, 2), bool)
        logits, new, route, hidden = _plan_forward(
            cfg, params, jnp.stack([cur, dr], axis=1), cache, pos, window,
            live=live, hidden=True)
        with jax.named_scope("mtp.verify"):
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # (B, 2)
            toks, took, last = greedy_accept_window(dr[:, None], g)
            took = jnp.where(active, took, 0)
            emitted = jnp.arange(2)[None, :] <= took[:, None]
            toks = jnp.where(active[:, None] & emitted, toks, -1)
        # the pairs' tokens are the window's own argmaxes: g0 = t_{p+1}
        # always, g1 = t_{p+2} where the draft was accepted
        draft_logits, counts = _draft_forward(
            cfg, params, hidden, g, new, pos,
            active[:, None] & emitted, live=len(plan_of(cfg).layers) in live)
        with jax.named_scope("mtp.verify"):
            drafted = jnp.argmax(draft_logits, axis=-1).astype(jnp.int32)
            draft = jnp.take_along_axis(drafted, took[:, None], axis=1)[:, 0]
            cache = dict(
                new, pos=pos + jnp.where(active, took + 1, 0),
                cur=jnp.where(active, last, cur),
                dr=jnp.where(active, draft, dr))
        return (toks, cache, _route_with(route, counts), logits,
                draft_logits)


def _route_with(route, counts):
    """A stack's ``route`` with the drafting block's expert layer's
    ``counts`` folded in: assignments and experts touched added, the
    largest load the larger (the tokens routed stay the stack's)."""
    if route is None:
        return None
    return jnp.concatenate([route[:1], route[1:4] + counts[:3],
                            jnp.maximum(route[4:], counts[3:])])


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
