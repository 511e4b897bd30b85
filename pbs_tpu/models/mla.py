"""Latent attention (multi-head latent attention, arXiv:2405.04434),
with or without a learned indexer that selects its positions (DeepSeek
sparse attention as DeepSeek-V3.2-Exp's ``Indexer`` writes it) for the
slot engine: what a ``models.plan.MlaKind`` layer of a planned stack
computes. What follows is the selecting kind; **a kind without an
indexer** (DeepSeek-V3's; ``MlaKind.selects`` false) has no ``wi_*``
leaves, keeps no ``ik`` row, scores and chooses nothing and attends
every position up to its own, its scores scaled by ``MlaKind.scale``
(YaRN's ``mscale`` squared in it), and its decode takes **a window of
queries a lane** (the verify window of a plan that drafts for itself:
query ``s`` at the cursor plus ``s``, each seeing the rows the earlier
ones wrote; ``ops/mla_attend.py::mla_attend_window``).

A position is kept as three rows, nothing a head: the RMS-normed latent
``ckv`` (``kv_rank``), one rotary key ``kr`` (``rope_dim``) that every
head shares, and the indexer's key ``ik`` (``index_dim``). On the normed
input ``h`` of position ``t``::

    c_q = rmsnorm(h W_qa);   [q_n | q_r] = c_q W_qb a head;  q_r turned at t
    [c_kv | k_r] = h W_kva;  c_kv <- rmsnorm(c_kv);          k_r turned at t
    [k_n | v] = c_kv W_kvb a head
    q^I = c_q W^I_q a head;  k^I = layernorm(h W^I_k);  both turned at t
    w = h W^I_w (index_heads * index_dim)^-1/2
    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])           s <= t
    S_t = the topk positions s <= t of largest I[t, s] (all, up to topk)
    o = softmax over S_t of (q_n . k_n[s] + q_r . k_r[s]) / sqrt(qk) @ v

Two programs of the engine (``models/slot_programs.py``), in the shape of
``models/kda.py``. :func:`mla_ingest` runs a whole prompt in the
per-head form (keys and values read off the latent rows once, for the
prompt, heads-major), a block of queries at a time: the block's indexer
scores, its choice, its attention under the choice. That attention is,
on a TPU, **one pass over the key blocks the block of queries can see**
(``ops/mla_ingest_attend.py``: a head's keys and values a block in VMEM
at a time, float32 scores, mask, running maximum and sum and the
values' product on the chip, no key block past the query block's own
end fetched), and anywhere else :func:`_attend_chunks`, the same
arithmetic in ``jax.numpy`` a chunk of keys at a time: by the platform
the program is lowered for, where the program says the layer streams
(``slot_programs.live_ingest``: head dims, the rung and where the cache
lies), nothing a user sets. :func:`mla_decode` is one
position a lane in the absorbed form: the query is carried into the
latent space (``q_n W_kvb^T``), scores and values are read off the
``kv_rank + rope_dim`` wide rows, which are therefore read once for all
heads, and the result leaves through ``W_kvb``'s value half.

The choice is a mask, not a gather: the ``topk``-th largest score of a
row is found by bisection over the float's bits (:func:`top_mask`: 32
counting passes, exact), and attention runs under the mask. The set
attended is exactly ``S_t``; a tie at the ``topk``-th score lets every
tied position in. **Padding and idle lanes are no-ops**: a padded
position and an idle lane leave every cache row as it was (an idle
lane attends its first row alone: its cursor rests where its last
request ended, and nothing up to there is its to read).

The decode's attention is, on a TPU, **one pass over a lane's live
rows** (``ops/mla_attend.py``: a block of positions in VMEM at a time,
scores, mask, softmax and values on the chip, the blocks past the
lane's cursor never fetched), and anywhere else :func:`attend_rows`,
the same arithmetic in ``jax.numpy`` over every kept row: by the
platform the program is lowered for, where the program says the layer
streams (``slot_programs.live_layers``: the caches' shapes and where
they lie), nothing a user sets. Why the mask stays and the
chosen rows are not gathered at this cache length: a query sees ~6,600
live rows of GLM-5's 10,240 and chooses 2,048, a third of what the
stream reads at 710-750 GB/s; single rows of 1 KiB, and of 128 B out
of a cache XLA:TPU keeps positions-minor, would have to come at more
than a third of that, after a sort to turn the mask into indices of a
fixed width that the ties do not have (``docs/SERVING.md``; PERF.md
section 6, PR 42).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.models.plan import MlaKind
from pbs_tpu.models.quant import wload
from pbs_tpu.models.transformer import rms_norm
from pbs_tpu.ops.mla_attend import mla_attend, mla_attend_window
from pbs_tpu.ops.mla_ingest_attend import (
    ingest_attend, ingest_attend_tiles, ingest_block)

__all__ = ["MLA_BLOCK", "MLA_KEYS", "MLA_SPANS", "attend_rows",
           "decode_choice", "ingest_pairs", "ingest_tiles", "mla_decode",
           "mla_ingest", "top_mask", "window_rows"]

#: Queries a block of the ingestion scores, chooses for and attends at
#: a time: the indexer's live tensors are ``(index_heads, MLA_BLOCK,
#: keys)``, never ``(heads, prompt, prompt)``, and the kernel's query
#: tile is a block (a head's ``(MLA_BLOCK, qk)`` whole in VMEM).
MLA_BLOCK = 256
#: Equal spans the prompt's queries are cut into, each against the keys
#: up to its own end, each span one loop over its blocks: what bounds
#: the tensors of ``mla.index`` and ``mla.select`` (a block's scores
#: and choice over a span's keys, not the prompt's) and the kernel's
#: grid (a span's key blocks; of them a block of queries runs those up
#: to its own). The ``jax.numpy`` attention runs the span whole: the
#: causal triangle in ``MLA_SPANS`` steps (at 4, five eighths of the
#: square).
MLA_SPANS = 4
#: Keys a chunk of the ``jax.numpy`` attention (:func:`_attend_chunks`:
#: a CPU's and a mesh's lowering, the kernel's oracle) holds: its live
#: float32 scores are ``(heads, MLA_BLOCK, MLA_KEYS)``, 128 MiB at 64
#: heads. Formed over a whole span of 6,144 or 8,192 keys at once,
#: XLA:TPU's max-and-subtract fusion took 27 and 47 ms a block where
#: 4,096 keys took 1.2 (PERF.md section 6, PR 41). The kernel's key
#: block is ``ops/mla_ingest_attend.py``'s own.
MLA_KEYS = 2048
_F32 = jnp.float32


def _turn(x: jax.Array, cos: jax.Array, sin: jax.Array,
          interleave: bool) -> jax.Array:
    """Rotary on the leading ``2 * cos.shape[-1]`` dims of x's last
    axis, the rest passed through: x (B, S, ..., D), cos and sin (B, S,
    half). ``interleave`` turns adjacent dims ``(2i, 2i + 1)`` together,
    else dim i with dim i + half."""
    half = cos.shape[-1]
    mid = (1,) * (x.ndim - 3)
    c = cos.reshape(cos.shape[:2] + mid + (half,)).astype(x.dtype)
    s = sin.reshape(sin.shape[:2] + mid + (half,)).astype(x.dtype)
    rot, rest = x[..., :2 * half], x[..., 2 * half:]
    if interleave:
        pairs = rot.reshape(rot.shape[:-1] + (half, 2))
        x0, x1 = pairs[..., 0], pairs[..., 1]
        rot = jnp.stack([x0 * c - x1 * s, x1 * c + x0 * s],
                        axis=-1).reshape(rot.shape)
    else:
        x0, x1 = rot[..., :half], rot[..., half:]
        rot = jnp.concatenate([x0 * c - x1 * s, x1 * c + x0 * s], axis=-1)
    return jnp.concatenate([rot, rest], axis=-1) if rest.shape[-1] else rot


def top_mask(scores: jax.Array, k: int) -> jax.Array:
    """The ``k`` largest of each row of float32 ``scores`` (..., N) as
    a mask; every entry where ``N <= k``. Exact: the k-th largest value
    is built bit by bit from the top (a float's bits, sign folded, order
    as the floats do), each bit one count of the entries at or above
    the candidate; entries equal to it are all in."""
    if scores.shape[-1] <= k:
        return jnp.ones(scores.shape, bool)
    bits = jax.lax.bitcast_convert_type(scores.astype(_F32), jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, floor):
        cand = floor | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, floor)

    floor = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    return key >= floor


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float):
    xf = x.astype(_F32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(_F32) + b.astype(_F32)).astype(x.dtype)


def _rows(a: MlaKind, ap: dict, h: jax.Array, cos, sin, eps: float, dt):
    """What a position gives, from its normed input h (B, S, d): the
    query's no-rotary and turned parts ``q_n`` (B, S, H, nope), ``q_r``
    (B, S, H, rope), the rows the cache keeps ``ckv`` (B, S, kv_rank),
    ``kr`` (B, S, rope), ``ik`` (B, S, index_dim), and the indexer's
    query ``qi`` (B, S, index_heads, index_dim) with its head weights
    ``w`` (B, S, index_heads) float32; the last three None for a kind
    without an indexer."""
    B, S, _ = h.shape
    il = a.rope.interleave
    cq = rms_norm(h @ wload(ap["wq_a"], dt), ap["q_norm"], eps)
    q = (cq @ wload(ap["wq_b"], dt)).reshape(
        B, S, a.n_heads, a.nope_dim + a.rope_dim)
    q_n, q_r = q[..., :a.nope_dim], _turn(q[..., a.nope_dim:], cos, sin, il)
    kv = h @ wload(ap["wkv_a"], dt)
    ckv = rms_norm(kv[..., :a.kv_rank], ap["kv_norm"], eps)
    kr = _turn(kv[..., a.kv_rank:], cos, sin, il)
    if not a.selects:
        return q_n, q_r, ckv, kr, None, None, None
    qi = _turn((cq @ wload(ap["wi_q"], dt)).reshape(
        B, S, a.index_heads, a.index_dim), cos, sin, il)
    ik = _turn(_layer_norm(h @ wload(ap["wi_k"], dt), ap["ik_norm"],
                           ap["ik_bias"], eps), cos, sin, il)
    w = (h @ wload(ap["wi_w"], dt)).astype(_F32) \
        / np.sqrt(a.index_heads * a.index_dim)
    return q_n, q_r, ckv, kr, ik, qi, w


def _halves(a: MlaKind, ap: dict, dt):
    """``W_kvb`` as a head reads it: the key half (kv_rank, H, nope)
    and the value half (kv_rank, H, v)."""
    wkv = wload(ap["wkv_b"], dt).reshape(
        a.kv_rank, a.n_heads, a.nope_dim + a.v_dim)
    return wkv[..., :a.nope_dim], wkv[..., a.nope_dim:]


def _put(rows: jax.Array, new: jax.Array, at: jax.Array,
         active: jax.Array) -> jax.Array:
    """Lane b's new rows (``new``: (B, S, W), one a tick or a verify
    window's) go to ``rows[b, at[b]:at[b] + S]`` where the lane is
    active (``active`` (B,), or (B, S) a row of the window); an idle
    lane's rows are written back as they were. One
    dynamic_update_slice a lane into the whole cache
    (``slot_programs._write_rows`` says why not a scatter)."""
    S = new.shape[1]
    if active.ndim == 2:
        active = active[:, :, None]

    def one(b, rows):
        old = jax.lax.dynamic_slice(rows, (b, at[b], 0),
                                    (1, S) + rows.shape[2:])
        row = jax.lax.dynamic_slice_in_dim(new, b, 1)
        return jax.lax.dynamic_update_slice(
            rows, jnp.where(active[b], row.astype(rows.dtype), old),
            (b, at[b], 0))

    return jax.lax.fori_loop(0, new.shape[0], one, rows)


def decode_choice(a: MlaKind, qi: jax.Array, w: jax.Array, ik: jax.Array,
                  row_pos: jax.Array) -> jax.Array:
    """The positions each lane's query attends, (B, T) bool: of the
    positions up to its own (``row_pos[b]``, whose key is in ``ik``
    already) the ``topk`` its indexer scores highest. qi (B,
    index_heads, index_dim), w (B, index_heads) float32, ik (B, T,
    index_dim)."""
    live = jnp.arange(ik.shape[1])[None, :] <= row_pos[:, None]
    with jax.named_scope("mla.index"):
        dots = jnp.einsum("bjd,btd->bjt", qi, ik,
                          preferred_element_type=_F32)
        index = jnp.einsum("bjt,bj->bt", jax.nn.relu(dots), w)
    with jax.named_scope("mla.select"):
        return top_mask(jnp.where(live, index, -jnp.inf), a.topk) & live


def mla_decode(a: MlaKind, ap: dict, h: jax.Array, caches,
               row_pos: jax.Array, active: jax.Array, cos: jax.Array,
               sin: jax.Array, eps: float, dt, live: bool = False):
    """One position for every lane, absorbed: h (B, 1, d) at position
    ``row_pos[b]``, ``caches`` the layer's in the order of ``a.rows``,
    ``ckv`` (B, T, kv_rank), ``kr`` (B, T, rope) and under an indexer
    ``ik`` (B, T, index_dim), ``active`` (B,), cos and sin (B, 1, rope
    / 2). An active lane's new rows go to its cursor; an idle lane's
    caches come out as they went in. ``live``: the program's word
    (``slot_programs.live_layers``) that this layer's attention streams
    the lanes' live rows.

    **A kind without an indexer takes a window**: h (B, S, d), query
    ``s`` of lane b at position ``row_pos[b] + s`` (cos and sin (B, S,
    rope / 2)), ``active`` (B, S) the rows of the window that are
    tokens (a lane is busy where its first is); the window's rows are
    written first, so query ``s`` sees what the queries before it
    wrote, and each attends every row up to its own.

    Returns (what the heads give (B, S, H * v), then the caches)."""
    B, S = h.shape[:2]
    if a.selects and S != 1:
        raise NotImplementedError(
            f"attention kind {a.name!r}: an indexer chooses for one "
            f"query a lane a tick, not for a window of {S}")
    q_n, q_r, *new, qi, w = _rows(a, ap, h, cos, sin, eps, dt)
    caches = [_put(rows, fresh, row_pos, active)
              for rows, fresh in zip(caches, new)]
    ckv, kr = caches[:2]
    # an idle lane's cursor rests where its last request ended, and
    # nothing up to there is its to read: it attends its first row
    busy = active if active.ndim == 1 else active[:, 0]
    at = jnp.where(busy, row_pos, 0)
    if not a.selects:
        with jax.named_scope("mla.attend"):
            w_k, w_v = _halves(a, ap, dt)
            q_lat = jnp.einsum("bshn,rhn->bshr", q_n, w_k)
            o_lat = _attend_window(q_lat, q_r, ckv, kr, at, live,
                                   scale=a.scale)
            out = jnp.einsum("bshr,rhv->bshv", o_lat, w_v)
        return (out.reshape(B, S, a.n_heads * a.v_dim), *caches)
    chosen = decode_choice(a, qi[:, 0], w[:, 0], caches[2], at)
    with jax.named_scope("mla.attend"):
        w_k, w_v = _halves(a, ap, dt)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_n[:, 0], w_k)
        o_lat = _attend(q_lat, q_r[:, 0], ckv, kr, chosen, at, live,
                        scale=a.scale)
        out = jnp.einsum("bhr,rhv->bhv", o_lat, w_v)
    return (out.reshape(B, 1, a.n_heads * a.v_dim), *caches)


def attend_rows(q_lat, q_r, ckv, kr, chosen, *, scale: float):
    """What every lane's heads read off its chosen rows, in
    ``jax.numpy`` over every kept row, the others masked: what a CPU
    runs and what :func:`pbs_tpu.ops.mla_attend.mla_attend` is held to.
    ``q_lat`` (B, H, kv_rank), ``q_r`` (B, H, rope), ``ckv`` (B, T,
    kv_rank), ``kr`` (B, T, rope), ``chosen`` (B, T) bool, or (B, H,
    T) where the rows do not share it (a window's queries side by side
    in ``H``: ``mla_attend_window``'s oracle). Returns ``o_lat`` (B, H,
    kv_rank) in ``ckv``'s dtype."""
    scores = (jnp.einsum("bhr,btr->bht", q_lat, ckv,
                         preferred_element_type=_F32)
              + jnp.einsum("bhe,bte->bht", q_r, kr,
                           preferred_element_type=_F32)) * scale
    if chosen.ndim == 2:
        chosen = chosen[:, None, :]
    probs = jax.nn.softmax(jnp.where(
        chosen, scores, jnp.finfo(_F32).min), axis=-1)
    return jnp.einsum("bht,btr->bhr", probs.astype(ckv.dtype), ckv)


# One trace and one lowered function a cache shape, whatever the layers
# (``models/mamba.py::_kernel_scan`` says why).
_kernel_attend = jax.jit(mla_attend, static_argnames=("scale",))


def _attend(q_lat, q_r, ckv, kr, chosen, row_pos, live: bool, *,
            scale: float):
    """The decode's attention: where the layer streams (``live``,
    ``slot_programs.live_layers``' answer: shapes the kernel's tiling
    takes, on one device) by the platform the program is lowered for,
    on a TPU the one-pass kernel and anywhere else :func:`attend_rows`;
    where it does not, :func:`attend_rows`."""
    if not live:
        return attend_rows(q_lat, q_r, ckv, kr, chosen, scale=scale)
    return jax.lax.platform_dependent(
        q_lat, q_r, ckv, kr, chosen, row_pos,
        tpu=functools.partial(_kernel_attend, scale=scale),
        default=lambda *args: attend_rows(*args[:-1], scale=scale))


def window_rows(q_lat, q_r, ckv, kr, row_pos, *, scale: float):
    """:func:`pbs_tpu.ops.mla_attend.mla_attend_window` in
    ``jax.numpy``: :func:`attend_rows` over a window's queries side by
    side, query ``s`` of lane b seeing the rows up to ``row_pos[b] +
    s``. ``q_lat`` (B, S, H, kv_rank), ``q_r`` (B, S, H, rope); returns
    (B, S, H, kv_rank)."""
    B, S, H, R = q_lat.shape
    seen = jnp.arange(ckv.shape[1])[None, None, :] \
        <= (row_pos[:, None] + jnp.arange(S))[:, :, None]       # (B, S, T)
    seen = jnp.broadcast_to(seen[:, :, None, :], (B, S, H, ckv.shape[1]))
    return attend_rows(
        q_lat.reshape(B, S * H, R), q_r.reshape(B, S * H, -1), ckv, kr,
        seen.reshape(B, S * H, -1), scale=scale).reshape(B, S, H, R)


_kernel_window = jax.jit(mla_attend_window, static_argnames=("scale",))


def _attend_window(q_lat, q_r, ckv, kr, row_pos, live: bool, *,
                   scale: float):
    """The decode's attention of a kind without an indexer, as
    :func:`_attend` chooses: the one-pass kernel where the layer
    streams and the program is lowered for a TPU, :func:`window_rows`
    anywhere else."""
    if not live:
        return window_rows(q_lat, q_r, ckv, kr, row_pos, scale=scale)
    return jax.lax.platform_dependent(
        q_lat, q_r, ckv, kr, row_pos,
        tpu=functools.partial(_kernel_window, scale=scale),
        default=functools.partial(window_rows, scale=scale))


def _attend_chunks(q, k, v, seen, scale: float, dt):
    """Softmax attention of a block of queries q (H, Q, qk) over the
    keys ``seen`` (Q, K) marks among the first K of k (H, S, qk) and v
    (H, S, v), all heads-major, ``MLA_KEYS`` keys at a time with a
    running maximum and sum (float32), so that the live scores are
    ``(H, Q, MLA_KEYS)`` whatever K: (H, Q, v) in ``dt``. A query may
    see nothing in a chunk (its indexer chose elsewhere): that chunk
    adds nothing. What a CPU and a mesh run, and what
    :func:`pbs_tpu.ops.mla_ingest_attend.ingest_attend` is held to."""
    H, Q, _ = q.shape
    K = seen.shape[1]
    low = jnp.finfo(_F32).min
    top = jnp.full((H, Q), low, _F32)
    total = jnp.zeros((H, Q), _F32)
    acc = jnp.zeros((H, Q, v.shape[-1]), _F32)
    for k0 in range(0, K, MLA_KEYS):
        k1 = min(k0 + MLA_KEYS, K)
        mask = seen[None, :, k0:k1]
        scores = jnp.einsum("hqd,hkd->hqk", q, k[:, k0:k1],
                            preferred_element_type=_F32) * scale
        peak = jnp.maximum(top, jnp.max(jnp.where(mask, scores, low), -1))
        probs = jnp.where(mask, jnp.exp(scores - peak[..., None]), 0.0)
        keep = jnp.exp(top - peak)
        total = total * keep + jnp.sum(probs, -1)
        acc = acc * keep[..., None] + jnp.einsum(
            "hqk,hkv->hqv", probs.astype(dt), v[:, k0:k1],
            preferred_element_type=_F32)
        top = peak
    return (acc / total[..., None]).astype(dt)


# One trace and one lowered function a span's shapes, whatever the
# layers (``models/mamba.py::_kernel_scan`` says why).
_kernel_ingest = jax.jit(ingest_attend, static_argnames=("scale",))


def _attend_block(q, k, v, seen, first, live: bool, *, scale: float, dt):
    """The ingestion's attention of one block of queries: where the
    layer streams (``live``, ``slot_programs.live_ingest``' answer:
    shapes the kernel's tiling takes, on one device) by the platform
    the program is lowered for, on a TPU the one-pass kernel over the
    key blocks up to the query block's own and anywhere else
    :func:`_attend_chunks`; where it does not, :func:`_attend_chunks`."""
    if not live:
        return _attend_chunks(q, k, v, seen, scale, dt)
    return jax.lax.platform_dependent(
        q, k, v, seen, first,
        tpu=functools.partial(_kernel_ingest, scale=scale),
        default=lambda *args: _attend_chunks(*args[:-1], scale, dt))


def _spans(S: int, block: int) -> list[tuple[int, int]]:
    """(first query, queries) of each span of a prompt of S rows: equal,
    whole blocks, at most ``MLA_SPANS``."""
    n = max(d for d in range(1, MLA_SPANS + 1) if S % (d * block) == 0)
    return [(i * (S // n), S // n) for i in range(n)]


def ingest_tiles(a: MlaKind, S: int) -> bool:
    """Whether the one-pass kernel's tiling takes layer ``a``'s
    ingestion of a prompt of ``S`` rows (a rung): whole blocks of
    queries, head dims whole rows of lanes, every span's keys whole
    key blocks (``ops/mla_ingest_attend.py``)."""
    block = min(MLA_BLOCK, S)
    return S % block == 0 and ingest_attend_tiles(
        block, a.nope_dim + a.rope_dim, a.v_dim, _spans(S, block)[0][1])


def ingest_pairs(S: int, plen: int) -> int:
    """The (query block, key block) pairs the kernel runs in one layer
    for a prompt of ``plen`` tokens at a rung of ``S`` rows: every
    query block that holds a token, against the key blocks up to the
    one its last position lies in (``ENG_SELECT``'s ``blocks`` of a
    prefill)."""
    block = min(MLA_BLOCK, S)
    return sum((first + block - 1) // ingest_block(start + rows) + 1
               for start, rows in _spans(S, block)
               for first in range(start, min(start + rows, plen), block))


def mla_ingest(a: MlaKind, ap: dict, h: jax.Array, valid: jax.Array,
               cos: jax.Array, sin: jax.Array, eps: float, dt,
               live: bool = False):
    """One prompt's pass, per head: h (1, S, d) padded, from position 0,
    ``valid`` (1, S) its real positions. A block of queries past the
    prompt's end is not run. ``live``: the program's word
    (``slot_programs.live_ingest``) that this layer's attention streams
    the key blocks a query block can see. Returns (what the heads give
    (1, S, H * v), and the prompt's rows in the order of ``a.rows``:
    ckv (1, S, kv_rank), kr (1, S, rope) and under an indexer ik (1, S,
    index_dim): the caller keeps the valid ones). A kind without an
    indexer scores and chooses nothing: every block attends what it
    sees."""
    S = h.shape[1]
    H, plen = a.n_heads, valid.sum()
    q_n, q_r, ckv, kr, ik, qi, w = _rows(a, ap, h, cos, sin, eps, dt)
    w_k, w_v = _halves(a, ap, dt)
    # a head's query and key whole, the shared rotary key behind each
    # head's own part: one product a pair, not two summed; heads-major,
    # so that a (head, block of keys) is one dense tile
    q = jnp.swapaxes(jnp.concatenate([q_n, q_r], axis=-1)[0], 0, 1)
    k = jnp.concatenate([
        jnp.einsum("sr,rhn->hsn", ckv[0], w_k),
        jnp.broadcast_to(kr[0][None], (H, S, a.rope_dim))], axis=-1)
    v = jnp.einsum("sr,rhv->hsv", ckv[0], w_v)
    block = min(MLA_BLOCK, S)
    if S % block:
        raise ValueError(f"a prompt of {S} rows is not whole blocks of "
                         f"{block} queries")
    scale = a.scale

    def attend(first, keys, select):
        """Queries [first, first + block) against keys [0, keys):
        (H, block, v)."""
        cut = lambda t, axis=0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, first, block, axis)
        seen = jnp.arange(keys)[None, :] \
            <= first + jnp.arange(block)[:, None]              # (Q, K)
        if select:
            with jax.named_scope("mla.index"):
                dots = jnp.einsum("qjd,kd->jqk", cut(qi[0]), ik[0, :keys],
                                  preferred_element_type=_F32)
                index = jnp.einsum("jqk,qj->qk", jax.nn.relu(dots),
                                   cut(w[0]))
            with jax.named_scope("mla.select"):
                seen = top_mask(jnp.where(seen, index, -jnp.inf),
                                a.topk) & seen
        with jax.named_scope("mla.attend"):
            return _attend_block(cut(q, 1), k, v, seen, first, live,
                                 scale=scale, dt=dt)

    out = []
    for start, rows in _spans(S, block):
        # a span whose last query sits below topk chooses everything;
        # the loop ends with the last block that holds a real position
        keys, select = start + rows, a.selects and start + rows > a.topk
        out.append(jax.lax.fori_loop(
            0, jnp.clip(-(-(plen - start) // block), 0, rows // block),
            lambda i, acc, start=start, keys=keys, select=select:
            jax.lax.dynamic_update_slice(
                acc, attend(start + i * block, keys, select),
                (0, i * block, 0)),
            jnp.zeros((H, rows, a.v_dim), dt)))
    heads = jnp.swapaxes(jnp.concatenate(out, axis=1), 0, 1)
    return (heads.reshape(1, S, H * a.v_dim), ckv, kr) + (
        (ik,) if a.selects else ())
