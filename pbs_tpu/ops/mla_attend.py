"""One pass over a lane's live latent rows a decode tick.

The attention of ``models/mla.py``'s absorbed decode, per lane, over
the rows its cache keeps (``ckv`` the latent, ``kr`` the rotary key
every head shares) under the choice its indexer made::

    s[h, t] = (q_lat[h] . ckv[t] + q_r[h] . kr[t]) / sqrt(qk)
    o_lat[h] = softmax over the chosen t of s[h, t] @ ckv

As ``jax.numpy`` XLA:TPU makes four fusions of it over *every kept
row*: the two score products write float32 ``(lanes, heads, T)``, the
masked softmax reads and writes them again, and the values' product
reads ``ckv`` a second time (``models/mla.py::attend_rows``, which
stays as the CPU lowering and as this kernel's oracle). Here a lane's
rows are brought into VMEM a block of ``tk`` positions at a time, the
block's scores, mask, exponentials and its part of the values' product
are formed there with a running maximum and sum
(``models/mla.py::_attend_chunks``' arithmetic, the block on the chip),
and the float32 scores never leave the chip: ``ckv`` is read once.

**Blocks past a lane's cursor are never fetched.** The block each
cursor is in is scalar-prefetched; the index maps of ``ckv``, ``kr``
and the choice name no block of a lane past it and the body runs only
up to it (``ops/live_attend.py``, the pipeline this kernel shares with
``ops/kv_attend.py``: the grid, the index map that names the next
lane's first block on a lane's dead steps, the running softmax in
scratch; here are the layout, the block size and the block's
arithmetic). An idle lane (``mla_decode`` rests its cursor at 0)
streams one block. Rows behind the cursor *inside* that last block are
read and masked, as every dead row is by the ``jax.numpy`` form: the
choice is ``decode_choice``'s own mask, so the set attended is the
same whichever lowering ran, ties in.

The caches come as they lie. XLA:TPU keeps a cache whose rows are
narrower than a row of lanes **positions-minor** (GLM-5's ``kr``:
``bf16[64,10240,64]{1,2,0}``, a lane's rotary keys as ``(64, T)``), so
the kernel takes ``swapaxes(kr, 1, 2)`` in ``(rope_dim, tk)`` blocks:
a bitcast of what lies there, dense rows of ``tk`` to fetch, and the
rotary scores a plain product. Asked for ``(tk, rope_dim)`` blocks,
XLA copies and pads the whole cache before every call (1.34 ms a layer
against 0.89: PERF.md section 6, PR 42). The choice comes as an int32
row a lane, one ``(1, tk)`` slab a block broadcast over the heads on
the chip.

**A layer without an indexer, and a window of queries a lane**
(:func:`mla_attend_window`): where nothing chooses, what a query
attends is every row up to its own, so the mask is the block's
positions against the lane's cursor, formed on the chip from the
scalar-prefetched cursors, and no ``(lanes, T)`` choice is read. A
lane's ``S`` queries (a verify window: the token at the cursor and the
drafted ones behind it, query ``s`` at position ``cursor + s``) ride as
``S x heads`` rows of one product against the block, row ``s x heads +
h`` masked at its own position: the lane's rows are read once for all
of them, and the pipeline runs to the block the last query is in.

At GLM-5's cell (64 lanes, 64 heads, 10,240 positions of 512 + 64,
cursors 3,072-9,700) a layer takes 0.89 ms where the same blocks
through the same pipeline with no arithmetic take 0.74 (713 GB/s) and
the ``jax.numpy`` form 3.15; blocks of 512 take 1.07 (twice the grid
steps), 2,048 the same 0.89 (fewer steps, more dead rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pbs_tpu.ops.live_attend import live_attend

__all__ = ["attend_block", "mla_attend", "mla_attend_tiles",
           "mla_attend_window"]

#: Positions a block, the widest that divides the cache's length: a
#: ``ckv`` block of 1 MiB at a latent of 512, double-buffered (the
#: module's last paragraph has the three sizes timed).
BLOCKS = (1024, 512)
_F32 = jnp.float32


def attend_block(T: int) -> int:
    """Positions a block of a cache of ``T`` positions (0: the kernel's
    tiling does not take that length)."""
    return next((tk for tk in BLOCKS if T % tk == 0), 0)


def mla_attend_tiles(heads: int, T: int, kv_rank: int) -> bool:
    """Whether the kernel's tiling takes these caches on a chip: a
    latent of whole rows of 128 lanes, whole blocks of positions, heads
    by the eight (a rotary key of any width: a block holds it whole)."""
    return kv_rank % 128 == 0 and heads % 8 == 0 and attend_block(T) > 0


def _block(b, j, q_ref, qr_ref, ckv_ref, kr_ref, chosen_ref, *, scale: float):
    """Block j of lane b: every head against the block's latent rows
    and rotary keys, a row live where the lane's indexer chose it."""
    rows = ckv_ref[0]                                           # (tk, R)
    nt = (((1,), (1,)), ((), ()))
    scores = (jax.lax.dot_general(q_ref[0], rows, nt,
                                  preferred_element_type=_F32)
              + jnp.dot(qr_ref[0], kr_ref[0],
                        preferred_element_type=_F32)) \
        * scale                                                 # (H, tk)
    return scores, chosen_ref[0] != 0, rows                     # (1, tk)


def mla_attend(q_lat, q_r, ckv, kr, chosen, row_pos, *, scale: float,
               block: int | None = None, interpret: bool = False):
    """What every lane's heads read off its chosen rows: ``q_lat`` (B,
    H, kv_rank) and ``q_r`` (B, H, rope_dim) in the compute dtype, the
    layer's caches ``ckv`` (B, T, kv_rank) and ``kr`` (B, T, rope_dim)
    as they lie, ``chosen`` (B, T) bool (``decode_choice``'s: nothing
    past ``row_pos[b]``, something at or before it), ``row_pos`` (B,)
    each lane's cursor. Returns ``o_lat`` (B, H, kv_rank) in ``ckv``'s
    dtype. ``models/mla.py::attend_rows`` is the same function in
    ``jax.numpy``. Compiled, the shapes have to satisfy
    :func:`mla_attend_tiles`; ``interpret`` (the tests) takes any,
    and ``block`` (the tests) another block than
    :func:`attend_block`'s."""
    B, H, R = q_lat.shape
    T, E = kr.shape[1:]
    tk = block or attend_block(T)
    if not tk or T % tk:
        raise ValueError(f"a cache of {T} positions is not whole blocks "
                         f"of {tk or BLOCKS}")
    # the block a lane's cursor is in (the pipeline's ``last``: a grid
    # step names no block of the lane past it)
    last = jnp.clip(row_pos.astype(jnp.int32) // tk, 0, T // tk - 1)
    return live_attend(
        functools.partial(_block, scale=scale), last, (), [q_lat, q_r], [],
        [(ckv, (1, tk, R), lambda lane, block: (lane, block, 0)),
         (jnp.swapaxes(kr, 1, 2), (1, E, tk),
          lambda lane, block: (lane, 0, block)),
         (chosen.astype(jnp.int32)[:, None, :], (1, 1, tk),
          lambda lane, block: (lane, 0, block))],
        blocks=T // tk, out=jax.ShapeDtypeStruct((B, H, R), ckv.dtype),
        vmem_limit_bytes=32 << 20, name="mla_attend", interpret=interpret)


def _window_block(b, j, pos_ref, q_ref, qr_ref, ckv_ref, kr_ref, *,
                  scale: float, heads: int, tk: int):
    """Block j of lane b: every head of every query of the lane's
    window against the block's latent rows and rotary keys, a row live
    up to the query's own position (query ``s``'s: ``pos[b] + s``)."""
    rows = ckv_ref[0]                                           # (tk, R)
    nt = (((1,), (1,)), ((), ()))
    scores = (jax.lax.dot_general(q_ref[0], rows, nt,
                                  preferred_element_type=_F32)
              + jnp.dot(qr_ref[0], kr_ref[0],
                        preferred_element_type=_F32)) \
        * scale                                             # (S H, tk)
    at = j * tk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    query = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // heads
    return scores, at <= pos_ref[b] + query, rows


def mla_attend_window(q_lat, q_r, ckv, kr, row_pos, *, scale: float,
                      block: int | None = None, interpret: bool = False):
    """What every head of each lane's ``S`` queries reads off the rows
    up to the query's own position, no indexer between: ``q_lat`` (B,
    S, H, kv_rank) and ``q_r`` (B, S, H, rope_dim), query ``s`` of lane
    b at position ``row_pos[b] + s`` (whose rows are in the caches
    already), ``ckv`` (B, T, kv_rank) and ``kr`` (B, T, rope_dim) as
    they lie. Returns ``o_lat`` (B, S, H, kv_rank) in ``ckv``'s dtype.
    ``models/mla.py::attend_rows`` under the causal mask is the same
    function in ``jax.numpy``. Tiling, ``block`` and ``interpret`` as
    :func:`mla_attend`'s."""
    B, S, H, R = q_lat.shape
    T, E = kr.shape[1:]
    tk = block or attend_block(T)
    if not tk or T % tk:
        raise ValueError(f"a cache of {T} positions is not whole blocks "
                         f"of {tk or BLOCKS}")
    pos = row_pos.astype(jnp.int32)
    # the block the window's last query is in
    last = jnp.clip((pos + S - 1) // tk, 0, T // tk - 1)
    out = live_attend(
        functools.partial(_window_block, scale=scale, heads=H, tk=tk),
        last, (pos,),
        [q_lat.reshape(B, S * H, R), q_r.reshape(B, S * H, E)], [],
        [(ckv, (1, tk, R), lambda lane, block, pos: (lane, block, 0)),
         (jnp.swapaxes(kr, 1, 2), (1, E, tk),
          lambda lane, block, pos: (lane, 0, block))],
        blocks=T // tk, out=jax.ShapeDtypeStruct((B, S * H, R), ckv.dtype),
        vmem_limit_bytes=32 << 20, name="mla_attend_window",
        interpret=interpret)
    return out.reshape(B, S, H, R)
