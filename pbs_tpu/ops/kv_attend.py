"""One pass over a lane's live keys and values a decode tick.

The grouped-query softmax attention of a decode tick (one query
position a lane), per lane, over the positions its cache keeps up to
its cursor (``k`` and ``v`` a position and a KV head; query head ``h``
reads KV head ``h // g``, ``g = H / nkv``)::

    s[h, t] = q[h] . k[t, h // g] / sqrt(hd)          for t <= cursor
    o[h]    = softmax over those t of s[h, t] @ v[:, h // g]

As ``jax.numpy`` (``models/slot_programs.py::_grouped_attention``, which
stays as the CPU lowering, as the path of every prompt forward and
mesh, and as this kernel's oracle) XLA:TPU reads **every position the
cache has**, whatever the cursors: a transpose of K to heads-major, the
scores over all of them, a ``where`` over the mask, a softmax and a
second full read for the values, and out of a cache stacked by layer
it slices the layer's K and V first. Here a lane's positions come into
VMEM a block of ``tk`` at a time, the block's scores, mask,
exponentials and its part of the values' product are formed there with
a float32 running maximum, sum and accumulator, probabilities rounded
to the cache's dtype before the values' product as the ``jax.numpy``
form rounds them, and K and V are read once each.

**Blocks past a lane's cursor are never fetched.** The block each
cursor is in is scalar-prefetched; K's and V's index maps name no
block of a lane past it (a block index that repeats is not fetched
again) and the body runs only up to it. A lane whose cursor is at 0 streams
one block. Positions behind the cursor *inside* that last block are
read and masked, as every dead position is by the ``jax.numpy`` form:
the set attended is the same whichever lowering ran. A cursor past the
cache's last position is clamped to it, which is also what a ring (a
window layer's cache, position p at ``p mod W``) needs, live whole once
lapped; no program sends a ring here (last paragraph), the tests alone
do.

**The cache comes as it lies.** XLA:TPU keeps ``bf16[B, T, nkv, 128]``
with the KV heads second-minor (``{3,2,1,0:T(8,128)(2,1)}`` at 8 heads,
``T(2,128)(2,1)`` at 2): a position's
heads are neighbouring rows of 128 lanes, two to a 32-bit word. A view
``(B, T, nkv * 128)`` sliced by the head, or a block ``(tk, nkv, 128)``
indexed by it, is another arrangement of the bytes, and XLA copies the
whole cache before every call to give it (PERF.md section 6, PRs 42 and
45). The rows as they lie, ``(B, T * nkv, 128)``, are a bitcast. So the
kernel takes a block of ``tk * nkv`` rows and forms **every query head
against every row**, ``(H, 128) x (tk * nkv, 128)^T``: the MXU streams
the same rows whichever heads ask (its columns are idle either way at 4
to 20 query rows a KV head), and the scores of a row whose KV head is
not the query's are masked with the dead positions. Their
probabilities are exact zeros, so the values' product over the rows as
they lie, ``(H, tk * nkv) x (tk * nkv, 128)``, sums what a product a
head would. What the mix costs is the softmax's elementwise work on
``nkv`` times the entries, which stays under the stream (last
paragraph). A
cache stacked by layer (the dense layer scan's carry) comes whole with
the layer's index, a second prefetched scalar in the index map: no
slice of a layer is made.

The pipeline itself (the grid, the prefetched cursor blocks, the index
map that names the next lane's first block on a lane's dead steps, the
running softmax in scratch) is ``ops/live_attend.py``'s, shared with
``ops/mla_attend.py``; here are the layout, the block size and the
block's arithmetic.

At solar's cell (256 lanes of 2,048 positions, 64 query heads on 8,
cursors 256-1,024, two lanes at rest) a layer takes 1.25 ms where the
same blocks through the same pipeline with no arithmetic take 1.22
(660 GB/s of what it fetches) and the ``jax.numpy`` form 3.62; blocks
of 128 positions take 1.55 (twice the steps) and of 512 1.34 (more dead
positions). Mistral's (16 x 1,024 out of the stack, cursors 128-260)
0.070 for 0.154 with its slices, laguna's full layers 0.43 for 0.86,
nemotron's (2 KV heads, blocks of 512) 0.40 for 0.64 and 0.45 at 1,024.

Two shapes stay on the ``jax.numpy`` form, on what was measured (PERF.md
section 6, PR 45). A lapped ring of 512 (laguna's window layers) takes
0.22 ms here and 0.18 there: all of it is live, and XLA's fusions
stream it faster than this pipeline does. A cache of **one KV head**
(jamba's 64 x 2,560, positions second-minor) took 0.16 ms here at
blocks of 640 or 2,560 and 0.125 at 1,280 for 0.159 there, and its cell
gained nothing end to end (1,287.5 -> 1,276.2 tokens/s, inside its
spread), while XLA, with this call in the program, staged the Mamba
layers' state through VMEM in copies of its own: :func:`kv_attend_tiles`
takes two KV heads and more, and ``BLOCKS`` carries no size for one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.ops.live_attend import live_attend

__all__ = ["attend_block", "kv_attend", "kv_attend_tiles"]

#: Positions a block, the widest that divides the cache's length and
#: keeps a block of K (and of V) within ``BLOCK_ROWS`` rows, positions
#: times KV heads: 256 positions of 8 heads, 512 of 2 (PERF.md section
#: 6, PR 45, has the sizes timed).
BLOCKS = (512, 256, 128)
BLOCK_ROWS = 2048
_F32 = jnp.float32


def attend_block(T: int, nkv: int = 1) -> int:
    """Positions a block of a cache of ``T`` positions of ``nkv`` KV
    heads (0: the kernel's tiling does not take that length)."""
    return next((tk for tk in BLOCKS
                 if T % tk == 0 and tk * nkv <= max(BLOCK_ROWS, 128 * nkv)),
                0)


def kv_attend_tiles(nkv: int, hd: int, T: int) -> bool:
    """Whether the kernel's tiling takes a cache of ``T`` positions of
    ``nkv`` KV heads of ``hd`` on a chip: a head one row of 128 lanes,
    whole blocks of positions, KV heads a power of two (a row's head
    and position are a mask and a shift of its index) and more than one
    (one head's cache lies positions second-minor and gained nothing:
    the module's last paragraph)."""
    return (hd == 128 and nkv > 1 and nkv & (nkv - 1) == 0
            and attend_block(T, nkv) > 0)


def _block(b, j, limit_ref, layer_ref, q_ref, heads_ref, k_ref, v_ref, *,
           scale: float, nkv: int, tk: int):
    """Block j of lane b: every query head against every row of the
    block as it lies, a row live for a head where it is the head's KV
    head's and at or before ``limit_ref[b]``, the last position the
    lane attends."""
    del layer_ref  # the index maps' alone
    keys, values = k_ref[0, 0], v_ref[0, 0]                  # (tk nkv, hd)
    nt = (((1,), (1,)), ((), ()))
    scores = jax.lax.dot_general(
        q_ref[0], keys, nt, preferred_element_type=_F32) * scale
    # row r of the block is position j tk + r // nkv, head r % nkv
    row = jax.lax.broadcasted_iota(jnp.int32, (1, tk * nkv), 1)
    shift = nkv.bit_length() - 1
    live = (row >> shift) <= limit_ref[b] - j * tk           # (1, rows)
    mask = ((row & (nkv - 1)) == heads_ref[...]) & live      # (H, rows)
    return scores, mask, values


def kv_attend(q, k, v, row_pos, layer=None, *, scale: float | None = None,
              block: int | None = None, interpret: bool = False):
    """What every lane's query heads read off its cache: ``q`` (B, H,
    hd) in the compute dtype, the layer's ``k`` and ``v`` (B, T, nkv,
    hd) as they lie, or with ``layer`` (an int32 scalar) the caches of
    every layer (L, B, T, nkv, hd) and the layer to read; ``row_pos``
    (B,) each lane's cursor: positions ``<= min(row_pos[b], T - 1)``
    are attended (a ring that has lapped is live whole). Returns (B, H,
    hd) in the cache's dtype, scaled by ``1 / sqrt(hd)`` as
    ``models/slot_programs.py::_grouped_attention`` scales, which is the same
    function in ``jax.numpy``, or by ``scale`` where a row is not one
    head (below). Compiled, the shapes have to satisfy
    :func:`kv_attend_tiles`; ``interpret`` (the tests) takes any whole
    blocks, and ``block`` (the tests) another block than
    :func:`attend_block`'s.

    **Heads narrower than a row.** A cache of 64-wide heads lies two
    KV heads to a row of 128 lanes (``slot_programs.kv_pack``): to this
    kernel it is a cache of half as many heads of 128. A query head
    comes zero but for the half its KV head has of a row, so its
    product with a row is its score with its own KV head and the other
    half's values land in the half of its output the caller drops;
    ``scale`` is then ``1 / sqrt(64)``, the head's and not the
    row's."""
    if layer is None:
        k, v, layer = k[None], v[None], 0
    B, H, hd = q.shape
    L, _, T, nkv, _ = k.shape
    tk = block or attend_block(T, nkv)
    if not tk or T % tk or nkv & (nkv - 1):
        raise ValueError(
            f"a cache of {T} positions of {nkv} KV heads is not whole "
            f"blocks of {tk or BLOCKS} positions of a power of two of heads")
    # query heads by the eight (a tile's sublanes); a padding row reads
    # the last KV head and is cut off below
    Hp = -(-H // 8) * 8
    heads = np.minimum(np.arange(Hp) // (H // nkv), nkv - 1).astype(np.int32)
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    # the last position a lane attends and the block it is in (the
    # pipeline's ``last``: a grid step names no block of the lane past it)
    limit = jnp.clip(row_pos.astype(jnp.int32), 0, T - 1)
    rows = tk * nkv
    cache = ((1, 1, rows, hd),
             lambda lane, block, limit, layer: (layer[0], lane, block, 0))
    out = live_attend(
        functools.partial(_block, scale=scale or 1.0 / np.sqrt(hd), nkv=nkv,
                          tk=tk),
        limit // tk, (limit, jnp.asarray(layer, jnp.int32).reshape(1)),
        [q], [jnp.asarray(heads)[:, None]],
        [(k.reshape(L, B, T * nkv, hd), *cache),
         (v.reshape(L, B, T * nkv, hd), *cache)],
        blocks=T // tk, out=jax.ShapeDtypeStruct((B, Hp, hd), k.dtype),
        vmem_limit_bytes=48 << 20, name="kv_attend", interpret=interpret)
    return out[:, :H]
