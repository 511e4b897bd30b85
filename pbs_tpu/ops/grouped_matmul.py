"""Rows sorted by group against each group's own matrix, one pass.

The product of ``models/moe.py::_sorted_rows``: ``rows`` (m, k) lie
sorted by expert, ``sizes[e]`` of them a held expert, whatever is left
behind the last one; group ``e``'s rows go against ``weights[e]`` (k,
n)::

    out[start[e]:start[e] + sizes[e]] = rows[the same] @ weights[e]

in float32 accumulators, the sum ``jax.lax.ragged_dot`` forms (which
stays as the CPU lowering and as this kernel's oracle). XLA:TPU's
``ragged-dot-*`` pays by the group touched and by the widths, not by
the rows or the bytes: 5.4-6.4 ms for 32 groups of ~96 rows at 2688 x
1856, where the weights' read is 0.39 ms and the arithmetic 0.16
(PERF.md section 6, PR 43), behind a copy of the whole ``(groups, k,
n)`` tensor where ``n`` is no multiple of 128.

Here the grid walks **visits**: a (row tile, group) pair for every
tile of ``tm`` rows a group has rows in, in the order of the rows.
Which tile and which group a visit is come scalar-prefetched (the
pattern of ``jax.experimental.pallas.ops.tpu.megablox``); the index
maps choose the row tile and the group's weight block by them, the
body forms the tile's product with that block on the MXU and stores
the rows that are the group's (the rest of the tile keeps what an
earlier visit of the same tile left: an output block stays on the chip
while consecutive visits name it). So

- a group's weights are fetched once: its visits are consecutive and
  a block index that repeats is not fetched again; a group with no
  rows has no visit, and no visit reaches the tiles behind the last
  held row (they are never read, and their rows of the output hold
  whatever was there);
- the weights are taken **as they lie**: a block is a group's whole
  ``k`` by ``tn`` columns, ``tn`` the whole of ``n`` where that fits
  (any ``n``: a block's last dimension may be the array's own) and a
  multiple of 128 that divides it otherwise, so nothing is padded or
  copied beforehand; ``k`` is never split, so no partial sum leaves
  the chip;
- the steps of the grid past the last visit (their number is static:
  tiles + groups - 1) name the last visit's blocks again and run
  nothing.

A visit costs its weight block's read (12 us for 10 MB at 819 GB/s)
or ``tm`` rows' arithmetic against it (6.5 us at the MXU's peak for
128 x 2688 x 1856), whichever is longer; a group that crosses a tile's
edge pays the arithmetic twice and the read once.

Alone on a v5e (my chip run, PR 44, call B; bfloat16, ms a product,
in / out, ``ragged_dot`` beside it): nemotron's 2048-row forward
(12,288 sorted rows, 3,072 on 32 experts of 2688 x 1856, ``we1``
turned) 0.68 / 0.79 against 5.48 / 4.66, its 1024-row one 0.56 / 0.61
against 5.08 / 4.26; glm-5's piece (16,384, 1,024 on 16 of 6144 x
2048, two column blocks) 0.78 / 0.91 against 1.46 / 1.63; solar's
tick and 256-row forward (2,048, 256 on 40 of 4096 x 1280) 0.62 /
0.61 against 1.99 / 1.56; laguna's 1024-row forward (10,240, 5,120 on
128 of 3072 x 1024) 1.31 / 1.38 against 2.93 / 2.83 and its tick (640,
320: 2.5 held rows an expert, ~118 of 128 touched) 1.02 / 1.03 against
1.36 / 1.26: 520-730 GB/s of the touched weights whatever the widths,
and ahead at every shape, least where a group has fewest rows, so
``models/moe.grouped_kernel_takes`` asks for nothing but the tiling
(PR 49). Tiles of 64 and 256 rows read within 7% of 128's at every
shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "grouped_matmul_tiles", "lies_turned"]

#: Rows a tile: the MXU's own height, so a visit's arithmetic is the
#: least a weight block costs however few rows its group has.
ROWS = 128
#: Most bytes of one group's weights a block (double-buffered beside
#: the rows and the output, under 64 MiB of the chip's 128 of VMEM).
BLOCK_BYTES = 16 << 20
_F32 = jnp.float32


def _columns(k: int, n: int, itemsize: int) -> int:
    """Columns a weight block: all of ``n`` where a group's matrix
    fits ``BLOCK_BYTES``, else the widest multiple of 128 that divides
    ``n`` and fits (0: none does)."""
    if k * n * itemsize <= BLOCK_BYTES:
        return n
    return next((tn for tn in range(n - n % 128, 0, -128)
                 if n % tn == 0 and k * tn * itemsize <= BLOCK_BYTES), 0)


def lies_turned(k: int, n: int) -> bool:
    """Whether XLA:TPU keeps a ``(groups, k, n)`` tensor with ``k`` the
    fastest dimension: it does where ``n`` is not whole rows of 128
    lanes and ``k`` is (nothing is padded that way). The kernel then
    takes ``swapaxes(weights, 1, 2)``, a bitcast of what lies there, in
    ``(tn, k)`` blocks and contracts both operands' last dimension;
    asked for ``(k, tn)`` blocks, XLA copies the whole tensor before
    every call (``copy bf16[32,2688,1856]``, 0.8 ms: what it does for
    its own ``ragged-dot``)."""
    return n % 128 != 0 and k % 128 == 0


def grouped_matmul_tiles(m: int, groups: int, k: int, n: int,
                         dtype) -> bool:
    """Whether the kernel's tiling takes this product on a chip: rows
    and matrices of one 16-bit or 32-bit float type, ``k`` whole
    sublane tiles of it, and a weight block that fits."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    return m > 0 and groups > 0 and k % (32 // dtype.itemsize) == 0 \
        and _columns(k, n, dtype.itemsize) > 0


def _visits(sizes, tiles: int, tm: int):
    """The grid's map: for each of ``tiles + groups - 1`` steps the
    group and the row tile it visits, the groups' row offsets and the
    number of visits there are; a step past the last visit repeats it
    (no visit at all: tile 0, and nothing runs)."""
    groups = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    span = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    through = jnp.cumsum(span)              # visits up to and with group e
    count = through[-1]
    step = jnp.clip(jnp.arange(tiles + groups - 1, dtype=jnp.int32),
                    0, jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.sum(through[None, :] <= step[:, None], axis=1,
                                dtype=jnp.int32), groups - 1)
    tile = jnp.clip(first[group] + step - (through[group] - span[group]),
                    0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, offsets, count[None]


def _visit_kernel(group_ref, tile_ref, offsets_ref, count_ref, x_ref, w_ref,
                  o_ref, *, tm: int, turned: bool):
    """Grid step (column block j, visit v): the tile's rows against the
    group's block ((tn, k) where the weights lie ``turned``), stored
    where the rows are the group's."""
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _():
        g = group_ref[v]
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[...],
            (((1,), (1 if turned else 0,)), ((), ())),
            preferred_element_type=_F32)
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def grouped_matmul(rows, weights, sizes, *, tile: int = ROWS,
                   interpret: bool = False):
    """``rows`` (m, k) sorted by group, ``weights`` (groups, k, n) of
    the same dtype, ``sizes`` (groups,) int32 rows a group (their sum
    at most m): the (m, n) product in that dtype, float32 accumulated,
    as ``jax.lax.ragged_dot(rows, weights, sizes)`` forms it, but for
    the rows behind the last group, which are neither read nor
    written. Compiled, the shapes have to satisfy
    :func:`grouped_matmul_tiles`; ``interpret`` (the tests) takes
    any, and ``tile`` (the tests) another row tile than ``ROWS``."""
    m, k = rows.shape
    groups, _, n = weights.shape
    if weights.shape[1] != k or weights.dtype != rows.dtype:
        raise ValueError(f"rows {rows.shape} {rows.dtype} against weights "
                         f"{weights.shape} {weights.dtype}")
    tn = _columns(k, n, rows.dtype.itemsize) or n
    tiles = pl.cdiv(m, tile)
    meta = _visits(sizes, tiles, tile)
    turned = lies_turned(k, n)
    if turned:
        weights = jnp.swapaxes(weights, 1, 2)
    pick = lambda j, v, grp, til, off, cnt: (  # noqa: E731
        (grp[v], j, 0) if turned else (grp[v], 0, j))
    # The three blocks double-buffered, the float32 product and 4 MiB
    # (compiled for a v5e each shape of the cells needs the blocks and
    # 1-2 MiB). No more: what a kernel may claim XLA's fusions around
    # it cannot count on, and with 96 MiB asked for nemotron's forward
    # took 65.9 ms where it takes 59.6 with 41 (PERF.md 6, PR 44).
    size = rows.dtype.itemsize
    vmem = 2 * size * (tile * k + k * tn + tile * tn) + 4 * tile * tn \
        + (4 << 20)
    return pl.pallas_call(
        functools.partial(_visit_kernel, tm=tile, turned=turned),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, tiles + groups - 1),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, v, grp, til, off, cnt: (
                    til[v], 0)),
                pl.BlockSpec((None, tn, k) if turned else (None, k, tn),
                             pick)],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda j, v, grp, til, off, cnt: (til[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        name="grouped_matmul", interpret=interpret,
    )(*meta, rows, weights)
