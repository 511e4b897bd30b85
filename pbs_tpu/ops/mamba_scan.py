"""The selective scan of a prompt's ingestion as one pass.

The recurrence of ``models/mamba.py`` per position t, channel c and
state n, float32::

    h[n, c] <- exp(dt[t, c] A[n, c]) h[n, c] + (dt[t, c] x[t, c]) B[t, n]
    y[t, c] = sum_n h[n, c] C[t, n]

As ``jax.numpy`` (``models/mamba.py::mamba_scan``, which stays as the
CPU lowering and as this kernel's oracle) it is a token loop over every
chunk at once and a second pass that hands the chunks' states on: a
``(chunks, d_state, d_inner)`` carry, a stacked output and every
exponential twice. Here a tile of channels' ``(d_state, tc)`` state
lies in VMEM (the state's output block, revisited) while the grid walks
the prompt's positions, innermost and in order: ``x``, ``dt``, ``B``
and ``C`` are read once, ``y`` is written once, and the state reaches
HBM once, after the last position.

The state lies as the cache holds it, ``d_state`` on the sublanes and
the channels on the lanes, so a position's ``dt`` and ``dt x`` are rows
stretched down the sublanes and its ``B`` and ``C`` columns stretched
along the lanes. ``B`` and ``C`` come side by side in one 128-lane row a
position; eight positions' rows are turned to columns together (one XLU
transpose) and a position's column is then a static slice, as
``ops/kda_step.py`` does with a head's vectors. Eight positions are
unrolled inside a ``fori_loop`` step, so that their eight rows of ``y``
are stored as one full tile. Everything is float32 on the VPU/EUP in
``mamba_decode``'s own order, each exponential once.

A position's arithmetic knows nothing of the rung or of the tile, so
the same prompt at any padded length leaves the same bits. A block of
positions that lies wholly behind the prompt's last real position
(``dt`` 0 there: decay 1, input 0) is not walked: it would leave the
state as it is (but for the sign of an entry that is zero), and its
rows of ``y``, which nobody reads, are written as zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_prompt_scan", "mamba_scan_tiles"]

#: Positions whose rows share one (8, 128) float32 tile.
GROUP = 8
#: Positions a grid step walks: a padded length is a multiple of it.
BLOCK = 128
#: Lanes of a vector register, and of the row that holds a position's
#: ``B`` and ``C`` side by side.
LANES = 128
#: Most bytes of state a tile of channels holds: 16 of the 64 vector
#: registers. A wider tile spills more of a position's decays, a
#: narrower one cuts the blocks' rows into shorter runs for the DMA: in
#: the 28-layer program a 2048-row forward reads 116.1 ms at tiles of
#: 256 channels, 96.6 at 512, 91.5 at 1024, 90.7 at 1280 and at 2560
#: (PERF.md section 6, PR 38).
STATE_BYTES = 64 << 10


def _tile(S: int, C: int, N: int) -> tuple[int, int]:
    """(positions a block, channels a tile): the widest tile of whole
    registers that divides the channels and holds ``STATE_BYTES`` of
    state (all of them where they do not come by the 128: the tests'
    toy widths, interpreted)."""
    most = max(LANES, STATE_BYTES // (4 * N) // LANES * LANES)
    tc = max((t for t in range(LANES, min(C, most) + 1, LANES)
              if C % t == 0), default=C)
    return min(BLOCK, S), tc


def mamba_scan_tiles(shape) -> bool:
    """Whether the kernel's tiling takes a prompt of this ``(S,
    d_inner, d_state)`` on a chip: channels by the 128, positions by
    the block, ``B`` and ``C`` side by side in one row of lanes."""
    S, C, N = shape
    return C % LANES == 0 and S % BLOCK == 0 and N % GROUP == 0 \
        and 2 * N <= LANES


def _scan_kernel(last_ref, x_ref, dt_ref, bc_ref, a_ref, y_ref, h_ref, *,
                 n: int, groups: int):
    """A block of positions of one tile of channels. ``h_ref``, the
    tile's (n, tc) block of the state's output, is the same block at
    every step along the positions: it stays in VMEM from the first,
    which zeroes it, to the last, after which it is written out."""
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        dt = dt_ref[rows, :]                                # (8, tc)
        dtx = dt * x_ref[rows, :]
        cols = bc_ref[rows, :].T                            # (128, 8)
        outs = []
        for j in range(GROUP):
            b, c = cols[:n, j:j + 1], cols[n:2 * n, j:j + 1]    # (n, 1)
            h = jnp.exp(dt[j:j + 1] * a_ref[...]) * h + dtx[j:j + 1] * b
            outs.append(jnp.sum(h * c, axis=0, keepdims=True))
        y_ref[rows, :] = jnp.concatenate(outs, axis=0)
        return h

    @pl.when(s <= last_ref[0])
    def _():
        h_ref[...] = jax.lax.fori_loop(0, groups, group, h_ref[...])

    @pl.when(s > last_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def mamba_prompt_scan(x, step, bm, cm, a_log, plen, *,
                      interpret: bool = False):
    """The selective scan over a whole prompt from a zero state: x,
    ``step`` (S, d_inner), ``bm``, ``cm`` (S, d_state), ``a_log``
    (d_state, d_inner), all float32; ``plen`` the prompt's real length
    (``step`` is 0 from there on). Returns (y (S, d_inner) without the
    skip, the state after the last position (d_state, d_inner)):
    ``models/mamba.py::mamba_scan`` is the same function in
    ``jax.numpy``. Rows of ``y`` in a block wholly behind ``plen`` are
    zeros. Compiled, the shape has to satisfy :func:`mamba_scan_tiles`;
    ``interpret`` (the tests) takes any whose positions come by the
    eight."""
    S, C = x.shape
    N = a_log.shape[0]
    ts, tc = _tile(S, C, N)
    if S % ts or ts % GROUP or 2 * N > LANES:
        raise ValueError(f"a prompt of {S} positions and {N} states does "
                         f"not come by the block of {ts}, the eight or "
                         f"one row of {LANES} lanes")
    # a position's B and C side by side, one row of lanes
    bc = jnp.concatenate(
        [bm, cm, jnp.zeros((S, LANES - 2 * N), jnp.float32)], axis=1)
    # the last block that holds a real position: the ones behind it
    # fetch nothing new (the same block again) and walk nothing
    last = jnp.maximum(plen.astype(jnp.int32) - 1, 0)[None] // ts
    seq = lambda c, s, last: (jnp.minimum(s, last[0]), c)   # noqa: E731
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, n=N, groups=ts // GROUP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(C // tc, S // ts),
            in_specs=[pl.BlockSpec((ts, tc), seq),
                      pl.BlockSpec((ts, tc), seq),
                      pl.BlockSpec((ts, LANES), lambda c, s, last: (
                          jnp.minimum(s, last[0]), 0)),
                      pl.BlockSpec((N, tc), lambda c, s, last: (0, c))],
            out_specs=[pl.BlockSpec((ts, tc), lambda c, s, last: (s, c)),
                       pl.BlockSpec((N, tc), lambda c, s, last: (0, c))]),
        out_shape=[jax.ShapeDtypeStruct((S, C), jnp.float32),
                   jax.ShapeDtypeStruct((N, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="mamba_prompt_scan", interpret=interpret,
    )(last, x, step, bc, -jnp.exp(a_log))
    return y, h
