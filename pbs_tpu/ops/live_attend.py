"""One pass over each lane's live blocks: the pipeline the decode
tick's attention kernels share.

``ops/kv_attend.py`` (keys and values a KV head) and
``ops/mla_attend.py`` (a latent row and a shared rotary key under a
choice) are the same machine over different blocks: grid ``(lanes, T /
tk)``, the block each lane's cursor is in scalar-prefetched, index maps
that name no block of a lane past it, a float32 running maximum, sum
and accumulator in scratch, zeroed at a lane's first step, folded up to
its last live block and written there. That machine is here, once; a
kernel brings its layout (what a block of each cache operand is and
where a (lane, block) pair lies in it), its block size and the block's
arithmetic, a function of the block's refs that returns the float32
scores, the mask and the rows the probabilities multiply.

**The next lane's first block is asked for early.** The pipeline looks
one grid step ahead, and a step past a lane's cursor takes no time:
clamped to the lane's own last block, the dead steps would put the
next lane's first fetch one step before its use, and every lane would
wait for a block whole. Past the cursor the cache operands' index map
(:func:`live_block`) names the next lane's first block instead, so that
it is fetched under this lane's last live step and lies there when its
lane begins (solar's K/V layer 1.54 -> 1.25 ms, mistral's 0.083 ->
0.070: PERF.md section 6, PR 45).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["live_attend", "live_block"]

_F32 = jnp.float32


def live_block(b, j, last, lanes: int):
    """The (lane, block) a cache operand's index map names at grid step
    (lane ``b``, block ``j``); ``last[b]`` is the block lane b's cursor
    is in. Up to the cursor's block the step's own; past it the NEXT
    lane's first block, which is then fetched under this lane's last
    live step and lies there when its lane begins (the pipeline looks
    one step ahead: behind a run of dead steps, which take no time,
    that fetch would be waited for whole). The last lane's dead steps
    stay on its last block (a block index that repeats is not fetched
    again)."""
    live, more = j <= last[b], b + 1 < lanes
    return (jnp.where(jnp.logical_or(live, jnp.logical_not(more)), b, b + 1),
            jnp.where(live, j, jnp.where(more, 0, last[b])))


def _kernel(last_ref, *refs, block_fn):
    """Grid step (lane b, block j): the block's part of the lane's
    softmax, folded into the running maximum, sum and accumulator."""
    *refs, o_ref, top_ref, total_ref, acc_ref = refs
    b, j = pl.program_id(0), pl.program_id(1)
    last = last_ref[b]
    low = jnp.finfo(_F32).min

    @pl.when(j == 0)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, low, _F32)
        total_ref[...] = jnp.zeros(total_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    @pl.when(j <= last)
    def _():
        scores, mask, values = block_fn(b, j, *refs)
        top = top_ref[...]
        peak = jnp.maximum(top, jnp.max(
            jnp.where(mask, scores, low), axis=-1, keepdims=True))
        # a block may hold no live entry of a row (``peak`` is then no
        # score of it): a masked entry's probability is a zero by the
        # mask, not by its exponential
        probs = jnp.where(mask, jnp.exp(scores - peak), 0.0)
        keep = jnp.exp(top - peak)
        total_ref[...] = total_ref[...] * keep \
            + jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * keep + jnp.dot(
            probs.astype(values.dtype), values, preferred_element_type=_F32)
        top_ref[...] = peak

    @pl.when(j == last)
    def _():
        o_ref[0] = (acc_ref[...] / total_ref[...]).astype(o_ref.dtype)


def live_attend(block_fn, last, scalars, lanes, whole, caches, *, blocks: int,
                out, vmem_limit_bytes: int, name: str,
                interpret: bool = False):
    """Every lane's softmax over its live blocks, ``(B, H, W)``.

    ``last`` (B,) int32 is the block each lane's cursor is in (of
    ``blocks`` a lane), prefetched with the further int32 ``scalars``
    (arrays; the index maps and ``block_fn`` see them as refs).
    ``lanes`` are the operands a lane has whole (``(B, x, y)``: a
    query), ``whole`` the ones the call has (``(x, y)``), and
    ``caches`` the streamed ones, each ``(array, block shape,
    place)`` with ``place(lane, block, *scalars)`` the block index of
    that pair in the array: the pipeline says which pair a step names
    (:func:`live_block`), the kernel where it lies. ``out`` is the
    result's ``jax.ShapeDtypeStruct``.

    ``block_fn(b, j, *refs)`` gets the refs of ``scalars``, ``lanes``,
    ``whole`` and ``caches`` in that order and returns a live block's
    float32 scores ``(H, n)``, its mask (``(H, n)`` or ``(1, n)``: what
    of them a row attends) and the rows ``(n, W)`` the probabilities
    multiply, rounded to those rows' dtype first. It runs for ``j <=
    last[b]`` only: a block past a lane's cursor is neither fetched nor
    read."""
    B, H, W = out.shape

    def lane(b, j, *_):
        return (b, 0, 0)

    def streamed(place):
        def index(b, j, last, *scalars):
            return place(*live_block(b, j, last, B), *scalars)
        return index

    return pl.pallas_call(
        functools.partial(_kernel, block_fn=block_fn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(scalars), grid=(B, blocks),
            in_specs=[pl.BlockSpec((1,) + x.shape[1:], lane) for x in lanes]
            + [pl.BlockSpec(x.shape, lambda b, j, *_: (0, 0)) for x in whole]
            + [pl.BlockSpec(shape, streamed(place))
               for _, shape, place in caches],
            out_specs=pl.BlockSpec((1, H, W), lane),
            scratch_shapes=[pltpu.VMEM((H, 1), _F32),
                            pltpu.VMEM((H, 1), _F32),
                            pltpu.VMEM((H, W), _F32)]),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        name=name, interpret=interpret,
    )(last, *scalars, *lanes, *whole, *(x for x, _, _ in caches))
