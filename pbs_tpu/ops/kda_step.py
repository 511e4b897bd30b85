"""One pass over a delta-rule layer's recurrent state a decode tick.

The step of ``models/kda.py`` per lane and head, on the float32 state
``S`` (head_dim x head_dim, key channel on the rows)::

    S <- Diag(alpha) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q

``S^T k`` has to be whole before the rank-one correction can be
written, so as ``jax.numpy`` XLA:TPU makes two fusions of it and reads
the state twice to write it once (``models/kda.py::state_step``, which
stays as the CPU lowering and as this kernel's oracle). Here a lane's
heads' (128, 128) tiles are brought into VMEM a block at a time, the
whole recurrence is done on each tile there, and the block is written
back where it came from (``input_output_aliases``): one read, one
write.

Everything is float32 on the VPU, multiply and sum in the ``jax.numpy``
step's own arithmetic: a float32 dot on the MXU runs in bfloat16 passes
unless asked otherwise, and the state's precision is the
configuration's. The key-side vectors (alpha, k, alpha k, alpha q) come
as rows, a head a sublane; the eight heads of one (8, 128) tile are
turned to columns together (one XLU transpose a vector) and a head's
column is then broadcast along the lanes. Those broadcasts (64
``vperm`` a head over three cross-lane units: ~170 bundles a head),
not the ~110 VPU operations, are what the kernel issues most of, and
both hide under the blocks' memory time: the kernel runs at the rate of
a plain copy through the same pipeline (PERF.md section 6, PR 36).

An idle lane's tiles are copied, not skipped: a pipelined output block
is written back whatever the kernel did with it. Its output row is
computed all the same, as the ``jax.numpy`` step does, so the layers
behind see the same activations whichever lowering ran.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_state_step", "kda_step_tiles"]

#: Heads whose vectors share one (8, 128) float32 tile.
GROUP = 8
#: Most bytes of state a block: 16 MiB of VMEM double-buffered in and
#: out. At heads of 128 that is 64 heads, a lane a grid step (isolated,
#: three layers at (256, 64, 128, 128): 10.46 ms at 16 heads a block,
#: 10.11 at 32, 10.06 at 64, a plain copy 10.03; my chip run, PR 36).
BLOCK_BYTES = 4 << 20


def _step_kernel(active_ref, alpha_ref, k_ref, q_ref, v_ref, beta_ref,
                 kq_ref, s_ref, o_ref, s_out_ref, *, heads: int, n: int):
    """A lane's block of ``heads`` heads, ``n`` of them (a sublane
    tile of the vectors) at a time."""

    def group(g, update: bool):
        rows = pl.ds(pl.multiple_of(g * n, n), n)
        alpha, k, q = alpha_ref[0, rows], k_ref[0, rows], q_ref[0, rows]
        v, beta, kq = v_ref[0, rows], beta_ref[0, rows], kq_ref[0, rows]
        cols = [alpha.T, k.T, (alpha * k).T, (alpha * q).T]   # (hd, n)
        outs = []
        for j in range(n):
            s = s_ref[0, g * n + j]                           # (hd, hd)
            a_c, k_c, ak_c, aq_c = (c[:, j:j + 1] for c in cols)
            seen_k = jnp.sum(s * ak_c, axis=0, keepdims=True)
            seen_q = jnp.sum(s * aq_c, axis=0, keepdims=True)
            u = beta[j:j + 1] * (v[j:j + 1] - seen_k)
            outs.append(seen_q + kq[j:j + 1] * u)
            s_out_ref[0, g * n + j] = s * a_c + k_c * u if update else s
        o_ref[0, rows] = jnp.concatenate(outs, axis=0)

    def lane(update: bool):
        jax.lax.fori_loop(0, heads // n,
                          lambda g, _: group(g, update), None)

    live = active_ref[pl.program_id(0)] != 0
    pl.when(live)(functools.partial(lane, True))
    pl.when(jnp.logical_not(live))(functools.partial(lane, False))


def kda_step_tiles(shape) -> bool:
    """Whether the kernel's tiling takes a state of this shape on a
    chip: heads of a multiple of 128 channels, by the eight."""
    _, H, hd, _ = shape
    return hd % 128 == 0 and H % GROUP == 0


def kda_state_step(state, alpha, k, q, v, beta, active, *,
                   interpret: bool = False):
    """One recurrent step for every lane, the state updated in place:
    ``state`` (B, H, hd, hd) float32, key channel on the rows;
    ``alpha`` (the decay a key channel), k, q, v (B, H, hd) float32;
    ``beta`` (B, H); ``active`` (B,) bool. Returns (o (B, H, hd),
    state); an inactive lane's state comes out bit for bit as it went
    in. ``models/kda.py::state_step`` is the same function in
    ``jax.numpy``. Compiled, the shape has to satisfy
    :func:`kda_step_tiles`; ``interpret`` (the tests) takes any."""
    B, H, hd, _ = state.shape
    n = min(GROUP, H)           # fewer heads than a tile: interpreted
    most = max(n, BLOCK_BYTES // (4 * hd * hd))
    hb = max(b for b in range(n, min(H, most) + 1, n) if H % b == 0)
    rows = pl.BlockSpec((1, hb, hd), lambda b, j, act: (b, j, 0))
    tiles = pl.BlockSpec((1, hb, hd, hd), lambda b, j, act: (b, j, 0, 0))
    # a head's two scalars as rows too: a block of them is one DMA
    lanes = lambda x: jnp.broadcast_to(x[..., None], (B, H, hd))  # noqa: E731
    o, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb),
            in_specs=[rows] * 6 + [tiles], out_specs=[rows, tiles]),
        out_shape=[jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 << 20),
        name="kda_state_step", interpret=interpret,
    )(active.astype(jnp.int32), alpha, k, q, v, lanes(beta),
      lanes(jnp.sum(k * q, axis=-1)), state)
    return o, new
