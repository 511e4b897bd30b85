"""Instrumented Pallas TPU matmul: the kernel counts its own work.

The reference's research core hinges on *hardware* counters the guest
can read cheaply (``drivers/perfctr/x86.c:228-312`` — rdpmc with zero
hypercalls). A TPU exposes no per-tenant PMC file, but a Pallas kernel
can play the PMU's role for the op it implements: alongside the
product, it emits a small counter vector accumulated on-device across
grid cells — MXU tile invocations, HBM tile traffic, and a
data-derived event (all-zero A tiles, the sparsity the MXU wasted work
on). The host scales tiles into FLOPs exactly like perf tooling scales
event counts, then feeds them to the telemetry ledger through the
job-metrics channel (``TpuBackend._METRIC_KEYS``).

Blockwise schedule: grid (M/bm, N/bn, K/bk) with k innermost; each
(i, j) output block accumulates over k in fp32 directly in the output
ref (initialized at k==0 — the standard Pallas matmul pattern). The
stats ref maps every grid cell to one block, so on TPU's sequential
grid the accumulation is race-free; interpreter mode (CPU CI) follows
the same order.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pbs_tpu.ops.interpret import resolve_interpret

DEFAULT_BLOCK = 256

# Stat vector slots (i32; tile counts, not raw flops — the host scales,
# like software scaling a PMC event count, so 2^31 is never a limit).
STAT_MXU_TILES = 0
STAT_A_ZERO_TILES = 1
STAT_READ_KIB = 2
STAT_WRITE_KIB = 3
N_STATS = 4


def _mm_kernel(a_ref, b_ref, o_ref, stats_ref, *, n_k: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(i == 0, jnp.logical_and(j == 0, k == 0)))
    def _init_stats():
        # Per-slot scalar stores: the stats ref lives in SMEM (the
        # scalar memory — r5 stage-2 on-chip finding: Mosaic rejects
        # scalar stores to VMEM, which interpret mode accepted), and
        # SMEM takes scalar writes, not vector ones.
        for t in range(N_STATS):
            stats_ref[t] = 0

    @pl.when(k == 0)
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] += jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # -- the PMU duty: count what just happened -------------------------
    a_kib = (a.size * a.dtype.itemsize) // 1024
    b_kib = (b.size * b.dtype.itemsize) // 1024
    o_kib = (o_ref.size * o_ref.dtype.itemsize) // 1024
    a_is_zero = (jnp.count_nonzero(a) == 0).astype(jnp.int32)
    stats_ref[STAT_MXU_TILES] += 1
    stats_ref[STAT_A_ZERO_TILES] += a_is_zero
    stats_ref[STAT_READ_KIB] += a_kib + b_kib
    # one write per finished (i, j) block
    stats_ref[STAT_WRITE_KIB] += jnp.where(k == n_k - 1, o_kib, 0)


@dataclasses.dataclass(frozen=True)
class MatmulStats:
    """Host-scaled view of the kernel's counter vector."""

    mxu_tiles: int
    a_zero_tiles: int
    flops: int  # tiles x 2 x bm x bn x bk (software-scaled, PMC-style)
    hbm_read_bytes: int
    hbm_write_bytes: int

    def metrics(self) -> dict[str, int]:
        """Shape expected by the Job metrics channel (step_fn returning
        ``(state, metrics)``) — lands in DEVICE_FLOPS / HBM_BYTES ledger
        slots via ``TpuBackend._METRIC_KEYS``."""
        return {
            "device_flops": self.flops,
            "hbm_bytes": self.hbm_read_bytes + self.hbm_write_bytes,
        }


def scale_stats(raw, block_m: int, block_n: int, block_k: int) -> MatmulStats:
    """raw: the (N_STATS,) i32 vector from :func:`instrumented_matmul`."""
    tiles = int(raw[STAT_MXU_TILES])
    return MatmulStats(
        mxu_tiles=tiles,
        a_zero_tiles=int(raw[STAT_A_ZERO_TILES]),
        flops=tiles * 2 * block_m * block_n * block_k,
        hbm_read_bytes=int(raw[STAT_READ_KIB]) * 1024,
        hbm_write_bytes=int(raw[STAT_WRITE_KIB]) * 1024,
    )


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def instrumented_matmul(
    a: jax.Array,  # (M, K)
    b: jax.Array,  # (K, N)
    block_m: int = DEFAULT_BLOCK,
    block_n: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(a @ b, stats)`` — stats is the raw (N_STATS,) i32
    on-device counter vector; scale with :func:`scale_stats`.
    fp32 accumulation regardless of input dtype (MXU-native)."""
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"inner dims differ: {K} vs {K2}")
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(
            f"shape ({M},{K})x({K},{N}) not divisible by blocks "
            f"({bm},{bn},{bk})")
    interpret = resolve_interpret(interpret)
    n_k = K // bk

    out, stats = pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            # Scalar counters accumulate in SMEM (Mosaic: VMEM takes
            # vector stores only); whole array, every grid cell.
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((N_STATS,), jnp.int32),
        ],
        interpret=interpret,
    )(a, b)
    return out, stats
