"""Pallas TPU flash attention (causal, GQA), training-grade.

The hot op of the flagship workload, written blockwise so attention
probabilities never materialize in HBM: per (batch, head, q-block)
grid cell, iterate over k/v blocks with the online-softmax recurrence
(running max m, normalizer l, fp32 accumulator) — the standard
flash-attention scheme expressed in Pallas for the MXU/VMEM hierarchy
(block sizes 128, fp32 accumulation via ``preferred_element_type``).

Differentiable end to end via ``jax.custom_vjp``: the forward kernel
additionally emits the per-row logsumexp, and the backward pass is two
more Pallas kernels — a dq pass (grid over q-blocks, loop over
k-blocks) and a dk/dv pass (grid over *kv*-head k-blocks, loop over
q-blocks and the GQA group, so the group reduction happens in-kernel).
Recompute-not-store: backward rebuilds p = exp(s - lse) blockwise from
q/k, exactly like forward, so nothing O(S²) ever exists.

Causal skip: a q-block only visits k-blocks up to its diagonal (and a
k-block only visits q-blocks from its diagonal on) — ``fori_loop`` with
a traced bound, so the work per row is triangular, not square.

Ragged S is accepted: the wrapper zero-pads up to the block size,
masks padded keys in-kernel, and slices padded query rows off.  The
backward kernels rely on the padded rows' output cotangent being zero,
which the wrapper's slice guarantees.

On the CPU the kernels run in Pallas interpret mode, so the same code
paths are tested in CI (the fake-backend pattern, SURVEY.md §4);
everywhere else they compile through Mosaic (``ops/interpret.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from pbs_tpu.ops.interpret import resolve_interpret
from pbs_tpu.utils.params import integer_param

# Block-shape defaults, env-tunable so an on-chip run can explore the
# VMEM/occupancy trade at long S without code edits
# (PBST_FLASH_BLOCK_Q=256 PBST_FLASH_BLOCK_K=512).
# Registered through the boot-param registry: a malformed value warns
# and falls back instead of making the package unimportable.
_block_q_param = integer_param("flash_block_q", 128)
_block_k_param = integer_param("flash_block_k", 128)


def _tile_checked(v: int, fallback: int, axis: str, mult: int) -> int:
    # Mosaic block shapes need (sublane, lane) multiples of (8, 128);
    # catch an off-tile knob HERE with the knob's name, not deep in
    # the kernel lowering (on-chip debug cycles are expensive).
    if v <= 0 or v % mult:
        print(f"pbst: PBST_FLASH_BLOCK_{axis}={v} is not a positive "
              f"multiple of {mult}; using {fallback}")
        return fallback
    return v


DEFAULT_BLOCK_Q = _tile_checked(_block_q_param.value, 128, "Q", 8)
DEFAULT_BLOCK_K = _tile_checked(_block_k_param.value, 128, "K", 128)
NEG_INF = -1e30


# -- forward ----------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                sm_scale: float, block_k: int, valid_len: int):
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (BQ, hd)
    bq = q.shape[0]
    hd = q.shape[1]
    s_len = k_ref.shape[2]
    i = pl.program_id(2)

    def body(j, carry):
        m, l, acc = carry
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, 0, ks, :]
        v = v_ref[0, 0, ks, :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        if valid_len < s_len:
            # Padded tail keys (S was rounded up to the block size):
            # mask them out; padded *query* rows produce garbage that
            # the host-side slice discards.
            s = jnp.where(kpos < valid_len, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if causal:
        # Only k-blocks at or before this q-block's diagonal.
        n_blocks = jax.lax.div(i * bq + bq + block_k - 1, block_k)
    else:
        n_blocks = s_len // block_k
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # lse rides a trailing singleton lane dim: TPU block shapes need the
    # last two dims (sublane, lane) divisible by (8, 128) or equal to
    # the array's — (bq, 1) with array (..., S, 1) satisfies that.
    lse_ref[0, 0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _fwd_call(qt, kt, vt, causal, bq, bk, valid_len, interpret,
              out_f32=False):
    """(o, lse) on padded (B, H, S_pad, hd) / (B, Hkv, S_pad, hd) inputs.

    ``out_f32`` emits o in fp32 — used by the lse variant so a combiner
    (ring attention) folds full-precision partials instead of ones
    already rounded to the compute dtype."""
    B, H, S_pad, hd = qt.shape
    group = H // kt.shape[1]
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=1.0 / np.sqrt(hd), block_k=bk,
        valid_len=valid_len)
    return pl.pallas_call(
        kernel,
        grid=(B, H, S_pad // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S_pad, hd),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, S_pad, hd),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (B, H, S_pad, hd),
                jnp.float32 if out_f32 else qt.dtype),
            jax.ShapeDtypeStruct((B, H, S_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)


# -- backward ---------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dlse_ref,
                   dq_ref, *, causal: bool, sm_scale: float, block_k: int,
                   valid_len: int):
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]    # (BQ, 1)
    # Softmax-jacobian diagonal minus the lse output's own cotangent:
    # ds = p * (dp - delta + dlse), since d lse_i / d s_ij = p_ij.
    delta = dl_ref[0, 0] - dlse_ref[0, 0]   # (BQ, 1)
    bq, hd = q.shape
    s_len = k_ref.shape[2]
    i = pl.program_id(2)

    def body(j, acc):
        ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, 0, ks, :].astype(jnp.float32)
        v = v_ref[0, 0, ks, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        if valid_len < s_len:
            s = jnp.where(kpos < valid_len, s, NEG_INF)
        p = jnp.exp(s - lse)            # masked entries: exp(-huge) = 0
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return acc + jax.lax.dot_general(
            ds, k, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        n_blocks = jax.lax.div(i * bq + bq + block_k - 1, block_k)
    else:
        n_blocks = s_len // block_k
    acc = jax.lax.fori_loop(
        0, n_blocks, body, jnp.zeros((bq, hd), jnp.float32))
    dq_ref[0, 0] = (acc * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dlse_ref,
                    dk_ref, dv_ref, *, causal: bool, sm_scale: float,
                    block_q: int, valid_len: int, group: int):
    k = k_ref[0, 0].astype(jnp.float32)  # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    bk, hd = k.shape
    s_len = q_ref.shape[2]
    j = pl.program_id(2)

    def body(i, carry):
        dk, dv = carry
        qs = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        # GQA: this kv head serves `group` q heads — reduce in-kernel.
        for r in range(group):
            q = q_ref[0, r, qs, :].astype(jnp.float32) * sm_scale
            do = do_ref[0, r, qs, :].astype(jnp.float32)
            lse = lse_ref[0, r, qs, :]   # (BQ, 1)
            delta = dl_ref[0, r, qs, :] - dlse_ref[0, r, qs, :]
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (BQ, BK)
            kpos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            if causal:
                qpos = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 0)
                s = jnp.where(kpos <= qpos, s, NEG_INF)
            if valid_len < s_len:
                s = jnp.where(kpos < valid_len, s, NEG_INF)
            p = jnp.exp(s - lse)
            # Padded q rows have do == 0 (wrapper slice guarantees a
            # zero cotangent), so they contribute nothing here even
            # though their p is degenerate.
            dv = dv + jax.lax.dot_general(
                p, do, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            dk = dk + jax.lax.dot_general(
                ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return dk, dv

    # Causal: a k-block only receives gradient from q-blocks at or
    # after its diagonal.
    i0 = jax.lax.div(j * bk, block_q) if causal else 0
    dk0 = jnp.zeros((bk, hd), jnp.float32)
    dv0 = jnp.zeros((bk, hd), jnp.float32)
    dk, dv = jax.lax.fori_loop(i0, s_len // block_q, body, (dk0, dv0))
    # dk accumulated against scaled q; the remaining sm_scale factor of
    # d(s)/d(k) is already inside q, so no extra scaling here.
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# -- custom-vjp core on padded, (B, H, S, hd)-transposed operands -----------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(qt, kt, vt, causal, bq, bk, valid_len, interpret, out_f32):
    return _fwd_call(qt, kt, vt, causal, bq, bk, valid_len, interpret,
                     out_f32)


def _flash_fwd(qt, kt, vt, causal, bq, bk, valid_len, interpret, out_f32):
    o, lse = _fwd_call(qt, kt, vt, causal, bq, bk, valid_len, interpret,
                       out_f32)
    return (o, lse), (qt, kt, vt, o, lse)


def _flash_bwd(causal, bq, bk, valid_len, interpret, out_f32, res, ct):
    do, dlse = ct  # dlse is nonzero when the caller consumed lse
    qt, kt, vt, o, lse = res
    B, H, S_pad, hd = qt.shape
    Hkv = kt.shape[1]
    group = H // Hkv
    sm_scale = 1.0 / np.sqrt(hd)
    # delta_i = rowsum(do_i * o_i): the softmax-jacobian diagonal term,
    # elementwise — XLA fuses this; no kernel needed. Trailing singleton
    # lane dim for the same TPU block-shape reason as lse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dlse = dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, sm_scale=sm_scale, block_k=bk,
            valid_len=valid_len),
        grid=(B, H, S_pad // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S_pad, hd),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, S_pad, hd),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta, dlse)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, sm_scale=sm_scale, block_q=bq,
            valid_len=valid_len, group=group),
        grid=(B, Hkv, S_pad // bk),
        in_specs=[
            pl.BlockSpec((1, group, S_pad, hd),
                         lambda b, kv, j: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j: (b, kv, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j: (b, kv, j, 0)),
            pl.BlockSpec((1, group, S_pad, hd),
                         lambda b, kv, j: (b, kv, 0, 0)),
            pl.BlockSpec((1, group, S_pad, 1),
                         lambda b, kv, j: (b, kv, 0, 0)),
            pl.BlockSpec((1, group, S_pad, 1),
                         lambda b, kv, j: (b, kv, 0, 0)),
            pl.BlockSpec((1, group, S_pad, 1),
                         lambda b, kv, j: (b, kv, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j: (b, kv, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, j: (b, kv, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kt.shape, kt.dtype),
            jax.ShapeDtypeStruct(vt.shape, vt.dtype),
        ],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta, dlse)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# -- public API -------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def plan_blocks(S: int, block_q: int, block_k: int) -> tuple[int, int, int]:
    """Mosaic-safe (bq, bk, S_pad) for the position dim.

    The hardware contract this encodes (r5 stage-2 on-chip finding —
    interpret mode accepts violations, Mosaic rejects them):
    position-dim loads index in sublane units of 8, so bq (the score
    tile's sublane dim) and every load offset must be a multiple of 8;
    bk lands in the score tile's LANE dim, where the module keeps the
    stricter full-lane contract its knob validator already asserts
    (``_tile_checked`` mult=128 for K — only chip-validated at 128,
    so the planner never emits less).  A short or ragged S therefore
    pads UP to a 128-multiple tile rather than clamping blocks down to
    S (S=127 clamped bq/bk to 127 and Mosaic refused the 127-row
    loads).  Invariants (pinned host-side by
    tests/test_attention.py::test_plan_blocks_mosaic_contract):
    bq % 8 == 0; bk % 128 == 0; S_pad >= S; S_pad % bq == S_pad % bk
    == 0.
    """
    s_tile = _round_up(max(S, 1), 128)
    # API callers may pass any positive block knob; round up to each
    # dim's quantum before fitting (the env knobs are pre-validated by
    # _tile_checked, this covers direct callers).
    bk = min(_round_up(max(block_k, 1), 128), s_tile)
    bq = min(_round_up(max(block_q, 1), 8), s_tile)
    # Mutual divisibility so one S_pad serves both grids: bq above bk
    # rounds down to a bk multiple; bq below bk rounds down to a
    # multiple-of-8 divisor of bk (floor 8 — bk is a 128 multiple).
    if bq >= bk:
        bq = (bq // bk) * bk
    else:
        while bk % bq:
            bq -= 8
    S_pad = _round_up(S, max(bq, bk))
    return bq, bk, S_pad


def _flash_padded(q, k, v, causal, block_q, block_k, interpret,
                  out_f32=False):
    """Shared pad/transpose plumbing; returns ((B,S,H,hd) o, (B,S,H,1)
    lse) with padding removed."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"H={H} not a multiple of Hkv={Hkv}")
    bq, bk, S_pad = plan_blocks(S, block_q, block_k)
    interpret = resolve_interpret(interpret)

    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # (B, H, S, hd) layout: heads become a grid dimension.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    o, lse = _flash(qt, kt, vt, causal, bq, bk, S, interpret, out_f32)
    return (o[:, :, :S].transpose(0, 2, 1, 3),
            lse[:, :, :S].transpose(0, 2, 1, 3))


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, Hkv, hd)
    v: jax.Array,  # (B, S, Hkv, hd)
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, S, H, hd). GQA: H must be a multiple of Hkv.

    Differentiable (custom VJP with Pallas backward kernels). Any S is
    accepted: a ragged tail (e.g. the S-1 of next-token training) is
    zero-padded up to the block size inside this wrapper; padded keys
    are masked in-kernel and padded query rows sliced off.
    """
    return _flash_padded(q, k, v, causal, block_q, block_k, interpret)[0]


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp, shape (B, S, H, 1) fp32 — the combiner state that lets a
    caller fold independently-computed attention partials (ring
    attention folds one of these per rotating k/v chunk). The lse
    output participates in autodiff (its cotangent feeds the ds term
    in the backward kernels). o is emitted in fp32 so the caller's
    fold accumulates at full precision regardless of compute dtype."""
    return _flash_padded(q, k, v, causal, block_q, block_k, interpret,
                         out_f32=True)
