"""Gated delta-rule linear attention (Kimi Delta Attention,
arXiv:2510.26692) for the slot engine: what a ``models.plan.KdaKind``
layer of a planned stack computes.

A request's state in such a layer is not keys and values but one
float32 ``(head_dim, head_dim)`` matrix a head, decayed per key channel
and corrected by the delta rule each token, and the last ``kernel - 1``
inputs of a short causal convolution. Per position::

    S <- Diag(exp g) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q

Two forms of it, one a program of the engine (``models/slot_programs.py``):
:func:`kda_decode`, one recurrent step for every lane of a decode tick,
and :func:`kda_ingest`, a whole prompt from a zero state by a
chunkwise-parallel scan (:func:`kda_chunked`). A state has no cursor to
mask what was folded into it, so both keep two invariants: **padding
and idle lanes are no-ops** (a padded position enters with ``beta`` 0
and decay 1, an inactive lane's state and tail come out bit for bit)
and **ingestion starts from zero** whatever the slot held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pbs_tpu.models.plan import KdaKind
from pbs_tpu.models.quant import wload
from pbs_tpu.models.transformer import rms_norm
from pbs_tpu.ops.kda_step import kda_state_step, kda_step_tiles

__all__ = ["KDA_CHUNK", "kda_chunked", "kda_decode", "kda_ingest",
           "state_step"]

#: Positions a chunk of the prompt's delta rule holds (``kda_chunked``).
KDA_CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def _kda_inputs(a: KdaKind, ap: dict, h: jax.Array, dt):
    """What a delta-rule layer reads off its normed input h (B, S, d):
    the three products that go through the short convolution, side by
    side (B, S, 3 * H * hd) in ``dt``; the log decay ``g`` (B, S, H,
    hd), float32 and negative, one a key channel; ``beta`` (B, S, H)
    in (0, 2): past one, ``1 - beta`` is a negative eigenvalue; the
    output gate (B, S, H, hd)."""
    B, S, _ = h.shape
    H, hd = a.n_heads, a.head_dim
    f32 = jnp.float32
    qkv = jnp.concatenate(
        [h @ wload(ap[n], dt) for n in ("wq", "wk", "wv")], axis=-1)
    step = jax.nn.softplus(
        ((h @ wload(ap["wa1"], dt)) @ wload(ap["wa2"], dt)).astype(f32)
        + ap["dt_bias"].astype(f32)).reshape(B, S, H, hd)
    g = -jnp.exp(ap["a_log"].astype(f32))[:, None] * step
    beta = 2.0 * jax.nn.sigmoid((h @ wload(ap["wb"], dt)).astype(f32))
    gate = jax.nn.sigmoid(
        ((h @ wload(ap["wg1"], dt)) @ wload(ap["wg2"], dt)).astype(f32))
    return qkv, g, beta, gate.reshape(B, S, H, hd)


def _kda_qkv(a: KdaKind, conved: jax.Array):
    """The convolution's output (..., 3 * H * hd), float32, as q, k, v
    (..., H, hd): silu, then q and k of unit length a head and q times
    ``hd ** -0.5``."""
    H, hd = a.n_heads, a.head_dim
    q, k, v = (t.reshape(t.shape[:-1] + (H, hd)) for t in jnp.split(
        jax.nn.silu(conved), 3, axis=-1))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return unit(q) * hd ** -0.5, unit(k), v


def _kda_out(a: KdaKind, ap: dict, o: jax.Array, gate: jax.Array,
             eps: float, dt) -> jax.Array:
    """Heads' outputs o (B, S, H, hd) float32: normed a head, gated,
    through ``wo``."""
    o = rms_norm(o, ap["o_norm"].astype(jnp.float32), eps) * gate
    return o.astype(dt).reshape(o.shape[:2] + (-1,)) @ wload(ap["wo"], dt)


def _conv_filters(ap: dict) -> jax.Array:
    """(kernel, 3 * H * hd) float32: one filter a channel, the last
    tap on the newest position."""
    return jnp.concatenate(
        [ap[n].astype(jnp.float32) for n in ("cq", "ck", "cv")], axis=-1)


def state_step(state, alpha, k, q, v, beta, active):
    """The recurrent step in ``jax.numpy``: what a CPU runs and what
    :func:`pbs_tpu.ops.kda_step.kda_state_step` is held to. ``state``
    (B, H, hd, hd) float32, key channel on the rows; ``alpha`` (the
    decay ``exp g``), k, q, v (B, H, hd); ``beta`` (B, H); ``active``
    (B,). Returns (o (B, H, hd), state).

    Products with the state are multiply-and-sum, not dots: a float32
    dot runs in bfloat16 passes on the chip by default. S^T k and S^T q
    of the decayed state S = Diag(alpha) state in one reduction over
    the state as it lies, the decay folded into the two vectors.
    o = S'^T q with S' = S + k u^T is S^T q + (k . q) u. ``S^T k`` has
    to be whole before the correction is written, so XLA:TPU makes of
    this a read pass and a read-and-write pass over the state."""
    kq = jnp.stack([k, q], axis=2) * alpha[:, :, None, :]
    seen = jnp.sum(state[:, :, None] * kq[..., None], axis=-2)
    u = beta[..., None] * (v - seen[:, :, 0])
    o = seen[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * u
    new = state * alpha[..., None] + k[..., None] * u[..., None, :]
    return o, jnp.where(active[:, None, None, None], new, state)


def _state_step(state, *rest):
    """The step by the platform the program is lowered for: on a TPU
    the one-pass kernel, where its tiling takes the state's shape (a
    head of a multiple of 128 channels, heads by the eight); anywhere
    else, and for any other shape, :func:`state_step`."""
    if not kda_step_tiles(state.shape):
        return state_step(state, *rest)
    return jax.lax.platform_dependent(
        state, *rest, tpu=kda_state_step, default=state_step)


def kda_decode(a: KdaKind, ap: dict, h: jax.Array, state: jax.Array,
                tail: jax.Array, active: jax.Array, eps: float, dt):
    """One recurrent step for every lane: h (B, 1, d), ``state`` (B, H,
    hd, hd) float32, ``tail`` (B, kernel - 1, 3 * H * hd). An inactive
    lane's state and tail come out as they went in, bit for bit: a
    state has no cursor to mask what an idle lane folded in.
    Returns (y (B, 1, d), state, tail)."""
    qkv, g, beta, gate = _kda_inputs(a, ap, h, dt)
    with jax.named_scope("kda.conv"):
        window = jnp.concatenate([tail, qkv.astype(tail.dtype)], axis=1)
        conved = jnp.sum(window.astype(jnp.float32)
                         * _conv_filters(ap)[None], axis=1)
        new_tail = jnp.where(active[:, None, None], window[:, 1:], tail)
        q, k, v = _kda_qkv(a, conved)           # (B, H, hd)
    with jax.named_scope("kda.state"):
        o, new = _state_step(state, jnp.exp(g[:, 0]), k, q, v, beta[:, 0],
                             active)
    return _kda_out(a, ap, o[:, None], gate, eps, dt), new, new_tail


def kda_chunked(q, k, v, g, beta):
    """The delta rule over a whole prompt from a zero state, a chunk of
    positions at a time: q, k, v, g (S, H, D) float32, beta (S, H);
    returns (o (S, H, D), the state after the last position (H, D, D)).

    Per position, ``S <- Diag(exp g) S; S <- S + beta k (v - S^T k)^T;
    o = S^T q``. Inside a chunk that starts from ``S0``, with ``G`` the
    running sum of ``g`` and ``u_t`` the corrected value of position t
    (``S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T``)::

        (I + Diag(beta) A) U = Diag(beta) (V - (K exp G) S0)
        O = (Q exp G) S0 + B U
        S_end = Diag(exp G_end) S0 + (K exp(G_end - G))^T U

    where ``A[t, i] = sum_d k_t k_i exp(G_t - G_i)`` for i < t and
    ``B[t, i]`` the same with ``q_t`` for i <= t: one triangular solve
    a chunk (the UT transform) and one state handed to the next. Every
    exponent is of a difference ``G_t - G_i`` with i <= t, so nothing
    overflows however fast a channel decays. A position with ``g`` 0
    and ``beta`` 0 is a no-op: that is how padding is given."""
    S, H, D = q.shape
    C = min(KDA_CHUNK, S)
    pad = -S % C
    if pad:
        q, k, v, g = (jnp.pad(t, ((0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
    N = (S + pad) // C
    # (N, H, C, ...): a chunk at a time, heads batched
    q, k, v, g = (t.reshape(N, C, H, D).transpose(0, 2, 1, 3)
                  for t in (q, k, v, g))
    beta = beta.reshape(N, C, H).transpose(0, 2, 1)
    t_i = jnp.arange(C)[:, None] - jnp.arange(C)[None, :]  # t - i
    eye = jnp.eye(C, dtype=jnp.float32)

    def one(s0, xs):
        q, k, v, g, beta = xs
        G = jnp.cumsum(g, axis=1)                          # (H, C, D)
        end = G[:, -1:, :]
        # k_i exp(G_t - G_i), i <= t: (H, t, i, D)
        kd = k[:, None, :, :] * jnp.exp(jnp.where(
            (t_i >= 0)[None, :, :, None],
            G[:, :, None, :] - G[:, None, :, :], -jnp.inf))
        A = jnp.sum(k[:, :, None, :] * kd, axis=-1) * (t_i > 0)
        Bm = jnp.sum(q[:, :, None, :] * kd, axis=-1)
        decayed = jnp.exp(G)
        rhs = beta[..., None] * (v - jnp.einsum(
            "hcd,hde->hce", k * decayed, s0, precision=_HI))
        U = jax.scipy.linalg.solve_triangular(
            eye + beta[..., None] * A, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("hcd,hde->hce", q * decayed, s0, precision=_HI) \
            + jnp.einsum("hti,hie->hte", Bm, U, precision=_HI)
        s1 = jnp.exp(end)[:, 0, :, None] * s0 + jnp.einsum(
            "hcd,hce->hde", k * jnp.exp(end - G), U, precision=_HI)
        return s1, o

    s_end, o = jax.lax.scan(one, jnp.zeros((H, D, D), jnp.float32),
                            (q, k, v, g, beta))
    return o.transpose(0, 2, 1, 3).reshape(N * C, H, D)[:S], s_end


def kda_ingest(a: KdaKind, ap: dict, h: jax.Array, valid: jax.Array,
                eps: float, dt):
    """One prompt's pass through a delta-rule layer, **from a zero
    state** whatever the slot held: h (1, S, d) padded, ``valid`` (1,
    S) its real positions. A padded position is a no-op of the
    recurrence (``beta`` 0, decay 1) and the tail is the last
    ``kernel - 1`` real positions, so every padded length leaves what
    the exact length would. Returns (y (1, S, d), state (1, H, hd,
    hd), tail (1, kernel - 1, 3 * H * hd))."""
    qkv, g, beta, gate = _kda_inputs(a, ap, h, dt)
    plen = valid.sum()
    with jax.named_scope("kda.conv"):
        taps = a.conv
        padded = jnp.pad(qkv[0], ((taps - 1, 0), (0, 0)))
        S = qkv.shape[1]
        filt = _conv_filters(ap)
        conved = sum(padded[j:j + S].astype(jnp.float32) * filt[j]
                     for j in range(taps))
        tail = jax.lax.dynamic_slice_in_dim(padded, plen, taps - 1)[None]
        q, k, v = _kda_qkv(a, conved)           # (S, H, hd)
    with jax.named_scope("kda.state"):
        real = valid[0]
        o, state = kda_chunked(
            q, k, v, jnp.where(real[:, None, None], g[0], 0.0),
            jnp.where(real[:, None], beta[0], 0.0))
    return _kda_out(a, ap, o[None], gate, eps, dt), state[None], tail
