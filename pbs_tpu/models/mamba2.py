"""State-space layer whose state is a matrix a head under one scalar
decay (Mamba-2, arXiv:2405.21060, as ``NemotronHMamba2Mixer`` writes
it) for the slot engine: what a ``models.plan.Mamba2Kind`` layer of a
planned stack computes.

A request's state in such a layer is one float32 ``(head_dim, d_state)``
matrix ``H_j`` a head and the last ``kernel - 1`` inputs of a short
causal convolution. On the normed input ``u`` of one position, with
``c = n_heads * head_dim``::

    [z (c) | xBC (c + 2 groups d_state) | dt (n_heads)] = u W_in
    [x | B | C] = silu(conv_b + sum_j conv_w[j] xBC_{t-3+j})
    dt_j = softplus(dt_j + dt_bias_j);   a_j = -exp(a_log_j)
    H_j <- exp(dt_j a_j) H_j + dt_j x_j (outer) B_g,      g = j // (heads / groups)
    y_j = H_j C_g + d_skip_j x_j
    out = rmsnorm_groups(y * silu(z)) W_out

The whole of a head's state decays by one scalar, so a chunk of ``Q``
positions of a prompt goes through matrix products
(:func:`mamba2_chunked`; ``s_t`` the running sum of ``dt a`` inside the
chunk)::

    y_t    = sum_{u<=t} exp(s_t - s_u) (C_t . B_u) dt_u x_u
             + exp(s_t) C_t H_prev
    H_next = exp(s_Q) H_prev + sum_u exp(s_Q - s_u) dt_u x_u (outer) B_u

Two programs of the engine (``models/slot_programs.py``) in the shape of
``models/mamba.py``: :func:`mamba2_decode`, one recurrent step for every
lane of a decode tick; :func:`mamba2_ingest`, a whole prompt from a zero
state in chunks. A state has no cursor to mask what was folded into it,
so they keep two invariants: **padding and idle lanes are no-ops** (a
padded position enters with ``dt`` 0: decay 1, input 0; an inactive
lane's state and tail come out bit for bit) and **ingestion starts from
zero** whatever the slot held. Both are ``jax.numpy``: neither has a
kernel yet (ROADMAP R23).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pbs_tpu.models.plan import Mamba2Kind
from pbs_tpu.models.quant import wload

__all__ = ["MAMBA2_CHUNK", "mamba2_chunked", "mamba2_decode",
           "mamba2_ingest", "mamba2_recurrence"]

#: Positions a chunk of the prompt's matrix form holds. The pairwise
#: decays of a chunk are ``(heads, Q, Q)`` float32 (64 MiB a layer for
#: every chunk of a 2048-row prompt at 128) and the products' work a
#: position grows with Q, the hand-overs' falls with it.
MAMBA2_CHUNK = 128
_F32 = jnp.float32
#: A product with the state on either side (what a chunk adds to it,
#: what it adds to a chunk's output) keeps float32's digits: on the
#: chip a float32 dot otherwise rounds both operands to bfloat16, and
#: the state is what the layer holds in float32 for.
_STATE_DOT = jax.lax.Precision.HIGHEST


def _in_proj(a: Mamba2Kind, ap: dict, h: jax.Array, dt):
    """``z`` (the gate's input, (B, S, c)) and ``xBC`` (the
    convolution's, (B, S, c + 2 groups d_state)) in ``dt``, and the raw
    step (B, S, n_heads) float32 as the product's accumulator left it."""
    c = a.d_inner
    out = jnp.dot(h, wload(ap["w_in"], dt), preferred_element_type=_F32)
    return (out[..., :c].astype(dt), out[..., c:c + a.d_conv].astype(dt),
            out[..., c + a.d_conv:])


def _split(a: Mamba2Kind, conved: jax.Array):
    """The convolution's output (..., c + 2 groups d_state) float32 as
    x (..., n_heads, head_dim), B and C (..., n_groups, d_state)."""
    c, gn = a.d_inner, a.n_groups * a.d_state
    lead = conved.shape[:-1]
    return (conved[..., :c].reshape(lead + (a.n_heads, a.head_dim)),
            conved[..., c:c + gn].reshape(lead + (a.n_groups, a.d_state)),
            conved[..., c + gn:].reshape(lead + (a.n_groups, a.d_state)))


def _step_size(ap: dict, raw: jax.Array) -> jax.Array:
    """``softplus(dt + dt_bias)`` a head, float32; not clamped."""
    return jax.nn.softplus(raw + ap["dt_bias"].astype(_F32))


def _out_proj(a: Mamba2Kind, ap: dict, y: jax.Array, x: jax.Array,
              z: jax.Array, eps: float, dt) -> jax.Array:
    """y, x (..., n_heads, head_dim) float32, z (..., c) in ``dt``: the
    skip, the gate, the norm a group of ``c / n_groups`` channels (its
    statistics the group's own, one weight a channel), ``W_out``."""
    lead = y.shape[:-2]
    with jax.named_scope("mamba2.norm"):
        y = y + ap["d_skip"].astype(_F32)[:, None] * x
        y = y.reshape(lead + (-1,)) * jax.nn.silu(z.astype(_F32))
        g = y.reshape(lead + (a.n_groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        y = g.reshape(lead + (-1,)) * ap["g_norm"].astype(_F32)
    return y.astype(dt) @ wload(ap["w_out"], dt)


def mamba2_decode(a: Mamba2Kind, ap: dict, h: jax.Array, state: jax.Array,
                  tail: jax.Array, active: jax.Array, eps: float, dt):
    """One recurrent step for every lane: h (B, 1, d), ``state`` (B,
    n_heads, head_dim, d_state) float32, ``tail`` (B, kernel - 1, c + 2
    groups d_state). An inactive lane's state and tail come out as they
    went in, bit for bit. Returns (y (B, 1, d), state, tail).

    The output is read off the state as it came in (``decay (H C) +
    dt x (B . C)``: the same number as the new state's ``H' C``), so
    that the state's one reduction and its one update read the same
    operand and XLA can make one pass of both."""
    z, xbc, raw = _in_proj(a, ap, h, dt)
    with jax.named_scope("mamba2.conv"):
        window = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=1)
        conved = jax.nn.silu(
            jnp.sum(window.astype(_F32) * ap["conv_w"].astype(_F32)[None],
                    axis=1) + ap["conv_b"].astype(_F32))
        new_tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    x, bm, cm = _split(a, conved)            # (B, H, P), (B, G, N) x 2
    step = _step_size(ap, raw[:, 0])                        # (B, H)
    with jax.named_scope("mamba2.step"):
        B, H, P, N = state.shape
        decay = jnp.exp(step * -jnp.exp(ap["a_log"].astype(_F32)))
        xd = x * step[..., None]                            # (B, H, P)
        # a head's view of its group's B and C, as (B, G, R, ...) axes
        grouped = state.reshape(B, a.n_groups, -1, P, N)
        xd_g, decay_g = (t.reshape((B, a.n_groups, -1) + t.shape[2:])
                         for t in (xd, decay))
        y = decay_g[..., None] * jnp.sum(
            grouped * cm[:, :, None, None, :], axis=-1) \
            + xd_g * jnp.sum(bm * cm, axis=-1)[:, :, None, None]
        new = decay_g[..., None, None] * grouped \
            + xd_g[..., None] * bm[:, :, None, None, :]
        new = jnp.where(active[:, None, None, None], new.reshape(
            state.shape), state)
    return (_out_proj(a, ap, y.reshape(B, H, P), x, z[:, 0], eps,
                      dt)[:, None], new, new_tail)


def mamba2_recurrence(x, step, bm, cm, a_log):
    """The recurrence a position at a time from a zero state: what
    :func:`mamba2_chunked` is held to (``tests/test_mamba2_serving.py``).
    x (S, H, P), ``step`` (S, H), ``bm``, ``cm`` (S, G, N), ``a_log``
    (H,), float32; returns (y (S, H, P) without the skip, the state
    after the last position (H, P, N))."""
    S, H, P = x.shape
    G, N = bm.shape[1:]
    A = -jnp.exp(a_log)

    def token(h, xs):
        x, step, bm, cm = xs
        bh, ch = (jnp.repeat(t, H // G, axis=0) for t in (bm, cm))
        h = jnp.exp(step * A)[:, None, None] * h \
            + (step[:, None] * x)[:, :, None] * bh[:, None, :]
        return h, jnp.sum(h * ch[:, None, :], axis=-1)

    h, y = jax.lax.scan(token, jnp.zeros((H, P, N), _F32),
                        (x, step, bm, cm))
    return y, h


def mamba2_chunked(x, step, bm, cm, a_log):
    """The same recurrence over a whole prompt from a zero state, a
    chunk of ``MAMBA2_CHUNK`` positions at a time, in the matrix form of
    the module docstring: x (S, H, P), ``step`` (S, H), ``bm``, ``cm``
    (S, G, N), ``a_log`` (H,), all float32; returns (y (S, H, P)
    without the skip, the state after the last position (H, P, N)).

    Every chunk's own part is computed at once: the pairwise decays
    ``exp(s_t - s_u)`` (``u <= t``), times ``C_t . B_u`` (one product a
    group, shared by its heads), times the chunk's inputs (one product
    a head); what each chunk alone adds to the state (one product a
    head). One pass over the chunks in order then hands the state on
    (a multiply-add of ``(H, P, N)`` a chunk), and what a chunk's entry
    state adds to its outputs is one more product a head. ``a < 0 <=
    dt``, so every exponent taken is ``<= 0``. A position with ``dt``
    0 is a no-op (decay 1, input 0): that is how padding is given, and
    how a ragged last chunk is filled. A chunk's arithmetic does not
    depend on how many chunks follow, so every rung leaves the same
    state."""
    S, H, P = x.shape
    G, N = bm.shape[1:]
    R = H // G
    Q = min(MAMBA2_CHUNK, S)
    pad = -S % Q
    if pad:
        x, step, bm, cm = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                           for t in (x, step, bm, cm))
    K = (S + pad) // Q
    A = -jnp.exp(a_log).reshape(G, R)
    step = step.reshape(K, Q, G, R)
    xd = x.reshape(K, Q, G, R, P) * step[..., None]         # dt_u x_u
    bm, cm = bm.reshape(K, Q, G, N), cm.reshape(K, Q, G, N)
    # s_t, the positions minor: (K, G, R, Q)
    s = jnp.cumsum(jnp.transpose(step * A, (0, 2, 3, 1)), axis=-1)
    last = s[..., -1]                                       # s_Q

    # inside the chunks
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decays = jnp.exp(jnp.where(seen, s[..., :, None] - s[..., None, :],
                               -jnp.inf))                   # (K, G, R, t, u)
    cb = jnp.einsum("ktgn,kugn->kgtu", cm, bm,
                    preferred_element_type=_F32)
    y = jnp.einsum("kgrtu,kugrp->ktgrp", decays * cb[:, :, None], xd,
                   preferred_element_type=_F32)
    # what each chunk adds to the state: sum_u exp(s_Q - s_u) dt x (x) B
    to_end = jnp.transpose(jnp.exp(last[..., None] - s), (0, 3, 1, 2))
    own = jnp.einsum("kugrp,kugn->kgrpn", xd * to_end[..., None], bm,
                     precision=_STATE_DOT, preferred_element_type=_F32)

    def chunk(h_in, xs):
        last, own = xs                      # (G, R), (G, R, P, N)
        return jnp.exp(last)[..., None, None] * h_in + own, h_in

    h_end, entry = jax.lax.scan(chunk, jnp.zeros((G, R, P, N), _F32),
                                (last, own))
    # what a chunk's entry state adds: exp(s_t) C_t H_prev
    carried = jnp.einsum("ktgn,kgrpn->ktgrp", cm, entry,
                         precision=_STATE_DOT, preferred_element_type=_F32)
    y = y + jnp.transpose(jnp.exp(s), (0, 3, 1, 2))[..., None] * carried
    return y.reshape(K * Q, H, P)[:S], h_end.reshape(H, P, N)


def mamba2_ingest(a: Mamba2Kind, ap: dict, h: jax.Array, valid: jax.Array,
                  eps: float, dt):
    """One prompt's pass through the layer, **from a zero state**
    whatever the slot held: h (1, S, d) padded, ``valid`` (1, S) its
    real positions. A padded position is a no-op of the recurrence
    (``dt`` 0) and the tail is the last ``kernel - 1`` real positions,
    so every padded length leaves what the exact length would. Returns
    (y (1, S, d), state (1, n_heads, head_dim, d_state), tail (1,
    kernel - 1, c + 2 groups d_state))."""
    z, xbc, raw = _in_proj(a, ap, h, dt)
    plen = valid.sum()
    with jax.named_scope("mamba2.conv"):
        taps, S = a.conv, xbc.shape[1]
        padded = jnp.pad(xbc[0], ((taps - 1, 0), (0, 0)))
        filt = ap["conv_w"].astype(_F32)
        conved = jax.nn.silu(sum(padded[j:j + S].astype(_F32) * filt[j]
                                 for j in range(taps))
                             + ap["conv_b"].astype(_F32))
        tail = jax.lax.dynamic_slice_in_dim(padded, plen, taps - 1)[None]
    x, bm, cm = _split(a, conved)            # (S, H, P), (S, G, N) x 2
    step = jnp.where(valid[0][:, None], _step_size(ap, raw[0]), 0.0)
    with jax.named_scope("mamba2.scan"):
        y, state = mamba2_chunked(x, step, bm, cm,
                                  ap["a_log"].astype(_F32))
    return (_out_proj(a, ap, y, x, z[0], eps, dt)[None], state[None], tail)
