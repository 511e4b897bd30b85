"""Selective state-space layer (Mamba-1, arXiv:2312.00752, as Jamba's
``JambaMambaMixer`` writes it) for the slot engine: what a
``models.plan.MambaKind`` layer of a planned stack computes.

A request's state in such a layer is one float32 ``(d_state, d_inner)``
matrix ``h`` and the last ``kernel - 1`` inputs of a short causal
convolution. On the normed input ``u`` of one position::

    [xr, z] = u W_in;   x = silu(conv_b + sum_j conv_w[j] xr_{t-3+j})
    [d, B, C] = x W_x, each RMS-normed;   dt = softplus(d W_dt + dt_bias)
    h[n, c] <- exp(dt[c] A[n, c]) h[n, c] + dt[c] B[n] x[c],  A = -exp(a_log)
    y[c] = sum_n h[n, c] C[n] + d_skip[c] x[c];   out = (y silu(z)) W_out

Every entry of ``h`` decays by a factor of its own at every position,
so no matrix product moves the state: it is exponentials and
multiply-adds between the in- and out-projections. The state and
``a_log`` lie ``d_state`` first: the channels fill the 128 lanes (laid
``(d_inner, d_state)`` a tile of 16 is padded to 128, eight times the
bytes).

Three programs of the engine (``models/slot_programs.py``) in the shape of
``models/kda.py``: :func:`mamba_decode`, one recurrent step for every
lane of a decode tick; :func:`mamba_ingest`, a whole prompt from a zero
state by :func:`mamba_scan` or, lowered for a TPU, by the one-pass
kernel of ``ops/mamba_scan.py``. A state has no cursor to mask what was
folded into it, so they keep two invariants: **padding and idle lanes
are no-ops** (a padded position enters with ``dt`` 0: decay 1, input 0;
an inactive lane's state and tail come out bit for bit) and **ingestion
starts from zero** whatever the slot held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pbs_tpu.models.plan import MambaKind
from pbs_tpu.models.quant import wload
from pbs_tpu.models.transformer import rms_norm
from pbs_tpu.ops.mamba_scan import mamba_prompt_scan, mamba_scan_tiles

__all__ = ["MAMBA_CHUNK", "mamba_decode", "mamba_ingest", "mamba_scan"]

#: Positions a chunk of the prompt's scan holds (``mamba_scan``): the
#: token loop is this long whatever the prompt's rung.
MAMBA_CHUNK = 64
#: Steps of that loop XLA sees as one body: each writes its rows of the
#: scan's output, and sixteen steps' rows go in one piece. It shapes what
#: a CPU runs and nothing on a chip, where the prompt's scan is
#: ``ops/mamba_scan.py`` (when PR 37 ran this loop there, sixteen read
#: 3.2 ms a layer where one read 4.7: PERF.md section 6).
MAMBA_UNROLL = 16
_F32 = jnp.float32


def _in_proj(a: MambaKind, ap: dict, h: jax.Array, dt):
    """``xr`` (the convolution's input) and ``z`` (the gate's), each
    (B, S, d_inner) in ``dt``."""
    xz = h @ wload(ap["w_in"], dt)
    return xz[..., :a.d_inner], xz[..., a.d_inner:]


def _selective(a: MambaKind, ap: dict, x: jax.Array, eps: float, dt):
    """What the recurrence reads off the convolution's output x (...,
    d_inner) float32: the step ``dt`` (..., d_inner), ``B`` and ``C``
    (..., d_state), float32. The two small products take ``dt``
    operands and give float32 (the accumulator's type), so that the
    three norms and the step see no rounding between."""
    r, n = a.dt_rank, a.d_state
    dbc = jnp.dot(x.astype(dt), wload(ap["w_x"], dt),
                  preferred_element_type=_F32)
    d, bm, cm = (rms_norm(t, ap[name].astype(_F32), eps) for t, name in (
        (dbc[..., :r], "dt_norm"), (dbc[..., r:r + n], "b_norm"),
        (dbc[..., r + n:], "c_norm")))
    step = jax.nn.softplus(
        jnp.dot(d.astype(dt), wload(ap["w_dt"], dt),
                preferred_element_type=_F32) + ap["dt_bias"].astype(_F32))
    return step, bm, cm


def _out_proj(ap: dict, y: jax.Array, x: jax.Array, z: jax.Array, dt):
    """y, x (..., d_inner) float32, z in ``dt``: the skip, the gate,
    ``W_out``."""
    y = (y + ap["d_skip"].astype(_F32) * x) * jax.nn.silu(z.astype(_F32))
    return y.astype(dt) @ wload(ap["w_out"], dt)


def mamba_decode(a: MambaKind, ap: dict, h: jax.Array, state: jax.Array,
                 tail: jax.Array, active: jax.Array, eps: float, dt):
    """One recurrent step for every lane: h (B, 1, d), ``state`` (B,
    d_state, d_inner) float32, ``tail`` (B, kernel - 1, d_inner). An
    inactive lane's state and tail come out as they went in, bit for
    bit. Returns (y (B, 1, d), state, tail)."""
    xr, z = _in_proj(a, ap, h, dt)
    with jax.named_scope("mamba.conv"):
        window = jnp.concatenate([tail, xr.astype(tail.dtype)], axis=1)
        x = jax.nn.silu(
            jnp.sum(window.astype(_F32) * ap["conv_w"].astype(_F32)[None],
                    axis=1) + ap["conv_b"].astype(_F32))    # (B, d_inner)
        new_tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    step, bm, cm = _selective(a, ap, x, eps, dt)
    with jax.named_scope("mamba.state"):
        A = -jnp.exp(ap["a_log"].astype(_F32))              # (n, c)
        new = jnp.exp(step[:, None, :] * A[None]) * state \
            + (step * x)[:, None, :] * bm[:, :, None]
        y = jnp.sum(new * cm[:, :, None], axis=1)
        new = jnp.where(active[:, None, None], new, state)
    return _out_proj(ap, y, x, z[:, 0], dt)[:, None], new, new_tail


def mamba_scan(x: jax.Array, step: jax.Array, bm: jax.Array, cm: jax.Array,
               a_log: jax.Array):
    """The selective scan over a whole prompt from a zero state: x,
    ``step`` (S, d_inner), ``bm``, ``cm`` (S, d_state), ``a_log``
    (d_state, d_inner), all float32; returns (y (S, d_inner) without
    the skip, the state after the last position (d_state, d_inner)).

    The prompt is cut into chunks of ``MAMBA_CHUNK`` positions. **One
    token loop of a chunk's length runs every chunk at once**, each
    from a zero state: a step works on ``(chunks, d_state, d_inner)``,
    which is enough to be bound by the state's bytes and not by the
    launch of a step (a loop over the prompt's positions one at a time
    moves a 320 KB state 2048 times). What a chunk's entry state
    ``h_in`` adds is then put in by one pass over the chunks in order:
    with ``D_t`` the running sum of ``dt`` inside the chunk,

        y_t += sum_n C_t[n] exp(A[n] D_t) h_in[n]
        h_in' = exp(A[n] D_end) h_in + (the chunk's own end state)

    ``A < 0 <= dt``, so every exponent taken is ``<= 0``: a channel
    that forgets at e^-30 a token underflows to 0 and nothing
    overflows. A position with ``dt`` 0 is a no-op (decay 1, input 0):
    that is how padding is given. The live part is one step's
    ``(chunks, d_state, d_inner)`` or one chunk's ``(chunk, d_state,
    d_inner)``, never the prompt's. A chunk's arithmetic does not
    depend on how many chunks follow, so every rung leaves the same
    state."""
    S, C = x.shape
    N = a_log.shape[0]
    L = min(MAMBA_CHUNK, S)
    pad = -S % L
    if pad:
        x, step, bm, cm = (jnp.pad(t, ((0, pad), (0, 0)))
                           for t in (x, step, bm, cm))
    K = (S + pad) // L
    A = -jnp.exp(a_log)                                     # (N, C)
    x, step, bm, cm = (t.reshape(K, L, -1) for t in (x, step, bm, cm))

    def token(h, xs):
        x, step, bm, cm = xs                # (K, C), (K, C), (K, N), (K, N)
        h = jnp.exp(step[:, None, :] * A[None]) * h \
            + (step * x)[:, None, :] * bm[:, :, None]
        return h, jnp.sum(h * cm[:, :, None], axis=1)

    # the loop runs over a chunk's positions, every chunk a row of a step
    ends, y = jax.lax.scan(
        token, jnp.zeros((K, N, C), _F32),
        tuple(jnp.swapaxes(t, 0, 1) for t in (x, step, bm, cm)),
        unroll=MAMBA_UNROLL)                                # y (L, K, C)
    run = jnp.cumsum(step, axis=1)                          # D_t: (K, L, C)

    def chunk(h_in, xs):
        run, cm, end = xs                   # (L, C), (L, N), (N, C)
        carried = jnp.exp(run[:, None, :] * A[None]) * h_in[None]
        add = jnp.sum(carried * cm[:, :, None], axis=1)     # (L, C)
        return jnp.exp(run[-1][None, :] * A) * h_in + end, add

    h_end, add = jax.lax.scan(chunk, jnp.zeros((N, C), _F32),
                              (run, cm, ends))
    y = jnp.swapaxes(y, 0, 1) + add                         # (K, L, C)
    return y.reshape(K * L, C)[:S], h_end


# One trace and one lowered function a rung, whatever the layers: a
# program's call sites are unrolled in Python, and a Pallas kernel is
# lowered to its Mosaic module in Python at every start.
_kernel_scan = jax.jit(mamba_prompt_scan)


def _scan(x, step, bm, cm, a_log, plen):
    """The prompt's scan by the platform the program is lowered for: on
    a TPU the one-pass kernel, where its tiling takes the shape
    (channels by the 128, positions by the block); anywhere else, and
    for any other shape, :func:`mamba_scan`. ``plen``, the prompt's
    real length, lets the kernel pass over blocks of padding."""
    if not mamba_scan_tiles(x.shape + a_log.shape[:1]):
        return mamba_scan(x, step, bm, cm, a_log)
    return jax.lax.platform_dependent(
        x, step, bm, cm, a_log, plen, tpu=_kernel_scan,
        default=lambda *args: mamba_scan(*args[:-1]))


def mamba_ingest(a: MambaKind, ap: dict, h: jax.Array, valid: jax.Array,
                 eps: float, dt):
    """One prompt's pass through a state-space layer, **from a zero
    state** whatever the slot held: h (1, S, d) padded, ``valid`` (1,
    S) its real positions. A padded position is a no-op of the
    recurrence (``dt`` 0) and the tail is the last ``kernel - 1`` real
    positions, so every padded length leaves what the exact length
    would. Returns (y (1, S, d), state (1, d_state, d_inner), tail (1,
    kernel - 1, d_inner))."""
    xr, z = _in_proj(a, ap, h, dt)
    plen = valid.sum()
    with jax.named_scope("mamba.conv"):
        taps, S = a.conv, xr.shape[1]
        padded = jnp.pad(xr[0], ((taps - 1, 0), (0, 0)))
        filt = ap["conv_w"].astype(_F32)
        x = jax.nn.silu(sum(padded[j:j + S].astype(_F32) * filt[j]
                            for j in range(taps))
                        + ap["conv_b"].astype(_F32))        # (S, d_inner)
        tail = jax.lax.dynamic_slice_in_dim(padded, plen, taps - 1)[None]
    step, bm, cm = _selective(a, ap, x, eps, dt)
    with jax.named_scope("mamba.scan"):
        y, state = _scan(x, jnp.where(valid[0][:, None], step, 0.0),
                         bm, cm, ap["a_log"].astype(_F32), plen)
    return _out_proj(ap, y, x, z[0], dt)[None], state[None], tail
