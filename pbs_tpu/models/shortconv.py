"""A gated short convolution as a mixer of its own (LFM2, as
``Lfm2MoeShortConv`` writes it) for the slot engine: what a
``models.plan.ConvKind`` layer of a planned stack computes.

There is no state matrix, no decay and no step size: the convolution
*is* the mixer. On the normed input ``h`` of one position, with ``c =
channels`` and ``K = conv`` taps::

    [B | C | u] = h W_in                       (d -> 3c, in that order)
    g_t = B_t * u_t
    c_t = sum_{j < K} conv_w[j] * g_{t-K+1+j}  (depthwise, causal,
                                                g_t = 0 for t < 0)
    y_t = (C_t * c_t) W_out

No bias and no activation anywhere. A request keeps the last ``K - 1``
rows of ``g`` (its tail, in the compute type: 8 KiB a layer at 2048
channels and three taps) and nothing else, whatever its length.

Two programs of the engine (``models/slot_programs.py``) in the shape
of ``models/mamba2.py``: :func:`conv_decode`, one step for every lane of
a decode tick; :func:`conv_ingest`, a whole padded prompt at once (the
filter is a shifted sum: no scan). A tail has no cursor to mask what
was folded into it, so they keep the recurrent kinds' two invariants:
**padding and idle lanes are no-ops** (the tail a prompt leaves is
``g`` at its last ``K - 1`` *real* positions, zeros where it is
shorter; an inactive lane's tail comes out bit for bit) and
**ingestion starts from zero** whatever the slot held. Both are
``jax.numpy``: a step is two matrix products round a fused elementwise
pass.

``g`` is rounded to the compute type before the filter sees it, in
both programs: the tail holds it in that type, so a position reads the
same ``g`` of its predecessors whether they came with it in a prompt
or through the cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pbs_tpu.models.plan import ConvKind
from pbs_tpu.models.quant import wload

__all__ = ["conv_decode", "conv_ingest"]

_F32 = jnp.float32


def _gates(a: ConvKind, ap: dict, h: jax.Array, dt):
    """``g = B * u`` and ``C``, (..., channels) each, in ``dt``."""
    c = a.channels
    bcu = h @ wload(ap["w_in"], dt)
    return bcu[..., :c] * bcu[..., 2 * c:], bcu[..., c:2 * c]


def _gate_out(ap: dict, gate: jax.Array, conved: jax.Array, dt):
    """``(C * c) W_out``: ``conved`` the filter's float32 sum."""
    return (gate.astype(_F32) * conved).astype(dt) @ wload(ap["w_out"], dt)


def conv_decode(a: ConvKind, ap: dict, h: jax.Array, tail: jax.Array,
                active: jax.Array, eps: float, dt):
    """One step for every lane: h (B, 1, d), ``tail`` (B, conv - 1,
    channels) the lane's last ``g`` rows, oldest first. An inactive
    lane's tail comes out as it went in, bit for bit. Returns (y (B, 1,
    d), tail). ``eps`` is the recurrent kinds' argument; this mixer has
    no norm of its own."""
    del eps
    g, gate = _gates(a, ap, h, dt)
    with jax.named_scope("conv.step"):
        window = jnp.concatenate([tail, g.astype(tail.dtype)], axis=1)
        conved = jnp.sum(window.astype(_F32)
                         * ap["conv_w"].astype(_F32)[None], axis=1)
        new_tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    return _gate_out(ap, gate[:, 0], conved, dt)[:, None], new_tail


def conv_ingest(a: ConvKind, ap: dict, h: jax.Array, valid: jax.Array,
                eps: float, dt):
    """One prompt's pass through the layer, **from a zero tail**
    whatever the slot held: h (1, S, d) padded, ``valid`` (1, S) its
    real positions. The filter is causal, so no real position reads a
    padded one, and the tail is ``g`` at the last ``conv - 1`` real
    positions (zeros where the prompt has fewer): every padded length
    leaves what the exact length would. Returns (y (1, S, d), tail (1,
    conv - 1, channels))."""
    del eps
    g, gate = _gates(a, ap, h, dt)
    with jax.named_scope("conv.filter"):
        taps, S = a.conv, g.shape[1]
        padded = jnp.pad(g[0], ((taps - 1, 0), (0, 0)))
        filt = ap["conv_w"].astype(_F32)
        conved = sum(padded[j:j + S].astype(_F32) * filt[j]
                     for j in range(taps))
        tail = jax.lax.dynamic_slice_in_dim(padded, valid.sum(),
                                            taps - 1)[None]
    return _gate_out(ap, gate[0], conved, dt)[None], tail
