"""Plain float32 reference of the ``dense-mamba-mqa`` family
(AI21-Jamba2-3B, ``model_type: jamba``): Mamba-1 selective state-space
layers as ``JambaMambaMixer`` writes them, a multi-query softmax
attention layer with no rotary where ``layer % attn_layer_period ==
attn_layer_offset``, a dense SwiGLU after every mixer, a tied embedding.

Straightforward ``jax.numpy``: no cache, no state carried between calls,
no chunks (the recurrence is a ``lax.scan`` a token at a time), float32
with ``HIGHEST`` matmuls. It imports nothing of the program
(``pbs_tpu``); norms, the int8 control's ``matmul`` and the seed word are
the dense reference's own pieces, the SwiGLU the ``moe-mixed-gqa``
reference's. ``c`` is the configuration file's dict (Hugging Face key
names).

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, pre-norm, no biases but
the convolution's and ``dt_bias``. Every layer ``i``:
``x <- x + Mix_i(rmsnorm(x))``, then ``x <- x + W2 (silu(W1 u) * W3 u)``
with ``u = rmsnorm(x)`` (``intermediate_size``). Logits:
``rmsnorm(x) @ E^T``, ``E`` the (vocab, d) embedding.

**Mamba layer** on the normed input ``u`` (``C`` = ``mamba_expand`` x
``d``, ``N`` = ``mamba_d_state``, ``K`` = ``mamba_d_conv``, ``R`` =
``mamba_dt_rank``):

1. ``[xr, z] = u W_in``, ``W_in`` (d, 2C).
2. ``x_t = silu(conv_b + sum_j conv_w[j] xr_{t-K+1+j})``: depthwise,
   causal, zeros before the first token.
3. ``[d_t, B_t, C_t] = x_t W_x``, ``W_x`` (C, R + 2N); each RMS-normed
   with a weight of its own (Jamba's addition to Mamba).
4. ``dt_t = softplus(d_t W_dt + dt_bias)``, (C,).
5. ``A = -exp(A_log)``; ``h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c]
   + dt_t[c] B_t[n] x_t[c]``, ``h_0 = 0``.
6. ``y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]``; the layer adds
   ``(y_t * silu(z_t)) W_out``.

**Attention layer:** ``q = u Wq`` as (``num_attention_heads``, hd), ``k``,
``v`` as (``num_key_value_heads``, hd), hd = d / heads, no rotary, causal,
scale ``hd^-1/2``, float32 softmax, every query head of a group on its
one KV head; ``x += concat(o) Wo``; no gate, no q/k norm, no window.

**Departures from the published model:** none but
``max_position_embeddings`` (no layer reads it: there is no rotary).
``A_log`` is held ``(N, C)``, the published ``(C, N)`` transposed: the
same numbers where the program's vector lanes want them.

**Forms the published config's keys name but do not spell out** (also
under ``assumed`` in the configuration file): which layers attend
(``JambaConfig``'s own rule over ``attn_layer_period`` /
``attn_layer_offset``); no rotary (Jamba has none and the config has no
key for one); ``num_experts: 1`` makes every MLP the dense SwiGLU, so
the ``expert_layer_*`` keys select nothing; seeded weights normal /
sqrt(fan_in) as the other families' (the tied embedding by the head's
fan-in, ``d``: logits of order one), norms at one; ``A_log = log(1 ..
N)`` a channel and ``dt_bias`` the inverse softplus of a step
log-uniform in [0.001, 0.1] (Mamba's own start: a state that remembers
tens to hundreds of tokens), ``D`` = 1, the convolution's filter and
bias uniform in +-1/2 (a Conv1d's start at fan-in 4); ``A_log``, ``D``
and ``dt_bias`` float32 whatever type the matrices are held in.

Weights are regenerated from ``--seed`` a layer at a time, so the
reference never holds a model (in float32 it is 11.3 GiB).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, rms_norm, seed_word)
from benchmarks.reference.moe_mixed_attn import _f32, swiglu  # noqa: F401

ATTN_LEAVES = ("wq", "wk", "wv", "wo")
MAMBA_LEAVES = ("w_in", "conv_w", "conv_b", "w_x", "w_dt", "dt_bias",
                "w_out")
MLP_LEAVES = ("w1", "w3", "w2")
_LEAF_ID = {n: i for i, n in enumerate(
    ATTN_LEAVES + MAMBA_LEAVES + MLP_LEAVES)}
HI = jax.lax.Precision.HIGHEST


def is_attention(c: dict, layer: int) -> bool:
    return layer % c["attn_layer_period"] == c["attn_layer_offset"]


def mamba_sizes(c: dict) -> tuple[int, int, int, int]:
    """(d_inner, d_state, dt_rank, convolution kernel)."""
    return (c["mamba_expand"] * c["hidden_size"], c["mamba_d_state"],
            c["mamba_dt_rank"], c["mamba_d_conv"])


def attn_shapes(c: dict) -> dict:
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    nkv = c["num_key_value_heads"] * hd
    return {"wq": (d, d), "wk": (d, nkv), "wv": (d, nkv), "wo": (d, d)}


def mamba_shapes(c: dict) -> dict:
    """The matrices drawn normal / sqrt(fan_in)."""
    d = c["hidden_size"]
    C, N, R, _ = mamba_sizes(c)
    return {"w_in": (d, 2 * C), "w_x": (C, R + 2 * N), "w_dt": (R, C),
            "w_out": (C, d)}


def mlp_shapes(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    return {"w1": (d, f), "w3": (d, f), "w2": (f, d)}


def _leaf_key(seed, name: str, layer):
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.random.fold_in(
        jax.random.fold_in(root, _LEAF_ID[name]), layer)


def _draw(seed, at, shapes: dict, dtype) -> dict:
    return {name: _normal(_leaf_key(seed, name, at), shape).astype(dtype)
            for name, shape in shapes.items()}


def attn_weights(c: dict, seed, layer: int, at, dtype) -> dict:
    """The mixer's weights of layer ``at`` (may be traced), which is of
    the kind of layer ``layer`` (static)."""
    norm = {"attn_norm": jnp.ones((c["hidden_size"],), dtype)}
    if is_attention(c, layer):
        return {**norm, **_draw(seed, at, attn_shapes(c), dtype)}
    C, N, R, taps = mamba_sizes(c)
    out = {**norm, **_draw(seed, at, mamba_shapes(c), dtype)}
    for name, shape in (("conv_w", (taps, C)), ("conv_b", (C,))):
        out[name] = jax.random.uniform(
            _leaf_key(seed, name, at), shape, jnp.float32, -0.5,
            0.5).astype(dtype)
    for name, width in (("dt_norm", R), ("b_norm", N), ("c_norm", N)):
        out[name] = jnp.ones((width,), dtype)
    step = jnp.exp(jax.random.uniform(
        _leaf_key(seed, "dt_bias", at), (C,), jnp.float32,
        math.log(1e-3), math.log(1e-1)))
    out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    out["a_log"] = jnp.log(jnp.broadcast_to(jnp.arange(
        1, N + 1, dtype=jnp.float32)[:, None], (N, C)))
    out["d_skip"] = jnp.ones((C,), jnp.float32)
    return out


def mlp_weights(c: dict, seed, at, dtype) -> dict:
    return {"mlp_norm": jnp.ones((c["hidden_size"],), dtype),
            **_draw(seed, at, mlp_shapes(c), dtype)}


def outer_weights(c: dict, seed, dtype) -> dict:
    """The tied embedding, drawn by the head's fan-in, and the last
    norm."""
    d, v = c["hidden_size"], c["vocab_size"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    return {"embed": (jax.random.normal(key, (v, d), jnp.float32)
                      / math.sqrt(d)).astype(dtype),
            "final_norm": jnp.ones((d,), dtype)}


# -- forward ------------------------------------------------------------------


def short_conv(x, filt, bias):
    """x (B, S, C), ``filt`` (K, C), ``bias`` (C,): causal, depthwise,
    the last tap on the newest position, zeros before the first."""
    taps, S = filt.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * filt[j] for j in range(taps)) + bias


def selective_scan(x, dt, bm, cm, a_log, rounded: bool = False):
    """Steps 5 and 6 without the skip, a token at a time: x, dt (B, S,
    C), bm, cm (B, S, N), ``a_log`` (N, C) -> y (B, S, C). ``rounded``
    (the tests' control) holds the state in bfloat16 between tokens."""
    A = -jnp.exp(a_log)

    def step(h, t):
        x, dt, bm, cm = t                   # (B, C), (B, C), (B, N), (B, N)
        h = jnp.exp(dt[:, None, :] * A[None]) * h \
            + (dt * x)[:, None, :] * bm[:, :, None]
        if rounded:
            # not a pair of casts: XLA drops those as excess precision
            h = jax.lax.reduce_precision(h, 8, 7)
        return h, jnp.sum(h * cm[:, :, None], axis=1)

    B, _, C = x.shape
    _, y = jax.lax.scan(
        step, jnp.zeros((B, a_log.shape[0], C), jnp.float32),
        tuple(jnp.swapaxes(t, 0, 1) for t in (x, dt, bm, cm)))
    return jnp.swapaxes(y, 0, 1)


def mamba(c: dict, x, w: dict, quant: bool = False, rounded: bool = False):
    """A Mamba layer's mixer on x (B, S, d), float32, with its
    residual."""
    C, N, R, _ = mamba_sizes(c)
    eps = c["rms_norm_eps"]
    u = rms_norm(x, w["attn_norm"], eps)
    xz = matmul(u, w["w_in"], quant)
    xr, z = xz[..., :C], xz[..., C:]
    xc = jax.nn.silu(short_conv(xr, w["conv_w"], w["conv_b"]))
    dbc = matmul(xc, w["w_x"], quant)
    d = rms_norm(dbc[..., :R], w["dt_norm"], eps)
    bm = rms_norm(dbc[..., R:R + N], w["b_norm"], eps)
    cm = rms_norm(dbc[..., R + N:], w["c_norm"], eps)
    dt = jax.nn.softplus(matmul(d, w["w_dt"], quant) + w["dt_bias"])
    y = selective_scan(xc, dt, bm, cm, w["a_log"], rounded) \
        + w["d_skip"] * xc
    return x + matmul(y * jax.nn.silu(z), w["w_out"], quant)


def attention(c: dict, x, w: dict, quant: bool = False):
    """A softmax layer's mixer on x (B, S, d), float32, with its
    residual; no rotary; one row of the batch at a time."""
    B, S, d = x.shape
    H, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    u = rms_norm(x, w["attn_norm"], c["rms_norm_eps"])
    q = matmul(u, w["wq"], quant).reshape(B, S, H, hd)
    k = matmul(u, w["wk"], quant).reshape(B, S, nkv, hd)
    v = matmul(u, w["wv"], quant).reshape(B, S, nkv, hd)
    k, v = (jnp.repeat(t, H // nkv, axis=2) for t in (k, v))
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def row(qkv):
        q, k, v = qkv
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(row, (q, k, v)).reshape(B, S, d)
    return x + matmul(a, w["wo"], quant)


def layer(c: dict, x, seed, layer_kind: int, at, dtype, quant: bool = False,
          rounded: bool = False):
    """Layer ``at`` (of the kind of ``layer_kind``) on x (B, S, d)."""
    w = _f32(attn_weights(c, seed, layer_kind, at, dtype))
    x = attention(c, x, w, quant) if is_attention(c, layer_kind) \
        else mamba(c, x, w, quant, rounded)
    w = _f32(mlp_weights(c, seed, at, dtype))
    u = rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])
    return x + swiglu(u, w["w1"], w["w3"], w["w2"], quant)


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant=False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there. ``quant`` is the
    control: ``True`` the harness's, every product in int8; ``"state"``
    the tests', every product float32 and the recurrent state held in
    bfloat16."""
    rounded, quant = (True, False) if quant == "state" else (False, quant)
    seed = seed_word(seed)
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)
    steps: dict = {}

    def step(kind_of):
        kind = is_attention(c, kind_of)
        if kind not in steps:
            steps[kind] = jax.jit(lambda x, at, seed: layer(
                c, x, seed, kind_of, at, dtype, quant, rounded))
        return steps[kind]

    @jax.jit
    def tail(x, outer, rows, cols, candidates):
        h = rms_norm(x[rows, cols], outer["final_norm"].astype(jnp.float32),
                     c["rms_norm_eps"])
        logits = matmul(h, outer["embed"].astype(jnp.float32).T, quant)
        picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
        return jnp.max(logits, -1), jnp.argmax(logits, -1), picked

    x = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(outer["embed"],
                                                       tokens)
    for at in range(n_layers):
        x = step(at)(x, at, seed)
    best, arg, picked = tail(x, outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


# -- the program's tree ---------------------------------------------------------


def init_tree(c: dict, seed, n_layers: int, dtype) -> dict:
    """The whole model as the tree the program serves, a layer at a
    time (``blocks/<NN>/attn/...``, ``blocks/<NN>/mlp/...``; no
    ``head``: the embedding is tied): the same values
    :func:`score_tokens` regenerates."""
    tree: dict = {**outer_weights(c, seed, dtype), "blocks": {}}
    for at in range(n_layers):
        tree["blocks"][f"{at:02d}"] = {
            "attn": attn_weights(c, seed, at, at, dtype),
            "mlp": mlp_weights(c, seed, at, dtype)}
    return tree
