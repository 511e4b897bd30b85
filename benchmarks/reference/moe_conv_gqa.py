"""Plain float32 reference of the ``moe-conv-gqa`` family (LiquidAI
LFM2-24B-A2B, ``model_type: lfm2_moe``): layers whose mixer is a gated
short convolution **alone** or grouped-query attention with RMS-normed
heads, each followed by a dense SwiGLU (the leading
``num_dense_layers``) or by sigmoid-routed SwiGLU experts with no
shared one; a tied head.

Straightforward ``jax.numpy``: no cache, no tail carried between calls
(the convolution is an explicit sum over the zero-padded sequence), no
sorting or grouped product, float32 with ``HIGHEST`` matmuls. It
imports nothing of the program (``pbs_tpu``); norms, the int8 control's
``matmul`` and the seed word are the dense reference's own pieces, the
tied embedding the ``dense-mamba-mqa`` reference's, the rotary and the
SwiGLU the ``moe-mixed-gqa`` reference's. ``c`` is the configuration
file's dict (Hugging Face key names).

``d`` = ``hidden_size``, eps = ``norm_eps``, RMSNorm ``x / sqrt(mean(x^2)
+ eps) * w`` with weights at one, no bias anywhere. Layer ``i`` of
``layer_types``::

    r = x;  h = rmsnorm_operator(x)
    conv:            [B | C | u] = h W_in        (d -> 3d, in that order)
                     g_t = B_t * u_t
                     c_t = sum_{j<K} w[j] * g_{t-K+1+j}   (depthwise,
                           causal, g_t = 0 for t < 0; K = conv_L_cache)
                     y_t = (C_t * c_t) W_out
    full_attention:  q = rmsnorm_q(h W_q as H heads of hd), k =
                     rmsnorm_k(h W_k as nkv heads of hd), v = h W_v
                     (the norm over the hd dims, one weight vector for
                     every head, before the rotary)
                     q, k = rotary(q, k; rope_theta, the whole head,
                     half-split)
                     y = softmax(q k^T / sqrt(hd), causal) v  W_o
                     (query head g reads KV head g // (H / nkv))
    x = r + y;  f = rmsnorm_ffn(x)
    i < num_dense_layers:   x = x + (silu(f W1) * (f W3)) W2
    else:  s = sigmoid(f W_gate)              (num_experts scores)
           S = the num_experts_per_tok largest of s + expert_bias
           p_e = routed_scaling_factor * s_e / (sum_S s + 1e-6)
           x = x + sum over e in S held here of p_e (silu(f W1_e) *
               (f W3_e)) W2_e
    logits = rmsnorm_final(x) E^T             (E the embedding: tied)

``hd`` = ``hidden_size / num_attention_heads``. No shared expert, no
activation inside the convolution, no step size, no decay.

**Departures from the published model, all of them the cut to one
chip** (the configuration file states the deployment): the depth is
``layer_types``' first ``n_layers`` entries, one pipeline stage of
four, and the head, which the last stage would hold, is read off the
tied embedding here so that the stage yields logits. Every expert of a
layer is held (``deployment.experts_first`` 0, ``num_experts`` of
``experts_total``); the code takes a share as the other families' does.

**Forms the published config's keys name but do not spell out** (also
under ``assumed`` in the configuration file): the head is tied
(``Lfm2MoeConfig``'s default; the catalog row has no
``tie_word_embeddings``); RMSNorm is plain, not ``1 + w``; the
in-projection's columns lie ``B | C | u``; ``norm_topk_prob`` divides
by the chosen scores' sum **plus 1e-6** (the modelling code's own
constant); ``expert_bias`` is float32 and takes part in the choice
alone. Seeded weights normal / sqrt(fan_in) as the other families',
the tied embedding drawn by the head's fan-in, norms at one, each
expert's matrices keyed by its number in the whole model, the
convolution's filter uniform in +-1/sqrt(K) (a Conv1d's start at its
fan-in), ``expert_bias`` 0.005 x normal so that it is no no-op and
leaves the load even.

``quant`` is the control: every matrix product in int8.

The sum over a token's chosen experts is formed as every other family's
reference forms it: every held expert for every token, a block of
experts at a time, weighted by ``p_e``, which is zero where the token
did not choose it (the timed sizes, 8 x 3,072 tokens on 64 experts, do
not fit a gather of four experts' matrices a token). Weights are
regenerated from ``--seed`` a layer (and a block of experts) at a time,
so the reference never holds a model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.dense_mamba_mqa import outer_weights  # noqa: F401
from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, rms_norm, seed_word)
from benchmarks.reference.moe_mixed_attn import (  # noqa: F401
    _f32, block_of_experts, held_range, rope, swiglu)

ATTN_LEAVES = ("wq", "wk", "wv", "wo")
CONV_LEAVES = ("w_in", "conv_w", "w_out")
DENSE_LEAVES = ("w1", "w3", "w2")
EXPERT_LEAVES = ("router", "router_bias", "we1", "we3", "we2")
_LEAF_ID = {n: i for i, n in enumerate(
    ATTN_LEAVES + CONV_LEAVES + DENSE_LEAVES + EXPERT_LEAVES)}
#: Experts drawn (and, in the forward, multiplied) at a time.
EXPERT_BLOCK = 16
#: ``Lfm2MoeSparseMoeBlock`` adds it to the sum it renormalises by.
RENORM_EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def is_conv(c: dict, layer: int) -> bool:
    kind = c["layer_types"][layer]
    assert kind in ("conv", "full_attention"), kind
    return kind == "conv"


def is_dense(c: dict, layer: int) -> bool:
    return layer < c["num_dense_layers"]


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def eps(c: dict) -> float:
    return float(c["norm_eps"])


def attn_shapes(c: dict) -> dict:
    d, hd = c["hidden_size"], head_dim(c)
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {"wq": (d, nq), "wk": (d, nkv), "wv": (d, nkv), "wo": (nq, d)}


def _leaf_key(seed, name: str, layer):
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.random.fold_in(
        jax.random.fold_in(root, _LEAF_ID[name]), layer)


def _draw(c: dict, seed, at, norm: str, shapes: dict, dtype) -> dict:
    """Leaves of layer ``at`` (may be traced): a norm at one and each of
    ``shapes`` drawn from its own key."""
    out = {norm: jnp.ones((c["hidden_size"],), dtype)}
    for name, shape in shapes.items():
        out[name] = _normal(_leaf_key(seed, name, at), shape).astype(dtype)
    return out


def attn_weights(c: dict, seed, at, dtype) -> dict:
    out = _draw(c, seed, at, "attn_norm", attn_shapes(c), dtype)
    out["q_norm"] = jnp.ones((head_dim(c),), dtype)
    out["k_norm"] = jnp.ones((head_dim(c),), dtype)
    return out


def conv_weights(c: dict, seed, at, dtype) -> dict:
    """A convolution layer's mixer: no bias (``conv_bias`` false)."""
    d, taps = c["hidden_size"], c["conv_L_cache"]
    out = _draw(c, seed, at, "attn_norm",
                {"w_in": (d, 3 * d), "w_out": (d, d)}, dtype)
    bound = 1.0 / math.sqrt(taps)
    out["conv_w"] = jax.random.uniform(
        _leaf_key(seed, "conv_w", at), (taps, d), jnp.float32, -bound,
        bound).astype(dtype)
    return out


def mixer_weights(c: dict, seed, layer: int, at, dtype) -> dict:
    """The mixer of layer ``at``, which is of layer ``layer``'s kind
    (static)."""
    return conv_weights(c, seed, at, dtype) if is_conv(c, layer) \
        else attn_weights(c, seed, at, dtype)


def dense_weights(c: dict, seed, at, dtype) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    return _draw(c, seed, at, "mlp_norm",
                 {"w1": (d, f), "w3": (d, f), "w2": (f, d)}, dtype)


def sparse_outer_weights(c: dict, seed, at, dtype) -> dict:
    """Router and its selection bias (float32) of layer ``at``."""
    total = c["deployment"]["experts_total"]
    out = _draw(c, seed, at, "mlp_norm",
                {"router": (c["hidden_size"], total)}, dtype)
    out["router_bias"] = 0.005 * jax.random.normal(
        _leaf_key(seed, "router_bias", at), (total,), jnp.float32)
    return out


def expert_block(c: dict, seed, at, first, count: int, dtype) -> dict:
    """``we1, we3, we2`` of the ``count`` experts from the model's
    expert ``first`` on, of layer ``at``: each expert's matrices are
    keyed by its number in the whole model."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    ids = first + jnp.arange(count)
    out = {}
    for name, shape in (("we1", (d, f)), ("we3", (d, f)), ("we2", (f, d))):
        key = _leaf_key(seed, name, at)
        out[name] = jax.vmap(lambda e: _normal(
            jax.random.fold_in(key, e), shape).astype(dtype))(ids)
    return out


# -- forward ------------------------------------------------------------------


def gated_conv(c: dict, x, w: dict, quant: bool = False):
    """A convolution layer's mixer on x (B, S, d), float32, with its
    residual: the filter as an explicit sum over the zero-padded
    sequence."""
    d, taps, S = c["hidden_size"], c["conv_L_cache"], x.shape[1]
    h = rms_norm(x, w["attn_norm"], eps(c))
    bcu = matmul(h, w["w_in"], quant)
    b, gate, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    g = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    conved = sum(w["conv_w"][j] * g[:, j:j + S] for j in range(taps))
    return x + matmul(gate * conved, w["w_out"], quant)


def attention(c: dict, x, w: dict, quant: bool = False):
    """An attention layer's mixer on x (B, S, d), float32, with its
    residual; queries and keys RMS-normed a head before the rotary; one
    row of the batch at a time."""
    B, S, _ = x.shape
    H, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    rp = c["rope_parameters"]
    h = rms_norm(x, w["attn_norm"], eps(c))
    q = matmul(h, w["wq"], quant).reshape(B, S, H, hd)
    k = matmul(h, w["wk"], quant).reshape(B, S, nkv, hd)
    v = matmul(h, w["wv"], quant).reshape(B, S, nkv, hd)
    q = rope(rms_norm(q, w["q_norm"], eps(c)), rp)
    k = rope(rms_norm(k, w["k_norm"], eps(c)), rp)
    k, v = (jnp.repeat(t, H // nkv, axis=2) for t in (k, v))
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def row(qkv):
        q, k, v = qkv
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(row, (q, k, v)).reshape(B, S, H * hd)
    return x + matmul(a, w["wo"], quant)


def mixer(c: dict, x, w: dict, layer: int, quant: bool = False):
    return gated_conv(c, x, w, quant) if is_conv(c, layer) \
        else attention(c, x, w, quant)


def routing(c: dict, h, router, bias, quant: bool):
    """h (N, d) -> (N, experts_total) weights: ``scale * s_e / (sum_S s
    + 1e-6)`` on a token's chosen experts, zero elsewhere; chosen by
    ``s + bias``."""
    s = jax.nn.sigmoid(matmul(h, router, quant))
    scored = s + bias if c["use_expert_bias"] else s
    _, topi = jax.lax.top_k(scored, c["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(topi, s.shape[-1], dtype=jnp.float32),
                     axis=-2)
    w = s * chosen
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + RENORM_EPS)
    return c["routed_scaling_factor"] * w


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant: bool = False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there."""
    seed = seed_word(seed)
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, held
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)
    steps: dict = {}

    def mixer_step(layer):
        kind = is_conv(c, layer)
        if kind not in steps:
            steps[kind] = jax.jit(lambda x, at, seed: mixer(
                c, x, _f32(mixer_weights(c, seed, layer, at, dtype)), layer,
                quant))
        return steps[kind]

    @jax.jit
    def dense_step(x, at, seed):
        w = _f32(dense_weights(c, seed, at, dtype))
        h = rms_norm(x, w["mlp_norm"], eps(c))
        return x + swiglu(h, w["w1"], w["w3"], w["w2"], quant)

    @jax.jit
    def route_step(x, at, seed):
        w = _f32(sparse_outer_weights(c, seed, at, dtype))
        h = rms_norm(x, w["mlp_norm"], eps(c))
        h = h.reshape(-1, h.shape[-1])
        return h, routing(c, h, w["router"], w["router_bias"], quant)

    @jax.jit
    def block_step(y, h, gate, at, start, seed):
        wb = _f32(expert_block(c, seed, at, start, block, dtype))
        g = jax.lax.dynamic_slice_in_dim(gate, start, block, axis=1)
        return y + block_of_experts(h, g, wb, quant)

    @jax.jit
    def tail(x, outer, rows, cols, candidates):
        h = rms_norm(x[rows, cols], outer["final_norm"].astype(jnp.float32),
                     eps(c))
        logits = matmul(h, outer["embed"].astype(jnp.float32).T, quant)
        picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
        return jnp.max(logits, -1), jnp.argmax(logits, -1), picked

    x = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(outer["embed"],
                                                       tokens)
    for layer in range(n_layers):
        x = mixer_step(layer)(x, layer, seed)
        if is_dense(c, layer):
            x = dense_step(x, layer, seed)
            continue
        h, gate = route_step(x, layer, seed)
        y = jnp.zeros_like(h)
        for start in range(first, first + held, block):
            y = block_step(y, h, gate, layer, start, seed)
        x = x + y.reshape(x.shape)
    best, arg, picked = tail(x, outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


# -- the program's tree ---------------------------------------------------------


def init_tree(c: dict, seed, n_layers: int, dtype) -> dict:
    """The whole held model as the tree the program serves, a layer at
    a time (``blocks/<NN>/attn/...``, ``blocks/<NN>/mlp/...``; no
    ``head``: the embedding is tied): the same values
    :func:`score_tokens` regenerates. An expert layer's experts are
    drawn a block at a time inside ``lax.map``, so that the float32
    draw of a leaf never exists for all of a layer's experts."""
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    starts = jnp.arange(first, first + held, block)
    tree: dict = {**outer_weights(c, seed, dtype), "blocks": {}}
    for layer in range(n_layers):
        if is_dense(c, layer):
            mlp = dense_weights(c, seed, layer, dtype)
        else:
            blocks = jax.lax.map(lambda s, at=layer: expert_block(
                c, seed, at, s, block, dtype), starts)
            mlp = {**sparse_outer_weights(c, seed, layer, dtype),
                   **{k: v.reshape((held,) + v.shape[2:])
                      for k, v in blocks.items()}}
        tree["blocks"][f"{layer:02d}"] = {
            "attn": mixer_weights(c, seed, layer, layer, dtype), "mlp": mlp}
    return tree
