"""Plain float32 reference of the ``moe-kda-gqa`` family (Solar-Open2-250B,
``model_type: solar_open2``): gated delta-rule linear attention (Kimi
Delta Attention, arXiv:2510.26692, as ``fla``'s ``KimiDeltaAttention``
writes it) in three layers of four, softmax grouped-query attention with
no rotary and an elementwise output gate in the fourth, and in every
layer many small routed experts behind a sigmoid router beside one
shared expert.

Straightforward ``jax.numpy``: no cache, no state carried between calls,
no chunks (the delta rule is a ``lax.scan`` a token at a time), no
sorting or grouped product, float32 with ``HIGHEST`` matmuls. It imports
nothing of the program (``pbs_tpu``); norms, the int8 control's
``matmul``, the seed word and the outer weights are the dense
reference's own pieces; the SwiGLU, a block of experts and its seeded
weights (``expert_block``: each expert keyed by its number in the whole
model) the ``moe-mixed-gqa`` reference's. ``c`` is the configuration file's dict
(Hugging Face key names).

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, pre-norm, no biases but
``dt_bias`` and the router's. Layer ``l`` is softmax if ``l in
gqa_layers``, else KDA.

**KDA layer** (``H`` = ``linear_attn_config.num_heads``, ``dk`` = ``dv``
= ``linear_attn_config.head_dim``, ``K`` = ``short_conv_kernel_size``):

- ``h = rmsnorm(x)``. ``q = silu(conv(h Wq))``, ``k = silu(conv(h Wk))``,
  ``v = silu(conv(h Wv))``, each (H, dk); ``conv`` is a causal depthwise
  convolution of kernel K over positions, one filter a channel
  (``y_t = sum_j c[j] x_{t-K+1+j}``, zeros before the first token).
  ``q``, ``k`` are L2-normalised per head (``x / sqrt(sum x^2 + 1e-6)``);
  ``q`` is then scaled by ``dk^-1/2``.
- Decay, per head and per key channel: ``g = -exp(A_log[head]) *
  softplus((h Wa1) Wa2 + dt_bias)``, ``alpha = exp(g)`` in (0, 1).
- ``beta = 2 * sigmoid(h Wb)``, one a head (``kda_allow_neg_eigval``:
  the factor 2 lets ``1 - beta`` go negative).
- State ``S`` (dk, dv) a head, zero before the first token:
  ``S <- Diag(alpha_t) S``; ``S <- S + beta_t k_t (v_t - S^T k_t)^T``;
  ``o_t = S^T q_t``.
- ``o = rmsnorm_per_head(o_t) * sigmoid((h Wg1) Wg2)``, then
  ``x += concat(o) Wo``.

**Softmax layer:** ``q = h Wq`` as (``num_attention_heads``,
``head_dim``), ``k``, ``v`` as (``num_key_value_heads``, ``head_dim``),
no rotary (``use_rope: false``), causal, scale ``head_dim^-1/2``,
float32 softmax, query head g reads KV head g // (heads / kv heads);
``o * sigmoid(h Wg)`` elementwise with ``Wg`` (d, heads x head_dim);
``x += concat(o) Wo``.

**Every layer's MLP:** ``r = sigmoid(h Wr)`` over all
``deployment.experts_total`` experts; S = the ``num_experts_per_tok``
largest of ``r + bias``; ``w_e = routed_scaling_factor * r_e / sum_S r``
(``norm_topk_prob``); ``y = sum over e in S that are held here of w_e
swiglu_e(h)`` (width ``moe_intermediate_size``) ``+ swiglu_shared(h)``
(width ``n_shared_experts * moe_intermediate_size``). No capacity, no
dropped token. After the last layer: ``rmsnorm``, untied head.

**Departures from the published model, all of them the cut to one chip's
share** (the configuration file states the deployment): this holder has
``n_routed_experts`` of the ``experts_total`` experts of each layer,
from ``deployment.experts_first``, and what an absent expert would add
is left out, here as in the program; the vocabulary is its first
``vocab_size`` rows; the depth is the first ``n_layers`` layers.

**Forms the published config's keys name but do not spell out** (also
under ``assumed`` in the configuration file): the KDA layer is ``fla``'s
(the config gives kernel, heads and head size and nothing else); both
low ranks (``Wa1``/``Wa2``, ``Wg1``/``Wg2``) are ``head_dim`` = 128, the
family's convention (``kda_use_full_proj: false`` read as this low-rank
pair); no bias on ``Wg2``; ``use_gqa_gate`` is an elementwise sigmoid
gate from the normed input before ``Wo``; the router is sigmoid with a
selection bias that does not enter the weights (the family's convention
since Solar Open 1; the config gives ``norm_topk_prob`` and the scale
only); ``intermediate_size: 10240`` names no layer while
``first_k_dense_replace`` is 0; no query/key norm in the softmax layer
(no key for one); seeded weights normal / sqrt(fan_in) as the other
families', ``A_log`` the log of uniform(1, 16) and ``dt_bias`` the
inverse softplus of a step log-uniform in [0.001, 0.1] (``fla``'s own
start: a state that remembers tens of tokens, not one), the router's
bias 0.005 x normal, so that neither is a no-op; these three are float32
whatever type the matrices are held in.

Every expert held is computed for every token and weighted by zero
where the token did not choose it, a block of experts at a time; weights
are regenerated from ``--seed`` a layer (and a block) at a time, so the
reference never holds a model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, outer_weights, rms_norm, seed_word)
from benchmarks.reference.moe_mixed_attn import (  # noqa: F401
    _f32, block_of_experts, expert_block, swiglu)

SOFTMAX_LEAVES = ("wq", "wk", "wv", "wo", "wg")
KDA_LEAVES = ("cq", "ck", "cv", "wa1", "wa2", "a_log", "dt_bias", "wb",
              "wg1", "wg2")
EXPERT_LEAVES = ("router", "router_bias", "ws1", "ws3", "ws2")
_LEAF_ID = {n: i for i, n in enumerate(
    SOFTMAX_LEAVES + KDA_LEAVES + EXPERT_LEAVES)}
#: Experts drawn (and, in the forward, multiplied) at a time.
EXPERT_BLOCK = 8
HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def is_softmax(c: dict, layer: int) -> bool:
    return layer in c["gqa_layers"]


def kda_sizes(c: dict) -> tuple[int, int, int, int]:
    """(heads, head size, convolution kernel, low rank)."""
    lin = c["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"], lin["head_dim"])


def softmax_shapes(c: dict) -> dict:
    d, hd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {"wq": (d, nq), "wk": (d, nkv), "wv": (d, nkv), "wo": (nq, d),
            "wg": (d, nq)}


def kda_shapes(c: dict) -> dict:
    d = c["hidden_size"]
    H, hd, taps, rank = kda_sizes(c)
    w = H * hd
    return {"wq": (d, w), "wk": (d, w), "wv": (d, w), "cq": (taps, w),
            "ck": (taps, w), "cv": (taps, w), "wa1": (d, rank),
            "wa2": (rank, w), "wb": (d, H), "wg1": (d, rank),
            "wg2": (rank, w), "wo": (w, d)}


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


def attn_weights(c: dict, seed, layer: int, at, dtype) -> dict:
    """The mixer's weights of layer ``at``, which is of the kind of
    layer ``layer`` (static)."""
    if is_softmax(c, layer):
        return _draw(c, seed, at, "attn_norm", softmax_shapes(c), dtype)
    H, hd, _, _ = kda_sizes(c)
    out = _draw(c, seed, at, "attn_norm", kda_shapes(c), dtype)
    out["o_norm"] = jnp.ones((hd,), dtype)
    out["a_log"] = jnp.log(jax.random.uniform(
        _leaf_key(seed, "a_log", at), (H,), jnp.float32, 1.0, 16.0))
    step = jnp.exp(jax.random.uniform(
        _leaf_key(seed, "dt_bias", at), (H * hd,), jnp.float32,
        math.log(1e-3), math.log(1e-1)))
    out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    return out


def shared_width(c: dict) -> int:
    return c["n_shared_experts"] * c["moe_intermediate_size"]


def sparse_outer_weights(c: dict, seed, at, dtype) -> dict:
    """Router, its bias and the shared expert of layer ``at``."""
    d, s = c["hidden_size"], shared_width(c)
    total = c["deployment"]["experts_total"]
    out = _draw(c, seed, at, "mlp_norm", {
        "router": (d, total), "ws1": (d, s), "ws3": (d, s), "ws2": (s, d)},
        dtype)
    out["router_bias"] = 0.005 * jax.random.normal(
        _leaf_key(seed, "router_bias", at), (total,), jnp.float32)
    return out


# -- forward ------------------------------------------------------------------


def short_conv(x, filt):
    """x (B, S, C), ``filt`` (K, C): causal, depthwise, the last tap on
    the newest position, zeros before the first."""
    taps, S = filt.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * filt[j] for j in range(taps))


def delta_rule(q, k, v, g, beta, rounded: str = ""):
    """One row's recurrence, a token at a time: q, k, v, g (S, H, D),
    beta (S, H) -> o (S, H, D). ``rounded`` is the second control's (see
    :func:`score_tokens`): ``"dots"`` rounds both operands of the
    state's two products to bfloat16, ``"state"`` also holds the state
    itself in bfloat16 between tokens."""

    def bf16(t):
        # not a pair of casts: XLA:TPU drops those as excess precision
        return jax.lax.reduce_precision(t, 8, 7) if rounded else t

    def step(state, t):
        q, k, v, g, beta = t
        state = state * jnp.exp(g)[..., None]
        seen = jnp.einsum("hkv,hk->hv", bf16(state), bf16(k), precision=HI)
        state = state + (beta[:, None, None] * k[..., None]
                         * (v - seen)[:, None, :])
        if rounded == "state":
            state = bf16(state)
        return state, jnp.einsum("hkv,hk->hv", bf16(state), bf16(q),
                                 precision=HI)

    H, D = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda(c: dict, x, w: dict, quant: bool = False, rounded: str = ""):
    """A KDA layer's mixer on x (B, S, d), float32, with its residual."""
    B, S, _ = x.shape
    H, hd, _, _ = kda_sizes(c)
    h = rms_norm(x, w["attn_norm"], c["rms_norm_eps"])

    def branch(proj, filt):
        y = jax.nn.silu(short_conv(matmul(h, w[proj], quant), w[filt]))
        return y.reshape(B, S, H, hd)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    q = unit(branch("wq", "cq")) / math.sqrt(hd)
    k = unit(branch("wk", "ck"))
    v = branch("wv", "cv")
    low = matmul(matmul(h, w["wa1"], quant), w["wa2"], quant)
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        low + w["dt_bias"]).reshape(B, S, H, hd)
    beta = 2.0 * jax.nn.sigmoid(matmul(h, w["wb"], quant))
    o = jax.lax.map(lambda row: delta_rule(*row, rounded),
                    (q, k, v, g, beta))
    gate = jax.nn.sigmoid(matmul(matmul(h, w["wg1"], quant), w["wg2"], quant))
    o = rms_norm(o, w["o_norm"], c["rms_norm_eps"]) \
        * gate.reshape(B, S, H, hd)
    return x + matmul(o.reshape(B, S, H * hd), w["wo"], quant)


def softmax_attention(c: dict, x, w: dict, quant: bool = False):
    """A softmax layer's mixer on x (B, S, d), float32, with its
    residual; no rotary; one row of the batch at a time."""
    B, S, _ = x.shape
    H, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    h = rms_norm(x, w["attn_norm"], c["rms_norm_eps"])
    q = matmul(h, w["wq"], quant).reshape(B, S, H, hd)
    k = matmul(h, w["wk"], quant).reshape(B, S, nkv, hd)
    v = matmul(h, w["wv"], quant).reshape(B, S, nkv, hd)
    k, v = (jnp.repeat(t, H // nkv, axis=2) for t in (k, v))
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def row(qkv):
        q, k, v = qkv
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(row, (q, k, v)).reshape(B, S, H * hd)
    a = a * jax.nn.sigmoid(matmul(h, w["wg"], quant))
    return x + matmul(a, w["wo"], quant)


def mixer(c: dict, x, w: dict, layer: int, quant: bool = False,
          rounded: str = ""):
    if is_softmax(c, layer):
        return softmax_attention(c, x, w, quant)
    return kda(c, x, w, quant, rounded)


def routing(c: dict, h, router, bias, quant: bool):
    """h (N, d) -> (N, experts_total) weights: ``scale * r_e / sum_S r``
    on a token's chosen experts, zero elsewhere; chosen by ``r + bias``."""
    r = jax.nn.sigmoid(matmul(h, router, quant))
    _, topi = jax.lax.top_k(r + bias, c["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(topi, r.shape[-1], dtype=jnp.float32),
                     axis=-2)
    w = r * chosen
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return c["routed_scaling_factor"] * w


def held_range(c: dict) -> tuple[int, int]:
    return int(c["deployment"]["experts_first"]), int(c["n_routed_experts"])


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant=False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there. ``quant`` is the
    control: ``True`` the harness's, every product in int8; ``"state"``
    or ``"dots"`` this family's second one (``tools/kda_state_control``),
    every product float32 but the recurrent state's (:func:`delta_rule`)."""
    rounded, quant = (quant, False) if isinstance(quant, str) \
        else ("", quant)
    seed = seed_word(seed)
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, held
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)
    steps: dict = {}

    def mixer_step(layer):
        kind = is_softmax(c, layer)
        if kind not in steps:
            steps[kind] = jax.jit(lambda x, at, seed: mixer(
                c, x, _f32(attn_weights(c, seed, layer, at, dtype)), layer,
                quant, rounded))
        return steps[kind]

    @jax.jit
    def route_step(x, at, seed):
        w = _f32(sparse_outer_weights(c, seed, at, dtype))
        h = rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])
        h = h.reshape(-1, h.shape[-1])
        return (h, routing(c, h, w["router"], w["router_bias"], quant),
                swiglu(h, w["ws1"], w["ws3"], w["ws2"], quant))

    @jax.jit
    def block_step(y, h, gate, at, start, seed):
        wb = _f32(expert_block(c, seed, at, start, block, dtype))
        g = jax.lax.dynamic_slice_in_dim(gate, start, block, axis=1)
        return y + block_of_experts(h, g, wb, quant)

    @jax.jit
    def tail(x, outer, rows, cols, candidates):
        h = rms_norm(x[rows, cols], outer["final_norm"].astype(jnp.float32),
                     c["rms_norm_eps"])
        logits = matmul(h, outer["head"].astype(jnp.float32), quant)
        picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
        return jnp.max(logits, -1), jnp.argmax(logits, -1), picked

    x = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(outer["embed"],
                                                       tokens)
    for layer in range(n_layers):
        x = mixer_step(layer)(x, layer, seed)
        h, gate, y = route_step(x, layer, seed)
        for start in range(first, first + held, block):
            y = block_step(y, h, gate, layer, start, seed)
        x = x + y.reshape(x.shape)
    best, arg, picked = tail(x, outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


# -- the program's tree ---------------------------------------------------------


def init_tree(c: dict, seed, n_layers: int, dtype) -> dict:
    """The whole held model as the tree the program serves, a layer at
    a time (``blocks/<NN>/attn/...``, ``blocks/<NN>/mlp/...``): the same
    values :func:`score_tokens` regenerates. A layer's experts are drawn
    a block at a time inside ``lax.map``, so that the float32 draw of a
    leaf never exists for all of a layer's experts."""
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    starts = jnp.arange(first, first + held, block)
    tree: dict = {**outer_weights(c, seed, dtype), "blocks": {}}
    for layer in range(n_layers):
        blocks = jax.lax.map(lambda s, at=layer: expert_block(
            c, seed, at, s, block, dtype), starts)
        mlp = {**sparse_outer_weights(c, seed, layer, dtype),
               **{k: v.reshape((held,) + v.shape[2:])
                  for k, v in blocks.items()}}
        tree["blocks"][f"{layer:02d}"] = {
            "attn": attn_weights(c, seed, layer, layer, dtype), "mlp": mlp}
    return tree
