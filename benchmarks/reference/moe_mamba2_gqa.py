"""Plain float32 reference of the ``moe-mamba2-gqa`` family
(NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type: nemotron_h``): blocks
that are a Mamba-2 mixer, a grouped-query attention layer or a layer of
routed experts **alone**, each behind one RMSNorm and one residual add,
in the order ``hybrid_override_pattern`` spells (``M``, ``*``, ``E``).

Straightforward ``jax.numpy``: no cache, no state carried between calls,
no chunks (the state-space recurrence is a ``lax.scan`` a position at a
time), no sorting or grouped product, float32 with ``HIGHEST`` matmuls.
It imports nothing of the program (``pbs_tpu``); norms, the int8
control's ``matmul``, the seed word and the outer weights are the dense
reference's own pieces, the sigmoid routing and the causal convolution
the ``moe-kda-gqa`` reference's. ``c`` is the configuration file's dict
(Hugging Face key names).

``d`` = ``hidden_size``, eps = ``layer_norm_epsilon``, no biases but the
convolution's and ``dt_bias``. Block ``i``: ``x <- x +
f_i(rmsnorm_i(x))`` with ``f_i`` by the ``i``-th letter of the pattern.
After the last block ``rmsnorm``, then the untied head.

**``M``, Mamba-2** (``H`` = ``mamba_num_heads``, ``P`` =
``mamba_head_dim``, ``G`` = ``n_groups``, ``N`` = ``ssm_state_size``,
``K`` = ``conv_kernel``; ``c`` = ``H P``):

1. ``[z (c) | xBC (c + 2 G N) | dt (H)] = u W_in``.
2. ``xBC_t <- silu(conv_b + sum_j conv_w[j] xBC_{t-K+1+j})``: depthwise,
   causal, zeros before the first token; split ``x`` (H, P), ``B`` and
   ``C`` (G, N); head ``j`` reads group ``j // (H / G)``.
3. ``dt_j = softplus(dt_j + dt_bias_j)``, ``a_j = -exp(A_log_j)``.
4. ``H_j`` (P, N) float32, zero before the first token:
   ``H_j <- exp(dt_j a_j) H_j + dt_j x_j (outer) B_g``;
   ``y_j = H_j C_g + D_j x_j``.
5. ``y <- rmsnorm_groups(y * silu(z))``: the mean square over each of
   the ``G`` groups of ``c / G`` channels, one weight a channel;
   ``f = y W_out``.

**``*``, attention:** ``q = u Wq`` as (``num_attention_heads``,
``head_dim``), ``k``, ``v`` as (``num_key_value_heads``, ``head_dim``),
no rotary and no other positional signal, causal, scale
``head_dim^-1/2``, float32 softmax, query head g reads KV head g //
(heads / kv heads); ``f = concat(o) Wo``; no gate, no q/k norm.

**``E``, experts:** ``r = sigmoid(u Wr)`` over all
``deployment.experts_total``; S = the ``num_experts_per_tok`` largest of
``r + bias`` (``n_group`` 1, ``topk_group`` 1: no group limit); ``w_e =
routed_scaling_factor r_e / sum_S r``; ``f = sum over e in S that are
held here of w_e relu(u W1_e)^2 W2_e`` (width
``moe_intermediate_size``; **no gate matrix**) ``+ relu(u Ws1)^2 Ws2``
(width ``moe_shared_expert_intermediate_size``, unweighted). No
capacity, no dropped token.

**Departures from the published model, all of them the cut to one chip's
share** (the configuration file states the deployment): this holder has
``n_routed_experts`` of the ``experts_total`` experts of each expert
block, from ``deployment.experts_first``, and what an absent expert
would add is left out, here as in the program; the vocabulary is its
first ``vocab_size`` rows; the depth is the pattern's first ``n_layers``
letters.

**Forms the published config's keys name but do not spell out** (also
under ``assumed`` in the configuration file):

- the attention layers carry no rotary although the config keeps
  ``rope_theta`` and ``partial_rotary_factor``: the ``nemotron_h``
  modelling code passes no position to its attention (the Mamba layers
  carry position);
- ``d_inner`` is ``mamba_num_heads x mamba_head_dim`` = 4096, not
  ``expand x hidden_size`` = 5376: the modelling code's rule (``expand``
  is unused);
- ``W_in``'s columns lie ``[z | x | B | C | dt]`` and the convolution
  runs over ``x``, ``B`` and ``C`` together;
- the gated norm multiplies by ``silu(z)`` **before** it normalises, a
  group at a time (``norm_before_gate`` false);
- ``dt`` is not clamped: ``time_step_min/max/floor`` set ``dt_bias``'s
  start, not a limit (``time_step_limit`` is (0, inf));
- ``A_log``, ``D``, ``dt_bias`` and the state are float32 whatever type
  the matrices are held in (NVIDIA's serving recipe for this model asks
  for a float32 state cache);
- seeded weights normal / sqrt(fan_in) as the other families', norms at
  one; ``A_log`` the log of uniform(1, 16) a head and ``dt_bias`` the
  inverse softplus of a step log-uniform in [0.001, 0.1] (Mamba-2's own
  start, inside ``time_step_min/max``: a state that remembers tens to
  hundreds of tokens; one that forgets in a step checks nothing), ``D``
  = 1, the convolution's filter and bias uniform in +-1/2 (a Conv1d's
  start at fan-in 4), the router's bias 0.005 x normal so that it is no
  no-op.

``quant`` is the control: ``True`` the harness's, every matrix product
in int8; ``"state"`` this family's second one
(``tools/mamba2_state_control.py``): every product float32 and the
state ``H`` held in bfloat16 between tokens.

Every expert held is computed for every token and weighted by zero
where the token did not choose it, a block of experts at a time; weights
are regenerated from ``--seed`` a block (and a block of experts) at a
time, so the reference never holds a model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, outer_weights, rms_norm, seed_word)
from benchmarks.reference.moe_kda_gqa import (  # noqa: F401
    _f32, held_range, routing, short_conv)

ATTN_LEAVES = ("wq", "wk", "wv", "wo")
MAMBA_LEAVES = ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "w_out")
EXPERT_LEAVES = ("router", "router_bias", "we1", "we2", "ws1", "ws2")
_LEAF_ID = {n: i for i, n in enumerate(
    ATTN_LEAVES + MAMBA_LEAVES + EXPERT_LEAVES)}
#: Experts drawn (and, in the forward, multiplied) at a time.
EXPERT_BLOCK = 8
HI = jax.lax.Precision.HIGHEST


def pattern(c: dict, n_layers: int) -> str:
    """The first ``n_layers`` letters: ``M`` Mamba-2, ``*`` attention,
    ``E`` experts."""
    letters = c["hybrid_override_pattern"][:n_layers]
    assert len(letters) == n_layers and set(letters) <= set("M*E"), letters
    return letters


def mamba_sizes(c: dict) -> tuple[int, int, int, int, int]:
    """(heads, head size, groups, state size, convolution kernel)."""
    return (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
            c["ssm_state_size"], c["conv_kernel"])


def eps(c: dict) -> float:
    return float(c["layer_norm_epsilon"])


def attn_shapes(c: dict) -> dict:
    d, hd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {"wq": (d, nq), "wk": (d, nkv), "wv": (d, nkv), "wo": (nq, d)}


def _leaf_key(seed, name: str, layer):
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.random.fold_in(
        jax.random.fold_in(root, _LEAF_ID[name]), layer)


def _draw(c: dict, seed, at, norm: str, shapes: dict, dtype) -> dict:
    """Leaves of block ``at`` (may be traced): a norm at one and each of
    ``shapes`` drawn from its own key."""
    out = {norm: jnp.ones((c["hidden_size"],), dtype)}
    for name, shape in shapes.items():
        out[name] = _normal(_leaf_key(seed, name, at), shape).astype(dtype)
    return out


def attn_weights(c: dict, seed, at, dtype) -> dict:
    return _draw(c, seed, at, "attn_norm", attn_shapes(c), dtype)


def mamba_weights(c: dict, seed, at, dtype) -> dict:
    """A Mamba-2 block's weights; ``a_log``, ``dt_bias`` and ``d_skip``
    float32 whatever ``dtype`` is."""
    d = c["hidden_size"]
    H, P, G, N, K = mamba_sizes(c)
    inner, conv = H * P, H * P + 2 * G * N
    out = _draw(c, seed, at, "attn_norm", {
        "w_in": (d, inner + conv + H), "w_out": (inner, d)}, dtype)
    out["conv_w"] = jax.random.uniform(
        _leaf_key(seed, "conv_w", at), (K, conv), jnp.float32, -0.5,
        0.5).astype(dtype)
    out["conv_b"] = jax.random.uniform(
        _leaf_key(seed, "conv_b", at), (conv,), jnp.float32, -0.5,
        0.5).astype(dtype)
    out["g_norm"] = jnp.ones((inner,), dtype)
    out["a_log"] = jnp.log(jax.random.uniform(
        _leaf_key(seed, "a_log", at), (H,), jnp.float32, 1.0, 16.0))
    step = jnp.exp(jax.random.uniform(
        _leaf_key(seed, "dt_bias", at), (H,), jnp.float32,
        math.log(float(c["time_step_min"])),
        math.log(float(c["time_step_max"]))))
    out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
    out["d_skip"] = jnp.ones((H,), jnp.float32)
    return out


def sparse_outer_weights(c: dict, seed, at, dtype) -> dict:
    """Router, its bias and the shared expert of block ``at``."""
    d, s = c["hidden_size"], c["moe_shared_expert_intermediate_size"]
    total = c["deployment"]["experts_total"]
    out = _draw(c, seed, at, "mlp_norm", {
        "router": (d, total), "ws1": (d, s), "ws2": (s, d)}, dtype)
    out["router_bias"] = 0.005 * jax.random.normal(
        _leaf_key(seed, "router_bias", at), (total,), jnp.float32)
    return out


def expert_block(c: dict, seed, at, first, count: int, dtype) -> dict:
    """``we1, we2`` of the ``count`` experts from the model's expert
    ``first`` on, of block ``at``: each expert's matrices are keyed by
    its number in the whole model, so two holders of one block hold
    different experts of the same model."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    ids = first + jnp.arange(count)
    out = {}
    for name, shape in (("we1", (d, f)), ("we2", (f, d))):
        key = _leaf_key(seed, name, at)
        out[name] = jax.vmap(lambda e: _normal(
            jax.random.fold_in(key, e), shape).astype(dtype))(ids)
    return out


# -- forward ------------------------------------------------------------------


def recurrence(x, dt, bm, cm, a_log, rounded: bool = False):
    """The state-space recurrence a position at a time, every row of
    the batch in step: x (B, S, H, P), ``dt`` (B, S, H), ``bm``, ``cm``
    (B, S, G, N), ``a_log`` (H,) -> y (B, S, H, P) without the skip.
    ``rounded`` is the second control's: the state is held in bfloat16
    between tokens."""
    B, S, H, P = x.shape
    G, N = bm.shape[2:]
    A = -jnp.exp(a_log)

    def step(h, t):
        x, dt, bm, cm = t
        bh, ch = (jnp.repeat(v, H // G, axis=1) for v in (bm, cm))
        h = jnp.exp(dt * A)[..., None, None] * h \
            + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
        if rounded:
            # not a pair of casts: XLA:TPU drops those as excess precision
            h = jax.lax.reduce_precision(h, 8, 7)
        return h, jnp.sum(h * ch[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32),
                        tuple(jnp.swapaxes(t, 0, 1)
                              for t in (x, dt, bm, cm)))
    return jnp.swapaxes(y, 0, 1)


def mamba2(c: dict, x, w: dict, quant: bool = False, rounded: bool = False):
    """A Mamba-2 block on x (B, S, d), float32, with its residual."""
    B, S, _ = x.shape
    H, P, G, N, _ = mamba_sizes(c)
    inner, gn = H * P, G * N
    u = rms_norm(x, w["attn_norm"], eps(c))
    zxd = matmul(u, w["w_in"], quant)
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * gn],
                  zxd[..., 2 * inner + 2 * gn:])
    xbc = jax.nn.silu(short_conv(xbc, w["conv_w"]) + w["conv_b"])
    xs = xbc[..., :inner].reshape(B, S, H, P)
    bm = xbc[..., inner:inner + gn].reshape(B, S, G, N)
    cm = xbc[..., inner + gn:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(xs, dt, bm, cm, w["a_log"], rounded) \
        + w["d_skip"][:, None] * xs
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    g = y.reshape(B, S, G, inner // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps(c))
    y = g.reshape(B, S, inner) * w["g_norm"]
    return x + matmul(y, w["w_out"], quant)


def attention(c: dict, x, w: dict, quant: bool = False):
    """An attention block on x (B, S, d), float32, with its residual;
    no rotary; one row of the batch at a time."""
    B, S, _ = x.shape
    H, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    u = rms_norm(x, w["attn_norm"], eps(c))
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

    a = jax.lax.map(row, (q, k, v)).reshape(B, S, H * hd)
    return x + matmul(a, w["wo"], quant)


def relu2(h, w1, w2, quant: bool):
    """The ungated MLP: ``relu(h W1)^2 W2``."""
    return matmul(jnp.square(jax.nn.relu(matmul(h, w1, quant))), w2, quant)


def block_of_experts(h, gate, wb: dict, quant: bool):
    """Sum over one block's experts of gate[:, e] * relu2_e(h)."""
    y = jnp.zeros_like(h)
    for e in range(wb["we1"].shape[0]):
        y = y + gate[:, e:e + 1] * relu2(h, wb["we1"][e], wb["we2"][e],
                                         quant)
    return y


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant=False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there. ``quant`` is the
    control (module docstring)."""
    rounded, quant = (True, False) if quant == "state" else (False, quant)
    seed = seed_word(seed)
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, held
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)

    @jax.jit
    def mamba_step(x, at, seed):
        return mamba2(c, x, _f32(mamba_weights(c, seed, at, dtype)), quant,
                      rounded)

    @jax.jit
    def attn_step(x, at, seed):
        return attention(c, x, _f32(attn_weights(c, seed, at, dtype)),
                         quant)

    @jax.jit
    def route_step(x, at, seed):
        w = _f32(sparse_outer_weights(c, seed, at, dtype))
        h = rms_norm(x, w["mlp_norm"], eps(c))
        h = h.reshape(-1, h.shape[-1])
        return (h, routing(c, h, w["router"], w["router_bias"], quant),
                relu2(h, w["ws1"], w["ws2"], quant))

    @jax.jit
    def block_step(y, h, gate, at, start, seed):
        wb = _f32(expert_block(c, seed, at, start, block, dtype))
        g = jax.lax.dynamic_slice_in_dim(gate, start, block, axis=1)
        return y + block_of_experts(h, g, wb, quant)

    @jax.jit
    def tail(x, outer, rows, cols, candidates):
        h = rms_norm(x[rows, cols], outer["final_norm"].astype(jnp.float32),
                     eps(c))
        logits = matmul(h, outer["head"].astype(jnp.float32), quant)
        picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
        return jnp.max(logits, -1), jnp.argmax(logits, -1), picked

    x = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(outer["embed"],
                                                       tokens)
    for at, letter in enumerate(pattern(c, n_layers)):
        if letter == "M":
            x = mamba_step(x, at, seed)
        elif letter == "*":
            x = attn_step(x, at, seed)
        else:
            h, gate, y = route_step(x, at, seed)
            for start in range(first, first + held, block):
                y = block_step(y, h, gate, at, start, seed)
            x = x + y.reshape(x.shape)
    best, arg, picked = tail(x, outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


# -- the program's tree ---------------------------------------------------------


def init_tree(c: dict, seed, n_layers: int, dtype) -> dict:
    """The whole held model as the tree the program serves, a block at
    a time (``blocks/<NN>/attn/...`` for a mixer, ``blocks/<NN>/mlp/...``
    for an expert block: a block has the one): the same values
    :func:`score_tokens` regenerates. A block's experts are drawn a
    block of them at a time inside ``lax.map``, so that the float32 draw
    of a leaf never exists for all of a block's experts."""
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    starts = jnp.arange(first, first + held, block)
    tree: dict = {**outer_weights(c, seed, dtype), "blocks": {}}
    for at, letter in enumerate(pattern(c, n_layers)):
        if letter == "M":
            part = {"attn": mamba_weights(c, seed, at, dtype)}
        elif letter == "*":
            part = {"attn": attn_weights(c, seed, at, dtype)}
        else:
            blocks = jax.lax.map(lambda s, at=at: expert_block(
                c, seed, at, s, block, dtype), starts)
            part = {"mlp": {
                **sparse_outer_weights(c, seed, at, dtype),
                **{k: v.reshape((held,) + v.shape[2:])
                   for k, v in blocks.items()}}}
        tree["blocks"][f"{at:02d}"] = part
    return tree
