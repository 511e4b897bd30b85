"""Plain float32 reference of the ``moe-mixed-gqa`` family (Laguna-S-2.1,
``model_type: laguna``): window and full attention layers with their own
head counts and rotary settings, a per-head output gate, a leading dense
SwiGLU layer and then layers of many small routed experts beside one
shared expert.

Straightforward ``jax.numpy``: no cache, no ring, no sorting or grouped
product, no scan, float32 with ``HIGHEST`` matmuls. It imports nothing
of the program (``pbs_tpu``); norms, the int8 control's ``matmul``, the
seed word and the outer weights are the dense reference's own pieces.
``c`` is the configuration file's dict (Hugging Face key names).

One layer ``l`` on x (B, S, d), with ``H`` =
``num_attention_heads_per_layer[l]``, ``hd`` = ``head_dim`` (not d / H),
``nkv`` = ``num_key_value_heads``, no biases:

- ``h = rmsnorm(x)``; ``q = h Wq`` as (H, hd); ``k = h Wk``, ``v = h Wv``
  as (nkv, hd).
- Rotary, half-split pairs, from ``rope_parameters[layer_types[l]]``:
  ``default`` is ``theta^(-2i/D)`` on all D = hd dims; ``yarn`` is
  Hugging Face's ``_compute_yarn_parameters`` written out in
  :func:`inv_freq` (per frequency a blend of ``theta^(-2i/D)`` and that
  over ``factor`` by a linear ramp between the two correction dims; cos
  and sin times ``attention_factor``) on the first D = hd x
  ``partial_rotary_factor`` dims of each head, the rest passed through.
- Query head g reads kv head g // (H / nkv); position i sees j <= i, and
  in a ``sliding_attention`` layer only i - ``sliding_window`` < j;
  scale 1/sqrt(hd); softmax in float32.
- ``gate = sigmoid(h Wg)``, Wg (d, H); head g's output times gate[g];
  ``x += concat(heads) Wo``.
- ``h = rmsnorm(x)``. A ``dense`` layer (``mlp_layer_types``): SwiGLU of
  width ``intermediate_size``. A ``sparse`` layer: ``p = softmax(h Wr)``
  over all ``deployment.experts_total`` experts, S = the
  ``num_experts_per_tok`` largest, ``w_e = moe_routed_scaling_factor *
  p_e / sum_S p`` (``norm_topk_prob``), ``y = sum over e in S that are
  held here of w_e * swiglu_e(h)`` (width ``moe_intermediate_size``;
  the weight is on the expert's output) ``+ swiglu_shared(h)`` (width
  ``shared_expert_intermediate_size``). No capacity, no dropped token.
- After the last layer: ``rmsnorm``, untied head, logits over
  ``vocab_size`` ids.

**Departures from the published model, all of them the cut to one chip's
share** (the configuration file states the deployment): this holder has
``num_experts`` of the ``experts_total`` experts of each layer, from
``deployment.experts_first``, and what an absent expert would add is
left out, here as in the program; the vocabulary is its first
``vocab_size`` rows; the depth is the first ``n_layers`` layers.

**Three forms the published config's keys name but do not spell out**
(also under ``assumed`` in the configuration file): the router scores by
softmax (``moe_router_logit_softcapping: 0`` is read as off); the shared
expert's output is added ungated; ``gating: per-head`` is a sigmoid
gate, one scalar a query head, computed from the layer's normed input
and applied to the attention output before ``Wo``. There is no
query/key norm (the config has no key for one).

Every expert held is computed for every token and weighted by zero
where the token did not choose it, a block of experts at a time; weights
are regenerated from ``--seed`` a layer (and a block) at a time, so the
reference never holds a model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, outer_weights, rms_norm, seed_word)

ATTN_LEAVES = ("wq", "wk", "wv", "wo", "wg")
DENSE_LEAVES = ("w1", "w3", "w2")
EXPERT_LEAVES = ("router", "we1", "we3", "we2", "ws1", "ws3", "ws2")
_LEAF_ID = {n: i for i, n in enumerate(
    ATTN_LEAVES + DENSE_LEAVES + EXPERT_LEAVES)}
#: Experts drawn (and, in the forward, multiplied) at a time.
EXPERT_BLOCK = 16
HI = jax.lax.Precision.HIGHEST

def attn_shapes(c: dict, layer: int) -> dict:
    d, hd = c["hidden_size"], c["head_dim"]
    nq = c["num_attention_heads_per_layer"][layer] * hd
    nkv = c["num_key_value_heads"] * hd
    return {"wq": (d, nq), "wk": (d, nkv), "wv": (d, nkv), "wo": (nq, d),
            "wg": (d, nq // hd)}


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
    """Attention weights of layer ``at``, which has the shapes of layer
    ``layer`` (static)."""
    return _draw(c, seed, at, "attn_norm", attn_shapes(c, layer), dtype)


def dense_weights(c: dict, seed, at, dtype) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    return _draw(c, seed, at, "mlp_norm",
                 {"w1": (d, f), "w3": (d, f), "w2": (f, d)}, dtype)


def sparse_outer_weights(c: dict, seed, at, dtype) -> dict:
    """Router and shared expert of layer ``at``."""
    d, s = c["hidden_size"], c["shared_expert_intermediate_size"]
    return _draw(c, seed, at, "mlp_norm", {
        "router": (d, c["deployment"]["experts_total"]),
        "ws1": (d, s), "ws3": (d, s), "ws2": (s, d)}, dtype)


def expert_block(c: dict, seed, at, first, count: int, dtype) -> dict:
    """``we1, we3, we2`` of the ``count`` experts from the model's
    expert ``first`` on, of layer ``at``: each expert's matrices are
    keyed by its number in the whole model, so two holders of one layer
    hold different experts of the same model."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    ids = first + jnp.arange(count)
    out = {}
    for name, shape in (("we1", (d, f)), ("we3", (d, f)), ("we2", (f, d))):
        key = _leaf_key(seed, name, at)
        out[name] = jax.vmap(lambda e: _normal(
            jax.random.fold_in(key, e), shape).astype(dtype))(ids)
    return out


# -- forward ------------------------------------------------------------------


def inv_freq(rp: dict, head_dim: int) -> np.ndarray:
    """Inverse frequencies of one layer type's rotating pairs, written
    out from the published ``rope_parameters`` (not the program's
    table)."""
    dim = int(head_dim * rp.get("partial_rotary_factor", 1))
    base = float(rp["rope_theta"])
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return 1.0 / pos
    assert rp["rope_type"] == "yarn", rp["rope_type"]
    factor, orig = rp["factor"], rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1 - ramp)


def rope(x, rp: dict):
    """x (B, S, H, hd), positions 0..S-1, half-split pairs on the
    leading rotary dims."""
    freqs = inv_freq(rp, x.shape[-1])
    rot = 2 * len(freqs)
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * jnp.asarray(freqs, jnp.float32))
    scale = rp.get("attention_factor", 1.0)
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def attention(c: dict, x, w: dict, layer: int, quant: bool = False):
    """The attention half of layer ``layer`` on x (B, S, d), float32,
    with its residual; one row of the batch at a time."""
    B, S, d = x.shape
    H = c["num_attention_heads_per_layer"][layer]
    nkv, hd = c["num_key_value_heads"], c["head_dim"]
    kind = c["layer_types"][layer]
    rp = c["rope_parameters"][kind]
    h = rms_norm(x, w["attn_norm"], c["rms_norm_eps"])
    q = rope(matmul(h, w["wq"], quant).reshape(B, S, H, hd), rp)
    k = rope(matmul(h, w["wk"], quant).reshape(B, S, nkv, hd), rp)
    v = matmul(h, w["wv"], quant).reshape(B, S, nkv, hd)
    k, v = (jnp.repeat(t, H // nkv, axis=2) for t in (k, v))
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        seen &= i - j < c["sliding_window"]

    def row(qkv):
        q, k, v = qkv
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(row, (q, k, v))
    a = a * jax.nn.sigmoid(matmul(h, w["wg"], quant))[..., None]
    return x + matmul(a.reshape(B, S, H * hd), w["wo"], quant)


def swiglu(h, w1, w3, w2, quant: bool):
    return matmul(jax.nn.silu(matmul(h, w1, quant)) * matmul(h, w3, quant),
                  w2, quant)


def routing(c: dict, h, router, quant: bool):
    """h (N, d) -> (N, experts_total) weights: ``scale * p_e / sum_S p``
    on a token's chosen experts, zero elsewhere."""
    p = jax.nn.softmax(matmul(h, router, quant), axis=-1)
    topv, topi = jax.lax.top_k(p, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    chosen = jax.nn.one_hot(topi, p.shape[-1], dtype=jnp.float32)
    return c["moe_routed_scaling_factor"] * jnp.sum(
        topv[..., None] * chosen, axis=-2)


def block_of_experts(h, gate, wb: dict, quant: bool):
    """Sum over one block's experts of gate[:, e] * swiglu_e(h)."""
    y = jnp.zeros_like(h)
    for e in range(wb["we1"].shape[0]):
        y = y + gate[:, e:e + 1] * swiglu(
            h, wb["we1"][e], wb["we3"][e], wb["we2"][e], quant)
    return y


def _f32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def held_range(c: dict) -> tuple[int, int]:
    return int(c["deployment"]["experts_first"]), int(c["num_experts"])


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant: bool = False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there."""
    seed = seed_word(seed)
    first, held = held_range(c)
    assert held % EXPERT_BLOCK == 0 or held < EXPERT_BLOCK, held
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)
    steps: dict = {}

    def attn_step(layer):
        key = (c["layer_types"][layer],
               c["num_attention_heads_per_layer"][layer])
        if key not in steps:
            steps[key] = jax.jit(lambda x, at, seed: attention(
                c, x, _f32(attn_weights(c, seed, layer, at, dtype)), layer,
                quant))
        return steps[key]

    @jax.jit
    def dense_step(x, at, seed):
        w = _f32(dense_weights(c, seed, at, dtype))
        h = rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])
        return x + swiglu(h, w["w1"], w["w3"], w["w2"], quant)

    @jax.jit
    def route_step(x, at, seed):
        w = _f32(sparse_outer_weights(c, seed, at, dtype))
        h = rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])
        h = h.reshape(-1, h.shape[-1])
        return (h, routing(c, h, w["router"], quant),
                swiglu(h, w["ws1"], w["ws3"], w["ws2"], quant))

    block = min(EXPERT_BLOCK, held)

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
        x = attn_step(layer)(x, layer, seed)
        if c["mlp_layer_types"][layer] == "dense":
            x = dense_step(x, layer, seed)
            continue
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
    values :func:`score_tokens` regenerates. A sparse layer's experts
    are drawn a block at a time inside ``lax.map``, so that the float32
    draw of a leaf never exists for all of a layer's experts."""
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    starts = jnp.arange(first, first + held, block)
    tree: dict = {**outer_weights(c, seed, dtype), "blocks": {}}
    for layer in range(n_layers):
        if c["mlp_layer_types"][layer] == "dense":
            mlp = dense_weights(c, seed, layer, dtype)
        else:
            blocks = jax.lax.map(lambda s, at=layer: expert_block(
                c, seed, at, s, block, dtype), starts)
            mlp = {**sparse_outer_weights(c, seed, layer, dtype),
                   **{k: v.reshape((held,) + v.shape[2:])
                      for k, v in blocks.items()}}
        tree["blocks"][f"{layer:02d}"] = {
            "attn": attn_weights(c, seed, layer, layer, dtype), "mlp": mlp}
    return tree
