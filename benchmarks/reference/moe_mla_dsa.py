"""Plain float32 reference of the ``moe-mla-dsa`` family (GLM-5,
``model_type: glm_moe_dsa``): multi-head latent attention whose queries
attend only the positions a learned indexer picks (DeepSeek sparse
attention), a leading dense SwiGLU layer and then layers of many routed
experts behind a sigmoid router beside one shared expert.

Straightforward ``jax.numpy``: no cache, no absorbed form (every head's
keys and values are read off the latent rows), no bisection (the choice
is ``lax.top_k``), no sorting or grouped product, float32 with
``HIGHEST`` matmuls, one row of the batch and one block of queries at a
time so that a request of nine thousand positions fits. It imports
nothing of the program (``pbs_tpu``); norms, the int8 control's
``matmul``, the seed word and the outer weights are the dense
reference's own pieces, the SwiGLU, the dense layer, a block of experts
and its seeded weights the ``moe-mixed-gqa`` reference's, the sigmoid
router with its selection bias and the shared expert the ``moe-kda-gqa``
reference's. ``c`` is the configuration file's dict (Hugging Face key
names).

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, pre-norm, no biases but
the indexer's LayerNorm's and the router's. ``H`` =
``num_attention_heads``, ``n`` = ``qk_nope_head_dim``, ``e`` =
``qk_rope_head_dim``, ``v`` = ``v_head_dim``; rotary ``theta`` =
``rope_parameters.rope_theta``, ``rope_type`` default, **adjacent
pairs** ``(2i, 2i + 1)`` turned together (``rope_interleave``; the
indexer's too, ``indexer_rope_interleave``), on ``e`` dims; no YaRN, so
the softmax's scale is ``(n + e)^-1/2`` and nothing else.

**The mixer**, on ``h = rmsnorm(x)`` at position ``t``:

- ``c_q = rmsnorm(h W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``, H heads
  of ``[q_n (n) | q_r (e)]``, ``q_r`` turned at ``t``.
- ``[c_kv (kv_lora_rank) | k_r (e)] = h W_kva``; ``c_kv <-
  rmsnorm(c_kv)``; ``k_r`` turned at ``t``, one for all heads. Head i:
  ``[k_n,i (n) | v_i (v)] = c_kv W_kvb,i``.
- Indexer (``J`` = ``index_n_heads`` heads of ``D`` =
  ``index_head_dim``): ``q^I = c_q W^I_q`` as (J, D), the first ``e`` of
  each turned; ``k^I = layernorm(h W^I_k)`` (D; weight and bias), its
  first ``e`` turned; ``w = h W^I_w (J D)^-1/2`` (J).
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])`` for ``s <= t``.
- ``S_t`` = the ``index_topk`` positions ``s <= t`` of largest
  ``I[t, s]`` (all of them while ``t < index_topk``; a tie at the last
  place lets every tied position in).
- ``score[t, s, i] = (q_n,i . k_n,i[s] + q_r,i . k_r[s]) (n + e)^-1/2``
  over ``s in S_t`` only; float32 softmax over ``S_t``; ``o_i = sum_s p
  v_i[s]``; ``x += concat_i(o_i) W_o``. No output gate.

**The MLP:** layers below ``first_k_dense_replace`` a dense SwiGLU of
``intermediate_size``. The others: ``r = sigmoid(h W_r)`` over all
``deployment.experts_total`` experts; the ``num_experts_per_tok`` largest
of ``r + bias`` are chosen (``n_group`` 1, ``topk_group`` 1: no group
limit); ``w_e = routed_scaling_factor * r_e / sum_chosen r``; ``y = sum
over chosen e that are held here of w_e swiglu_e(h)`` (width
``moe_intermediate_size``) ``+ swiglu_shared(h)`` (width
``n_shared_experts * moe_intermediate_size``). No capacity, no dropped
token. After the last layer: ``rmsnorm``, untied head.

**Departures from the published model, all of them the cut to one chip's
share** (the configuration file states the deployment): this holder has
``n_routed_experts`` of the ``experts_total`` experts of each expert
layer, from ``deployment.experts_first``, and what an absent expert
would add is left out, here as in the program; the vocabulary is its
first ``vocab_size`` rows; the depth is the first ``n_layers`` layers, of
which the first ``first_k_dense_replace`` are dense.

**Forms the published config's keys name but do not spell out** (also
under ``assumed`` in the configuration file):

- the indexer is DeepSeek-V3.2-Exp's published ``Indexer`` (the config
  names its sizes, not its form): its query from the query latent, one
  key a position from the layer's input through a LayerNorm, a weight a
  head from the layer's input, relu, the weighted sum;
- its Hadamard rotation of ``q^I`` and ``k^I`` is not written: an
  orthogonal map applied to both leaves every ``q^I . k^I`` as it was;
- its FP8 rounding is a deployment's precision, not the model's (the
  configuration states bfloat16);
- the first ``e`` = 64 of the indexer's 128 channels turn, as there;
- ``rope_interleave`` is read as adjacent pairs;
- the LayerNorm's epsilon is ``rms_norm_eps`` (the config has no other);
- the multi-token-prediction layer (``num_nextn_predict_layers`` 1,
  layer 78) is a drafting head that a server without self-drafting never
  runs, and is left out;
- seeded weights normal / sqrt(fan_in) as the other families', norms at
  one, the LayerNorm's bias 0.1 x normal and the router's bias 0.005 x
  normal so that neither is a no-op.

``quant`` in :func:`score_tokens` names the controls, which both have to
fail the cell's limit: ``True`` the harness's, every weight product in
int8; ``"dense"`` this family's second one, the choice left out (every
``s <= t`` attended), every product float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, outer_weights, rms_norm, seed_word)
from benchmarks.reference.moe_kda_gqa import (  # noqa: F401
    held_range, routing, shared_width, sparse_outer_weights)
from benchmarks.reference.moe_mixed_attn import (  # noqa: F401
    _f32, block_of_experts, dense_weights, expert_block, swiglu)

MIXER_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wi_q", "wi_k",
                "ik_bias", "wi_w")
#: Leaf numbers of their own, clear of the other references' tables.
_LEAF_ID = {n: 100 + i for i, n in enumerate(MIXER_LEAVES)}
#: Experts drawn (and, in the forward, multiplied) at a time.
EXPERT_BLOCK = 4
#: Queries scored, chosen for and attended at a time.
QUERY_BLOCK = 128
HI = jax.lax.Precision.HIGHEST


def mixer_sizes(c: dict) -> dict:
    return {"H": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "kvr": c["kv_lora_rank"], "n": c["qk_nope_head_dim"],
            "e": c["qk_rope_head_dim"], "v": c["v_head_dim"],
            "J": c["index_n_heads"], "D": c["index_head_dim"],
            "topk": c["index_topk"]}


def mixer_shapes(c: dict) -> dict:
    d, z = c["hidden_size"], mixer_sizes(c)
    return {"wq_a": (d, z["qr"]), "wq_b": (z["qr"], z["H"] * (z["n"] + z["e"])),
            "wkv_a": (d, z["kvr"] + z["e"]),
            "wkv_b": (z["kvr"], z["H"] * (z["n"] + z["v"])),
            "wo": (z["H"] * z["v"], d), "wi_q": (z["qr"], z["J"] * z["D"]),
            "wi_k": (d, z["D"]), "wi_w": (d, z["J"])}


def is_dense(c: dict, layer: int) -> bool:
    return layer < c["first_k_dense_replace"]


def _leaf_key(seed, name: str, layer):
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.random.fold_in(
        jax.random.fold_in(root, _LEAF_ID[name]), layer)


def attn_weights(c: dict, seed, at, dtype) -> dict:
    """The mixer's weights of layer ``at`` (may be traced)."""
    z = mixer_sizes(c)
    out = {"attn_norm": jnp.ones((c["hidden_size"],), dtype),
           "q_norm": jnp.ones((z["qr"],), dtype),
           "kv_norm": jnp.ones((z["kvr"],), dtype),
           "ik_norm": jnp.ones((z["D"],), dtype),
           "ik_bias": (0.1 * jax.random.normal(
               _leaf_key(seed, "ik_bias", at), (z["D"],),
               jnp.float32)).astype(dtype)}
    for name, shape in mixer_shapes(c).items():
        out[name] = _normal(_leaf_key(seed, name, at), shape).astype(dtype)
    return out


# -- forward ------------------------------------------------------------------


def turn(x, theta: float, rot: int):
    """Rotary on the leading ``rot`` dims of x (S, ..., D) at positions
    0..S-1, adjacent pairs ``(2i, 2i + 1)`` turned together; the rest
    pass through."""
    S, half = x.shape[0], rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    shape = (S,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x0, x1 = x[..., 0:rot:2], x[..., 1:rot:2]
    pairs = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return jnp.concatenate(
        [pairs.reshape(x.shape[:-1] + (rot,)), x[..., rot:]], axis=-1)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def chosen_positions(index, seen, topk: int):
    """``index`` (Q, S) float32, ``seen`` (Q, S) the causal mask: the
    mask of each query's ``topk`` best seen positions (all of them where
    it sees no more; ties at the last place all in)."""
    if index.shape[-1] <= topk:
        return seen
    index = jnp.where(seen, index, -jnp.inf)
    last = jax.lax.top_k(index, topk)[0][:, -1:]
    return seen & (index >= last)


def mixer_row(c: dict, x, w: dict, quant: bool = False,
              dense: bool = False, picked: bool = False):
    """The mixer on one row x (S, d), float32, without its residual.
    ``dense`` leaves the choice out; ``picked`` returns the (S, S) mask
    of the positions each query attends instead."""
    S = x.shape[0]
    z, eps = mixer_sizes(c), c["rms_norm_eps"]
    H, n, e, v = z["H"], z["n"], z["e"], z["v"]
    theta = float(c["rope_parameters"]["rope_theta"])
    h = rms_norm(x, w["attn_norm"], eps)
    cq = rms_norm(matmul(h, w["wq_a"], quant), w["q_norm"], eps)
    q = matmul(cq, w["wq_b"], quant).reshape(S, H, n + e)
    q_n, q_r = q[..., :n], turn(q[..., n:], theta, e)
    kv = matmul(h, w["wkv_a"], quant)
    ckv = rms_norm(kv[:, :z["kvr"]], w["kv_norm"], eps)
    k_r = turn(kv[:, z["kvr"]:], theta, e)
    kvh = matmul(ckv, w["wkv_b"], quant).reshape(S, H, n + v)
    k_n, val = kvh[..., :n], kvh[..., n:]
    qi = turn(matmul(cq, w["wi_q"], quant).reshape(S, z["J"], z["D"]),
              theta, e)
    ki = turn(layer_norm(matmul(h, w["wi_k"], quant), w["ik_norm"],
                         w["ik_bias"], eps), theta, e)
    wi = matmul(h, w["wi_w"], quant) / math.sqrt(z["J"] * z["D"])
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def queries(first):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, block)  # noqa
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        if not dense:
            dots = jnp.einsum("qjd,kd->qjk", cut(qi), ki, precision=HI)
            index = jnp.einsum("qjk,qj->qk", jax.nn.relu(dots), cut(wi),
                               precision=HI)
            seen = chosen_positions(index, seen, z["topk"])
        if picked:
            return seen
        s = (jnp.einsum("qhn,khn->hqk", cut(q_n), k_n, precision=HI)
             + jnp.einsum("qhe,ke->hqk", cut(q_r), k_r, precision=HI)) \
            / math.sqrt(n + e)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, val,
                          precision=HI).reshape(block, H * v)

    out = jax.lax.map(queries, jnp.arange(0, S, block))
    if picked:
        return out.reshape(S, S)
    return matmul(out.reshape(S, H * v), w["wo"], quant)


def mixer(c: dict, x, w: dict, quant: bool = False, dense: bool = False):
    """A layer's mixer on x (B, S, d), float32, with its residual; one
    row of the batch at a time."""
    return x + jax.lax.map(lambda r: mixer_row(c, r, w, quant, dense), x)


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant=False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there. ``quant`` is the
    control: ``True`` the harness's, every weight product in int8;
    ``"dense"`` this family's second one, every product float32 and the
    indexer's choice left out."""
    dense, quant = (quant == "dense", False) if isinstance(quant, str) \
        else (False, quant)
    seed = seed_word(seed)
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, held
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)

    @jax.jit
    def mixer_step(x, at, seed):
        return mixer(c, x, _f32(attn_weights(c, seed, at, dtype)), quant,
                     dense)

    @jax.jit
    def dense_step(x, at, seed):
        w = _f32(dense_weights(c, seed, at, dtype))
        return x + jax.lax.map(lambda r: swiglu(
            rms_norm(r, w["mlp_norm"], c["rms_norm_eps"]), w["w1"], w["w3"],
            w["w2"], quant), x)

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
        x = mixer_step(x, layer, seed)
        if is_dense(c, layer):
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
    values :func:`score_tokens` regenerates. An expert layer's experts
    are drawn a block at a time inside ``lax.map``, so that the float32
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
            "attn": attn_weights(c, seed, layer, dtype), "mlp": mlp}
    return tree
