"""Plain float32 reference of the program's second family
(``pbs_tpu/models/moe.py``): the dense family's attention, and in place
of its MLP a token-choice mixture of SwiGLU experts, as that file
computes it: router softmax over all experts, top-k, the k gate weights
renormalized to sum to one, the experts' outputs added with those
weights; the Switch load-balance term (experts x sum over experts of
[share of tokens whose first choice it is] x [mean router probability])
averaged over layers and added to the training loss with
``aux_loss_weight``. **No token is dropped**: the family runs the
program with ``dropless=True`` (capacity = the group's token count), so
every token keeps every choice and the reference needs no capacity.

Every expert is computed for every token and weighted (by zero where
the token did not choose it): straightforward, no dispatch. It imports
nothing of the program; attention, norms, the int8 control's ``matmul``,
the weight draw and the sketch are the dense reference's own pieces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    ADAM_EPS, B1, B2, WEIGHT_DECAY, _normal, attention, layer_shapes,
    leaf_norms, matmul, outer_weights, rms_norm, seed_word, sketch)

DENSE_LEAVES = ("wq", "wk", "wv", "wo")


def moe_shapes(c: dict) -> dict:
    d, f, E = c["hidden_size"], c["intermediate_size"], c["num_experts"]
    dense = layer_shapes(c)
    return {**{k: dense[k] for k in DENSE_LEAVES}, "router": (d, E),
            "we1": (E, d, f), "we3": (E, d, f), "we2": (E, f, d)}


def layer_weights(c: dict, seed, layer, dtype) -> dict:
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    out = {"attn_norm": jnp.ones((c["hidden_size"],), dtype),
           "mlp_norm": jnp.ones((c["hidden_size"],), dtype)}
    for i, (name, shape) in enumerate(moe_shapes(c).items()):
        key = jax.random.fold_in(jax.random.fold_in(root, i), layer)
        out[name] = _normal(key, shape).astype(dtype)
    return out


def experts(c: dict, h, w: dict, quant: bool):
    """h (B, S, d) -> (mixture output, this layer's load-balance term)."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(matmul(h, w["router"], quant), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.clip(jnp.sum(topv, -1, keepdims=True), 1e-9)
    chosen = jax.nn.one_hot(topi, E, dtype=jnp.float32)   # (B, S, k, E)
    gate = jnp.sum(topv[..., None] * chosen, axis=-2)      # (B, S, E)
    y = jnp.zeros_like(h)
    for e in range(E):
        act = jax.nn.silu(matmul(h, w["we1"][e], quant)) \
            * matmul(h, w["we3"][e], quant)
        y = y + gate[..., e:e + 1] * matmul(act, w["we2"][e], quant)
    first = chosen[..., 0, :].reshape(-1, E)
    aux = E * jnp.sum(jnp.mean(first, 0)
                      * jnp.mean(probs.reshape(-1, E), 0))
    return y, aux


def block(c: dict, x, w: dict, quant: bool = False):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = attention(c, x, w, quant)
    y, aux = experts(c, rms_norm(x, w["mlp_norm"], c["rms_norm_eps"]), w,
                     quant)
    return x + y, aux


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant: bool = False):
    """As the dense reference's: best logit, best token and each
    candidate's logit at the positions ``(rows[i], cols[i])``."""
    seed = seed_word(seed)
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)

    @jax.jit
    def run(outer, tokens, rows, cols, candidates, seed):
        x = outer["embed"].astype(jnp.float32)[tokens]
        for layer in range(n_layers):
            x, _ = block(c, x, layer_weights(c, seed, layer, dtype), quant)
        h = rms_norm(x[rows, cols], outer["final_norm"].astype(jnp.float32),
                     c["rms_norm_eps"])
        logits = matmul(h, outer["head"].astype(jnp.float32), quant)
        picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
        return jnp.max(logits, -1), jnp.argmax(logits, -1), picked

    return jax.device_get(run(outer, tokens, rows, cols, candidates, seed))


def init_tree(c: dict, seed, n_layers: int, dtype) -> dict:
    layers = jax.lax.map(lambda l: layer_weights(c, seed, l, dtype),
                         jnp.arange(n_layers))
    return {**outer_weights(c, seed, dtype), "layers": layers}


def loss(c: dict, params: dict, tokens, quant: bool = False):
    """(language-model loss + aux_loss_weight x mean load-balance term,
    language-model loss): the first is what the program differentiates,
    the second what it reports."""
    x = params["embed"][tokens[:, :-1]]
    n, aux = params["layers"]["wq"].shape[0], 0.0
    for l in range(n):
        x, a = block(c, x, {k: v[l] for k, v in params["layers"].items()},
                     quant)
        aux = aux + a / n
    h = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    logp = jax.nn.log_softmax(matmul(h, params["head"], quant), axis=-1)
    lm = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    return lm + c["aux_loss_weight"] * aux, lm


def train_readings(c: dict, seed: int, n_layers: int, batches,
                   learning_rate: float, quant: bool = False) -> dict:
    """As the dense reference's: follow the first AdamW steps."""

    @jax.jit
    def run(batches, seed):
        p0 = init_tree(c, seed, n_layers, jnp.float32)
        p, m, v = p0, *(jax.tree.map(jnp.zeros_like, p0) for _ in "mv")
        losses, g1 = [], None
        for t, tok in enumerate(batches, start=1):
            (_, lm), g = jax.value_and_grad(
                lambda q: loss(c, q, tok, quant), has_aux=True)(p)
            losses.append(lm)
            g1 = g if g1 is None else g1
            m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
            v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
            p = jax.tree.map(
                lambda w, a, b: w - learning_rate * (
                    (a / (1 - B1 ** t))
                    / (jnp.sqrt(b / (1 - B2 ** t)) + ADAM_EPS)
                    + WEIGHT_DECAY * w), p, m, v)
        return (jnp.stack(losses), leaf_norms(g1),
                leaf_norms(jax.tree.map(jnp.subtract, p, p0)), sketch(g1))

    losses, g, dp, sk = run(tuple(jnp.asarray(b) for b in batches),
                            seed_word(seed))
    return {"loss": [float(x) for x in losses],
            "grad_sketch": {k: jax.device_get(x) for k, x in sk.items()},
            "grad_norm": {k: float(x) for k, x in g.items()},
            "dparam_norm": {k: float(x) for k, x in dp.items()}}
