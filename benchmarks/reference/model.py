"""Plain float32 reference of the decoder both configurations share.

Straightforward ``jax.numpy``: no cache, no batching tricks, no kernels,
no scan, float32 with ``jax.default_matmul_precision("highest")`` (on a
TPU a float32 matmul otherwise runs in bf16 passes). It imports nothing
of the program (``pbs_tpu``) and takes nothing the program made: the
weights come from ``--seed`` through :func:`layer_weights` /
:func:`outer_weights`, which are the benchmark's definition of "the
weights of seed n". The harness builds the program's tree from the same
two functions, so both sides hold the same values; the reference
regenerates them layer by layer and never holds a whole model.

Architecture (InternLM2 and Mistral share it; Llama-style): RMSNorm
before attention and MLP, grouped-query attention with rotary position
embedding in the half-split convention, SwiGLU, untied output head, no
biases.

``quant`` switches every matmul to the *control* precision: both
operands of the forward product, and of the two backward products,
rounded to int8 (symmetric; weights per output channel, activations and
gradients per row). That is the precision step below bf16 a later PR
would be tempted by on a v5e (393 TOP/s in int8), and ``correct`` has to
come out false for it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
B1, B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.95, 1e-8, 0.1


def layer_shapes(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    hd = d // c["num_attention_heads"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return {"wq": (d, nq), "wk": (d, nkv), "wv": (d, nkv), "wo": (nq, d),
            "w1": (d, f), "w3": (d, f), "w2": (f, d)}


def seed_word(seed: int):
    """``--seed`` (any whole number up to a little over 2**31) as the
    32-bit word the weights are keyed by. Jitted code takes it as an
    argument, never as a constant: a program that bakes the seed in is a
    new program, and a new compile, for every seed."""
    return np.uint32(seed % (2 ** 31))


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])


def layer_weights(c: dict, seed: int, layer, dtype) -> dict:
    """Weights of one layer of ``seed``'s model, rounded to ``dtype``
    (the type they are held in). ``seed`` (a :func:`seed_word`) and
    ``layer`` may be traced."""
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    out = {"attn_norm": jnp.ones((c["hidden_size"],), dtype),
           "mlp_norm": jnp.ones((c["hidden_size"],), dtype)}
    for i, (name, shape) in enumerate(layer_shapes(c).items()):
        key = jax.random.fold_in(jax.random.fold_in(root, i), layer)
        out[name] = _normal(key, shape).astype(dtype)
    return out


def outer_weights(c: dict, seed: int, dtype) -> dict:
    root = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    d, v = c["hidden_size"], c["vocab_size"]
    k_emb, k_head = jax.random.split(root)
    return {"embed": (_normal(k_emb, (v, d)) * math.sqrt(d)).astype(dtype),
            "final_norm": jnp.ones((d,), dtype),
            "head": _normal(k_head, (d, v)).astype(dtype)}


# -- forward ----------------------------------------------------------------


def _int8(x, axis):
    """Round to 127 symmetric levels along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _int8_matmul(x, w):
    """``x @ w`` as an int8 path computes it: both operands of the
    forward product and of both backward products rounded to int8
    (activations and gradients per row, weights per output channel)."""
    return _mm(_int8(x, -1), _int8(w, -2))


def _int8_matmul_fwd(x, w):
    return _int8_matmul(x, w), (x, w)


def _int8_matmul_bwd(res, g):
    x, w = res
    g = _int8(g, -1)
    dx = _mm(g, jnp.swapaxes(_int8(w, -2), -1, -2))
    x2, g2 = x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1])
    return dx, _mm(jnp.swapaxes(_int8(x2, -1), 0, 1), g2)


_int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def matmul(x, w, quant: bool):
    return _int8_matmul(x, w) if quant else _mm(x, w)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (B, S, H, hd); positions 0..S-1; half-split rotation."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c: dict, x, w: dict, quant: bool = False):
    """The attention half of a decoder layer on x (B, S, d), float32,
    causal, with its residual; ``w`` already float32."""
    B, S, d = x.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nh
    h = rms_norm(x, w["attn_norm"], c["rms_norm_eps"])
    q = rope(matmul(h, w["wq"], quant).reshape(B, S, nh, hd),
             c["rope_theta"])
    k = rope(matmul(h, w["wk"], quant).reshape(B, S, nkv, hd),
             c["rope_theta"])
    v = matmul(h, w["wv"], quant).reshape(B, S, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, S, d)
    return x + matmul(a, w["wo"], quant)


def block(c: dict, x, w: dict, quant: bool = False):
    """One decoder layer on x (B, S, d), float32, causal."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = attention(c, x, w, quant)
    h = rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])
    gate = jax.nn.silu(matmul(h, w["w1"], quant))
    return x + matmul(gate * matmul(h, w["w3"], quant), w["w2"], quant)


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant: bool = False):
    """Run the model of ``seed`` (weights held in ``dtype``) over
    ``tokens`` (B, S) and read its logits at the N positions
    ``(rows[i], cols[i])``: the best logit, the best token, and the
    logit of each of ``candidates`` (K, N) there. Layer by layer,
    weights made and dropped as it goes: memory is one layer."""
    seed = seed_word(seed)
    outer = jax.jit(lambda s: outer_weights(c, s, dtype))(seed)

    @jax.jit
    def one(x, layer, seed):
        return block(c, x, layer_weights(c, seed, layer, dtype), quant)

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
        x = one(x, layer, seed)
    best, arg, picked = tail(x, outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


# -- training ---------------------------------------------------------------


def init_tree(c: dict, seed: int, n_layers: int, dtype) -> dict:
    """The whole model as the tree the program trains: layer leaves
    stacked on axis 0. One layer at a time inside ``lax.map`` so the
    float32 draw of a leaf never exists for all layers at once."""
    layers = jax.lax.map(lambda l: layer_weights(c, seed, l, dtype),
                         jnp.arange(n_layers))
    return {**outer_weights(c, seed, dtype), "layers": layers}


def loss(c: dict, params: dict, tokens, quant: bool = False):
    """Mean next-token cross-entropy: positions 0..S-2 predict 1..S-1."""
    x = params["embed"][tokens[:, :-1]]
    for l in range(params["layers"]["wq"].shape[0]):
        x = block(c, x, {k: v[l] for k, v in params["layers"].items()},
                  quant)
    h = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    logp = jax.nn.log_softmax(matmul(h, params["head"], quant), axis=-1)
    tgt = tokens[:, 1:]
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))


def leaf_norms(tree: dict) -> dict:
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


SKETCHES = 64


def sketch(tree: dict) -> dict:
    """Per leaf, ``SKETCHES`` fixed random projections ``u^T G v`` of
    the leaf as a matrix, ``u`` and ``v`` of +-1. The root mean square
    of the difference of two trees' sketches estimates the norm of their
    difference (``E[(u^T E v)^2] = |E|^2``) without ever holding both
    trees: a number that moves in the first order with rounding noise,
    where a norm moves only in the second."""
    out = {}
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for i, (path, g) in enumerate(leaves):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        g = g.astype(jnp.float32)
        g = g.reshape(-1, g.shape[-1]) if g.ndim > 1 else g[None, :]
        ku, kv = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(7), i))
        u = jax.random.rademacher(ku, (g.shape[0], SKETCHES), jnp.float32)
        v = jax.random.rademacher(kv, (g.shape[1], SKETCHES), jnp.float32)
        gv = jnp.matmul(g, v, precision=jax.lax.Precision.HIGHEST)
        out[name] = jnp.sum(u * gv, axis=0)
    return out


def train_readings(c: dict, seed: int, n_layers: int, batches,
                   learning_rate: float, quant: bool = False) -> dict:
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    float32 weights. Returns each step's loss, the per-leaf norm and
    sketch of the first gradient, and the per-leaf norm of the
    parameters' change after the last step."""

    @jax.jit
    def run(batches, seed):
        p0 = init_tree(c, seed, n_layers, jnp.float32)
        p, m, v = p0, *(jax.tree.map(jnp.zeros_like, p0) for _ in "mv")
        losses, g1 = [], None
        for t, tok in enumerate(batches, start=1):
            l, g = jax.value_and_grad(lambda q: loss(c, q, tok, quant))(p)
            losses.append(l)
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
