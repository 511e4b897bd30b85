"""Plain float32 reference of the ``moe-mla-mtp`` family (DeepSeek-V3,
``model_type: deepseek_v3``): multi-head latent attention over every
earlier position (no indexer) with YaRN-scaled rotary, leading dense
SwiGLU layers and then layers of many routed experts behind a
group-limited sigmoid router beside one shared expert, and a
multi-token-prediction module of depth one that drafts the token after
the next.

Straightforward ``jax.numpy``: no cache, no absorbed form (every head's
keys and values are read off the latent rows), no verify window, no
sorting or grouped product, float32 with ``HIGHEST`` matmuls, one row of
the batch and one block of queries at a time so that a request of two
and a half thousand positions fits. It imports nothing of the program
(``pbs_tpu``); norms, the int8 control's ``matmul``, the seed word and
the outer weights are the dense reference's own pieces, the SwiGLU, the
dense layer, a block of experts and its seeded weights the
``moe-mixed-gqa`` reference's, the router's and the shared expert's
seeded weights the ``moe-kda-gqa`` reference's. ``c`` is the
configuration file's dict (Hugging Face key names).

``d`` = ``hidden_size``, eps = ``rms_norm_eps``, pre-norm residual
blocks, no biases but the router's. ``H`` = ``num_attention_heads``,
``n`` = ``qk_nope_head_dim``, ``e`` = ``qk_rope_head_dim``, ``v`` =
``v_head_dim``.

**The mixer** (every layer), on ``h = rmsnorm(x)`` at position ``t``:

- ``c_q = rmsnorm(h W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``, H heads
  of ``[q_n (n) | q_r (e)]``, ``q_r`` turned at ``t``.
- ``[c (kv_lora_rank) | k_r (e)] = h W_kva``; ``c <- rmsnorm(c)``;
  ``k_r`` turned at ``t``, one for all heads. Head i: ``[k_n,i (n) |
  v_i (v)] = c W_kvb,i``.
- ``score[t, s, i] = (q_n,i . k_n,i[s] + q_r,i . k_r[s]) * scale`` over
  **every** ``s <= t``; float32 softmax; ``o_i = sum_s p v_i[s]``; ``x
  += concat_i(o_i) W_o``.
- Rotary: ``rope_theta`` with YaRN as Hugging Face's
  ``_compute_yarn_parameters`` writes it (``rope_scaling``: ``factor``,
  ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``:
  :func:`yarn_inv_freq`), on the ``e`` rotary dims, **adjacent pairs**
  ``(2i, 2i + 1)`` turned together. The factor on cos and sin is
  ``yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
  mscale_all_dim)`` = 1 here; what YaRN does to the magnitudes sits in
  the softmax's scale instead: ``scale = (n + e)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1`` (:func:`softmax_scale`).

**The MLP:** layers below ``first_k_dense_replace`` a dense SwiGLU of
``intermediate_size``. The others: ``r = sigmoid(h W_r)`` over all
``deployment.experts_total`` experts, float32; choosing uses ``r + b``
(``b`` the router's float32 bias, which takes no part in the weights):
the experts are ``n_group`` groups of consecutive ones; a group's score
is the sum of its two largest ``r + b``; the ``topk_group`` best groups
are kept, the others masked out; the ``num_experts_per_tok`` largest ``r
+ b`` among the kept groups are chosen (ties to the lower number);
``w_e = routed_scaling_factor r_e / (sum_chosen r + 1e-20)``; ``y = sum
over chosen e that are held here of w_e swiglu_e(h)`` (width
``moe_intermediate_size``) ``+ swiglu_shared(h)`` (width
``n_shared_experts * moe_intermediate_size``). No capacity, no dropped
token. After the last layer: ``rmsnorm``, untied head.

**The drafting module** (``num_nextn_predict_layers`` 1; the technical
report's equations 21-23; the released weights' names ``enorm``,
``hnorm``, ``eh_proj``, ``shared_head.norm``; embedding and head are the
main model's). For position ``i`` of a sequence whose next token
``t_{i+1}`` is known: ``u_i = [rmsnorm_e(emb(t_{i+1})) ;
rmsnorm_h(h_i)] W_eh`` (``(2 d, d)``), ``h_i`` the main stack's output
at ``i`` after its final rmsnorm; ``y = block(u)`` (one block of the
expert layers' kind: the mixer above over the ``u`` of positions ``0 ..
i``, row ``i`` at rotary position ``i``, then the routed and shared
experts behind a router of its own); ``draft logits_i = rmsnorm_s(y_i)
W_head``, which predict ``t_{i+2}`` (:func:`draft_logits`).

**Departures from the published model, all of them the cut to one chip's
share** (the configuration file states the deployment): this holder has
``n_routed_experts`` of the ``experts_total`` experts of each expert
layer, from ``deployment.experts_first``, and what an absent expert
would add is left out, here as in the program; the vocabulary is its
first ``vocab_size`` rows; the depth is the first ``n_layers`` layers, of
which the first ``first_k_dense_replace`` are dense, and the drafting
module sits behind them.

**Forms the published config's keys name but do not spell out** (also
under ``assumed`` in the configuration file):

- the rotated pairs are adjacent ones, as the released implementation
  turns them;
- the concatenation is ``[emb ; h]``, the order of the open servers
  that load the released ``eh_proj`` (the report writes ``[h ; emb]``;
  with seeded weights one is a row permutation of the other);
- ``h_i`` is taken after the stack's final norm, and nothing is masked
  at position 0;
- groups that are not kept are masked to minus infinity (the released
  code fills with 0, which differs only where a kept score plus its
  bias is negative);
- seeded weights normal / sqrt(fan_in) as the other families', norms at
  one, the router's bias 0.005 x normal float32 so that it is no no-op;
  the drafting module's leaves are keyed as layer 61, where the
  released checkpoint has them.

``quant`` in :func:`score_tokens` and :func:`draft_scores` is the
harness's control: every weight product in int8.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.model import (  # noqa: F401  (re-exported)
    _normal, matmul, outer_weights, rms_norm, seed_word)
from benchmarks.reference.moe_kda_gqa import (  # noqa: F401
    held_range, shared_width, sparse_outer_weights)
from benchmarks.reference.moe_mixed_attn import (  # noqa: F401
    _f32, block_of_experts, dense_weights, expert_block, swiglu)

MIXER_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "eh_proj")
#: Leaf numbers of their own, clear of the other references' tables.
_LEAF_ID = {n: 200 + i for i, n in enumerate(MIXER_LEAVES)}
#: The layer number the drafting module's leaves are keyed by (the
#: released checkpoint's ``model.layers.61``).
DRAFT_LAYER = 61
#: Experts drawn (and, in the forward, multiplied) at a time.
EXPERT_BLOCK = 4
#: Queries attended at a time.
QUERY_BLOCK = 128
HI = jax.lax.Precision.HIGHEST


def mixer_sizes(c: dict) -> dict:
    return {"H": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "kvr": c["kv_lora_rank"], "n": c["qk_nope_head_dim"],
            "e": c["qk_rope_head_dim"], "v": c["v_head_dim"]}


def mixer_shapes(c: dict) -> dict:
    d, z = c["hidden_size"], mixer_sizes(c)
    return {"wq_a": (d, z["qr"]),
            "wq_b": (z["qr"], z["H"] * (z["n"] + z["e"])),
            "wkv_a": (d, z["kvr"] + z["e"]),
            "wkv_b": (z["kvr"], z["H"] * (z["n"] + z["v"])),
            "wo": (z["H"] * z["v"], d)}


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
           "kv_norm": jnp.ones((z["kvr"],), dtype)}
    for name, shape in mixer_shapes(c).items():
        out[name] = _normal(_leaf_key(seed, name, at), shape).astype(dtype)
    return out


def draft_weights(c: dict, seed, dtype) -> dict:
    """The drafting module's own leaves outside its block: the two
    norms, the projection and the norm before the shared head."""
    d = c["hidden_size"]
    ones = jnp.ones((d,), dtype)
    return {"enorm": ones, "hnorm": ones, "head_norm": ones,
            "eh_proj": _normal(_leaf_key(seed, "eh_proj", DRAFT_LAYER),
                               (2 * d, d)).astype(dtype)}


# -- rotary -------------------------------------------------------------------


def yarn_inv_freq(c: dict) -> np.ndarray:
    """Inverse frequencies of the ``e / 2`` rotating pairs under YaRN,
    as Hugging Face's ``_compute_yarn_parameters`` computes them from
    ``rope_theta`` and ``rope_scaling`` (float64 on the host)."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], \
        float(c["rope_theta"])
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp        # the share of the unscaled frequency
    return interpolation * (1.0 - keep) + extrapolation * keep


def yarn_get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(c: dict, squared: bool = True) -> float:
    """``(n + e)^-1/2 m^2``, ``m`` YaRN's magnitude correction with
    ``mscale_all_dim``. ``squared`` False leaves ``m^2`` out: the scale
    of a reading that forgets it (the tests' control)."""
    rs = c["rope_scaling"]
    m = yarn_get_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (m * m if squared else 1.0) / math.sqrt(
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"])


def turn(c: dict, x):
    """Rotary on the last axis of x (S, ..., e) at positions 0..S-1,
    adjacent pairs ``(2i, 2i + 1)`` turned together, YaRN's
    frequencies, cos and sin times the attention factor (1 here)."""
    rs = c["rope_scaling"]
    factor = yarn_get_mscale(float(rs["factor"]), float(rs["mscale"])) \
        / yarn_get_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(c), jnp.float32)
    shape = (S,) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


# -- forward ------------------------------------------------------------------


def mixer_row(c: dict, x, w: dict, quant: bool = False,
              scale: float | None = None):
    """The mixer on one row x (S, d), float32, without its residual.
    ``scale``: another softmax scale than :func:`softmax_scale`'s (the
    tests' control)."""
    S = x.shape[0]
    z, eps = mixer_sizes(c), c["rms_norm_eps"]
    H, n, e, v = z["H"], z["n"], z["e"], z["v"]
    scale = softmax_scale(c) if scale is None else scale
    h = rms_norm(x, w["attn_norm"], eps)
    cq = rms_norm(matmul(h, w["wq_a"], quant), w["q_norm"], eps)
    q = matmul(cq, w["wq_b"], quant).reshape(S, H, n + e)
    q_n, q_r = q[..., :n], turn(c, q[..., n:])
    kv = matmul(h, w["wkv_a"], quant)
    ckv = rms_norm(kv[:, :z["kvr"]], w["kv_norm"], eps)
    k_r = turn(c, kv[:, z["kvr"]:])
    kvh = matmul(ckv, w["wkv_b"], quant).reshape(S, H, n + v)
    k_n, val = kvh[..., :n], kvh[..., n:]
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def queries(first):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, block)  # noqa
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(block)[:, None]
        s = (jnp.einsum("qhn,khn->hqk", cut(q_n), k_n, precision=HI)
             + jnp.einsum("qhe,ke->hqk", cut(q_r), k_r, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, val,
                          precision=HI).reshape(block, H * v)

    out = jax.lax.map(queries, jnp.arange(0, S, block))
    return matmul(out.reshape(S, H * v), w["wo"], quant)


def mixer(c: dict, x, w: dict, quant: bool = False,
          scale: float | None = None):
    """A layer's mixer on x (B, S, d), float32, with its residual; one
    row of the batch at a time."""
    return x + jax.lax.map(lambda r: mixer_row(c, r, w, quant, scale), x)


def routing(c: dict, h, router, bias, quant: bool):
    """h (N, d) -> (N, experts_total) weights: ``scale * r_e / (sum_S r
    + 1e-20)`` on a token's chosen experts, zero elsewhere; chosen by
    ``r + bias`` among the ``topk_group`` groups whose two best ``r +
    bias`` sum highest."""
    r = jax.nn.sigmoid(matmul(h, router, quant))
    groups = (r + bias).reshape(r.shape[0], c["n_group"], -1)
    best = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(best, c["topk_group"])
    keep = jnp.sum(jax.nn.one_hot(kept, c["n_group"]), axis=-2) > 0
    biased = jnp.where(keep[..., None], groups, -jnp.inf).reshape(r.shape)
    _, topi = jax.lax.top_k(biased, c["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(topi, r.shape[-1], dtype=jnp.float32),
                     axis=-2)
    w = r * chosen
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return c["routed_scaling_factor"] * w


class _Steps:
    """The jitted pieces one pass over the model is made of, weights
    drawn from the seed layer by layer inside each."""

    def __init__(self, c: dict, dtype, quant: bool):
        first, held = held_range(c)
        block = min(EXPERT_BLOCK, held)
        assert held % block == 0, held
        self.c, self.first, self.held, self.block = c, first, held, block
        eps = c["rms_norm_eps"]

        @jax.jit
        def mixer_step(x, at, seed):
            return mixer(c, x, _f32(attn_weights(c, seed, at, dtype)), quant)

        @jax.jit
        def dense_step(x, at, seed):
            w = _f32(dense_weights(c, seed, at, dtype))
            return x + jax.lax.map(lambda r: swiglu(
                rms_norm(r, w["mlp_norm"], eps), w["w1"], w["w3"],
                w["w2"], quant), x)

        @jax.jit
        def route_step(x, at, seed):
            w = _f32(sparse_outer_weights(c, seed, at, dtype))
            h = rms_norm(x, w["mlp_norm"], eps)
            h = h.reshape(-1, h.shape[-1])
            return (h, routing(c, h, w["router"], w["router_bias"], quant),
                    swiglu(h, w["ws1"], w["ws3"], w["ws2"], quant))

        @jax.jit
        def block_step(y, h, gate, at, start, seed):
            wb = _f32(expert_block(c, seed, at, start, block, dtype))
            g = jax.lax.dynamic_slice_in_dim(gate, start, block, axis=1)
            return y + block_of_experts(h, g, wb, quant)

        @jax.jit
        def join_step(hidden, follows, outer, seed):
            w = _f32(draft_weights(c, seed, dtype))
            emb = outer["embed"].astype(jnp.float32)[follows]
            u = jnp.concatenate([rms_norm(emb, w["enorm"], eps),
                                 rms_norm(hidden, w["hnorm"], eps)], -1)
            return jax.lax.map(
                lambda r: matmul(r, w["eh_proj"], quant), u)

        self.mixer_step, self.dense_step = mixer_step, dense_step
        self.route_step, self.block_step = route_step, block_step
        self.join_step = join_step

    def block_of(self, x, at, seed, dense: bool):
        """One decoder block, keyed as layer ``at``."""
        x = self.mixer_step(x, at, seed)
        if dense:
            return self.dense_step(x, at, seed)
        h, gate, y = self.route_step(x, at, seed)
        for start in range(self.first, self.first + self.held, self.block):
            y = self.block_step(y, h, gate, at, start, seed)
        return x + y.reshape(x.shape)

    def stack(self, outer, tokens, n_layers: int, seed):
        """The main stack's output before its final norm, (B, S, d)."""
        x = jax.jit(lambda e, t: e.astype(jnp.float32)[t])(
            outer["embed"], tokens)
        for layer in range(n_layers):
            x = self.block_of(x, layer, seed, is_dense(self.c, layer))
        return x

    def draft(self, outer, x, follows, seed):
        """The drafting module's block output (B, S, d) before its head
        norm: ``x`` the stack's output before its final norm,
        ``follows`` (B, S) the token after each position."""
        eps = self.c["rms_norm_eps"]
        hidden = jax.jit(lambda x, w: rms_norm(
            x, w.astype(jnp.float32), eps))(x, outer["final_norm"])
        u = self.join_step(hidden, follows, outer, seed)
        return self.block_of(u, DRAFT_LAYER, seed, dense=False)


def _tail(c: dict, quant: bool):
    @jax.jit
    def tail(x, norm, outer, rows, cols, candidates):
        h = rms_norm(x[rows, cols], norm.astype(jnp.float32),
                     c["rms_norm_eps"])
        logits = matmul(h, outer["head"].astype(jnp.float32), quant)
        picked = jnp.take_along_axis(logits, candidates.T, axis=-1).T
        return jnp.max(logits, -1), jnp.argmax(logits, -1), picked

    return tail


def _model(c: dict, seed: int, dtype, quant):
    """The seed's word, the jitted pieces and the outer weights a pass
    over the model starts from."""
    word = seed_word(seed)
    return word, _Steps(c, dtype, bool(quant)), jax.jit(
        lambda s: outer_weights(c, s, dtype))(word)


def _draft_pass(c: dict, seed, steps, outer, tokens, n_layers: int, dtype):
    """The drafting module's block output over ``tokens`` (B, S), each
    position paired with the token after it (the last position's pair
    wraps round and is never read), and its head norm."""
    x = steps.stack(outer, tokens, n_layers, seed)
    y = steps.draft(outer, x, jnp.roll(tokens, -1, axis=1), seed)
    return y, jax.jit(
        lambda s: draft_weights(c, s, dtype)["head_norm"])(seed)


def score_tokens(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant=False):
    """As the dense reference's: run the model of ``seed`` (weights held
    in ``dtype``) over ``tokens`` (B, S) and read its logits at the N
    positions ``(rows[i], cols[i])``: the best logit, the best token and
    the logit of each of ``candidates`` (K, N) there. ``quant`` is the
    harness's control, every weight product in int8. The drafting
    module takes no part: a served token is the main stack's."""
    seed, steps, outer = _model(c, seed, dtype, quant)
    x = steps.stack(outer, tokens, n_layers, seed)
    best, arg, picked = _tail(c, bool(quant))(
        x, outer["final_norm"], outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


def draft_scores(c: dict, seed: int, n_layers: int, dtype, tokens, rows,
                 cols, candidates, quant=False):
    """:func:`score_tokens` of the drafting module: its logits at the N
    positions ``(rows[i], cols[i])`` of ``tokens`` (B, S), each of which
    has a token after it (``cols[i] + 1 < S``: the pair is ``(h_i,
    t_{i+1})`` and the logits predict ``t_{i+2}``): the best logit, the
    best token and the logit of each of ``candidates`` (K, N)."""
    seed, steps, outer = _model(c, seed, dtype, quant)
    y, norm = _draft_pass(c, seed, steps, outer, jnp.asarray(tokens),
                          n_layers, dtype)
    best, arg, picked = _tail(c, bool(quant))(
        y, norm, outer, rows, cols, candidates)
    return (jax.device_get(best), jax.device_get(arg),
            jax.device_get(picked))


def _all_logits(c: dict, x, norm, outer, quant: bool):
    h = rms_norm(x, norm.astype(jnp.float32), c["rms_norm_eps"])
    return jax.lax.map(lambda r: matmul(
        r, outer["head"].astype(jnp.float32), quant), h)


def full_logits(c: dict, seed: int, n_layers: int, dtype, tokens,
                quant=False):
    """The main stack's logits at every position, (B, S, V) float32 (the
    tests' oracle; the chip's comparison reads single positions)."""
    seed, steps, outer = _model(c, seed, dtype, quant)
    x = steps.stack(outer, jnp.asarray(tokens), n_layers, seed)
    return jax.jit(lambda x, o: _all_logits(
        c, x, o["final_norm"], o, bool(quant)))(x, outer)


def draft_logits(c: dict, seed: int, n_layers: int, dtype, tokens,
                 quant=False):
    """The drafting module's logits at every position of ``tokens`` (B,
    S) that has a token after it, given the sequence: (B, S - 1, V)
    float32; entry ``i`` is computed from ``(h_i, t_{i+1})`` over the
    pairs ``0 .. i`` and predicts ``t_{i+2}``."""
    seed, steps, outer = _model(c, seed, dtype, quant)
    y, norm = _draft_pass(c, seed, steps, outer, jnp.asarray(tokens),
                          n_layers, dtype)
    return jax.jit(lambda y, n, o: _all_logits(
        c, y, n, o, bool(quant)))(y, norm, outer)[:, :-1]


# -- the program's tree ---------------------------------------------------------


def _expert_layer(c: dict, seed, at, dtype) -> dict:
    """An expert layer's MLP leaves, keyed as layer ``at``: its experts
    drawn a block at a time inside ``lax.map``, so that the float32
    draw of a leaf never exists for all of a layer's experts."""
    first, held = held_range(c)
    block = min(EXPERT_BLOCK, held)
    blocks = jax.lax.map(
        lambda s: expert_block(c, seed, at, s, block, dtype),
        jnp.arange(first, first + held, block))
    return {**sparse_outer_weights(c, seed, at, dtype),
            **{k: v.reshape((held,) + v.shape[2:])
               for k, v in blocks.items()}}


def init_tree(c: dict, seed, n_layers: int, dtype) -> dict:
    """The whole held model as the tree the program serves, a layer at
    a time (``blocks/<NN>/attn/...``, ``blocks/<NN>/mlp/...``) with the
    drafting module under ``blocks/mtp``: the same values
    :func:`score_tokens` and :func:`draft_logits` regenerate."""
    tree: dict = {**outer_weights(c, seed, dtype), "blocks": {}}
    for layer in range(n_layers):
        mlp = dense_weights(c, seed, layer, dtype) if is_dense(c, layer) \
            else _expert_layer(c, seed, layer, dtype)
        tree["blocks"][f"{layer:02d}"] = {
            "attn": attn_weights(c, seed, layer, dtype), "mlp": mlp}
    if c["num_nextn_predict_layers"]:
        tree["blocks"]["mtp"] = {
            **draft_weights(c, seed, dtype),
            "attn": attn_weights(c, seed, DRAFT_LAYER, dtype),
            "mlp": _expert_layer(c, seed, DRAFT_LAYER, dtype)}
    return tree
