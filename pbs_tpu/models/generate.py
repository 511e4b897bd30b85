"""Autoregressive serving: KV-cache prefill/decode/generate, TPU-first.

The reference has no serving path (no models at all, SURVEY.md §0); in
PBS-T the latency-sensitive tenant class the scheduler BOOSTs on wake
(``csched_schedule``'s BOOST priority) is exactly a batch-inference
loop, so the framework ships one: KV-cached autoregressive decoding
over the flagship transformer's weights.

TPU-first choices:

- **Static shapes throughout**: the cache is allocated at ``max_seq``
  up front; position is data, not shape. Prefill and every decode step
  compile once, regardless of prompt length or tokens generated.
- **``lax.scan`` everywhere**: over stacked layer params + cache slabs
  inside one forward (compile time O(1) in depth), and over decode
  steps inside :func:`make_generate` (one dispatch per generation, not
  per token).
- **GQA cache**: cached K/V at ``n_kv_heads`` (memory ∝ kv heads, not
  query heads); queries group over them at attention time.
- **bfloat16 cache** (compute dtype): HBM-resident cache is the serving
  memory bill; fp32 would double it for no MXU benefit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.models.quant import embed_rows, wload
from pbs_tpu.models.transformer import (
    TransformerConfig,
    apply_rope,
    rms_norm,
    rope_tables,
)


def init_cache(cfg: TransformerConfig, batch: int,
               max_len: int | None = None) -> dict:
    """Zeroed KV slabs: (L, B, T, n_kv_heads, head_dim) + position."""
    T = max_len if max_len is not None else cfg.max_seq
    shape = (cfg.n_layers, batch, T, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),  # tokens already cached
    }


def _cached_attention(q, ck, cv, start_pos, cfg: TransformerConfig):
    """q (B,S,H,hd) against full cache slabs ck/cv (B,T,nkv,hd); rows
    r attend to absolute cols <= start_pos + r (causal over history)."""
    B, S, H, hd = q.shape
    T, nkv = ck.shape[1], ck.shape[2]
    group = H // nkv
    qg = q.reshape(B, S, nkv, group, hd).transpose(0, 2, 3, 1, 4)
    kt = ck.transpose(0, 2, 1, 3)  # (B, nkv, T, hd)
    vt = cv.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bngqh,bnkh->bngqk", qg, kt) / np.sqrt(hd)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    mask = cols <= rows + start_pos  # unwritten tail is masked too
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqk,bnkh->bngqh", probs, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def _forward_with_cache_impl(cfg: TransformerConfig, params: dict,
                             tokens: jax.Array, cache: dict,
                             constrain=lambda x: x, mlp_fn=None):
    """Shared cached-forward plumbing (embed, rope slice, KV update,
    cached attention, norms, head) parameterized over the FFN block so
    the dense and MoE serving paths keep ONE copy. ``mlp_fn(lp, h) ->
    (y, extra)`` replaces the dense SwiGLU when given; per-layer
    ``extra`` scalars (e.g. MoE drop fractions) are summed. Returns
    (logits, new_cache, extra_sum)."""
    B, S = tokens.shape
    T = cache["k"].shape[2]
    dt = cfg.dtype
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    start = cache["pos"]

    # wload/embed_rows accept plain bf16/fp32 weights or int8
    # {"q","s"} leaves (models.quant weight-only serving quantization).
    x = constrain(embed_rows(params["embed"], tokens, dt))
    cos_full, sin_full = rope_tables(cfg, T)
    cos = jax.lax.dynamic_slice_in_dim(cos_full, start, S)
    sin = jax.lax.dynamic_slice_in_dim(sin_full, start, S)

    def body(carry, layer):
        x, extra = carry
        lp, ck, cv = layer
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ wload(lp["wq"], dt)).reshape(B, S, nh, hd)
        k = (h @ wload(lp["wk"], dt)).reshape(B, S, nkv, hd)
        v = (h @ wload(lp["wv"], dt)).reshape(B, S, nkv, hd)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, start, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, start, axis=1)
        attn = _cached_attention(q, ck, cv, start, cfg)
        x = constrain(x + attn.reshape(B, S, nh * hd) @ wload(lp["wo"], dt))
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if mlp_fn is None:
            gate = jax.nn.silu(h @ wload(lp["w1"], dt))
            up = h @ wload(lp["w3"], dt)
            y = (gate * up) @ wload(lp["w2"], dt)
            e = jnp.zeros((), jnp.float32)
        else:
            y, e = mlp_fn(lp, h)
        x = constrain(x + y)
        return (x, extra + e), (ck, cv)

    zero = jnp.zeros((), jnp.float32)
    (x, extra), (new_k, new_v) = jax.lax.scan(
        body, (x, zero), (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ wload(params["head"], dt)).astype(jnp.float32)
    new_cache = {"k": new_k, "v": new_v, "pos": start + S}
    return logits, new_cache, extra


def forward_with_cache(cfg: TransformerConfig, params: dict,
                       tokens: jax.Array, cache: dict,
                       constrain=lambda x: x) -> tuple[jax.Array, dict]:
    """Run ``tokens`` (B, S) through the model starting at the cache
    position: new K/V are written into the slabs, attention sees the
    whole prefix. Returns (logits (B, S, vocab) fp32, updated cache).
    S is static; use S=prompt_len for prefill and S=1 for decode."""
    logits, new_cache, _ = _forward_with_cache_impl(
        cfg, params, tokens, cache, constrain)
    return logits, new_cache


def prefill(cfg: TransformerConfig, params: dict, prompt: jax.Array,
            cache: dict, constrain=lambda x: x) -> tuple[jax.Array, dict]:
    """Ingest the prompt in one pass; returns (last-position logits
    (B, vocab), cache)."""
    logits, cache = forward_with_cache(cfg, params, prompt, cache, constrain)
    return logits[:, -1, :], cache


def _sample(logits: jax.Array, key: jax.Array,
            temperature: float) -> jax.Array:
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def make_generate_loop(cfg: TransformerConfig, max_new_tokens: int,
                       temperature: float, fwd):
    """The shared decode loop (cache init, prefill, single-use keys,
    on-device step scan) parameterized over the forward:
    ``fwd(params, tokens, cache) -> (logits, cache, extra)``. Returns
    ``loop(params, prompt, key) -> (toks (B, max_new), extra_prefill,
    extra_decode_sum, P)`` — wrappers decide what ``extra`` means
    (dense: nothing; MoE: router drop fractions)."""

    def loop(params: dict, prompt: jax.Array, key: jax.Array):
        B, P = prompt.shape
        cache = init_cache(cfg, B, max_len=P + max_new_tokens)
        logits, cache, extra0 = fwd(params, prompt, cache)
        key, first_key = jax.random.split(key)  # single-use keys
        first = _sample(logits[:, -1, :], first_key, temperature)

        # max_new_tokens - 1 decode forwards produce the remaining
        # tokens; the step emits what it sampled, so no forward's
        # output is discarded.
        def step(carry, step_key):
            tok, cache, esum = carry
            logits, cache, e = fwd(params, tok[:, None], cache)
            nxt = _sample(logits[:, -1, :], step_key, temperature)
            return (nxt, cache, esum + e), nxt

        n_rest = max_new_tokens - 1
        keys = jax.random.split(key, max(n_rest, 1))[:n_rest]
        zero = jnp.zeros((), jnp.float32)
        (_, _, esum), rest = jax.lax.scan(step, (first, cache, zero),
                                          keys)
        toks = jnp.concatenate([first[None], rest], axis=0)
        return toks.transpose(1, 0), extra0, esum, P

    return loop


def make_generate(cfg: TransformerConfig, max_new_tokens: int,
                  temperature: float = 0.0, constrain=lambda x: x):
    """Returns ``generate(params, prompt, key) -> (B, max_new_tokens)``
    — jit it once; the whole decode loop is a single on-device scan.

    ``prompt`` is (B, P) int32 with a static P; the cache is sized to
    ``P + max_new_tokens`` so serving memory is exactly what the request
    class needs, not cfg.max_seq."""

    def fwd(params, tokens, cache):
        return _forward_with_cache_impl(cfg, params, tokens, cache,
                                        constrain)

    loop = make_generate_loop(cfg, max_new_tokens, temperature, fwd)

    def generate(params: dict, prompt: jax.Array,
                 key: jax.Array) -> jax.Array:
        toks, _extra0, _esum, _P = loop(params, prompt, key)
        return toks

    return generate


def make_serve_step(cfg: TransformerConfig, max_new_tokens: int,
                    temperature: float = 0.0, constrain=lambda x: x):
    """A Job-shaped batch-inference loop: ``state`` is (params, key,
    requests_served); each step generates one batch and bumps the
    counter — the latency-sensitive tenant of SURVEY.md §7's minimum
    slice, multiplexed against training by the credit scheduler."""
    gen = make_generate(cfg, max_new_tokens, temperature, constrain)

    def serve_step(state, prompts: jax.Array):
        params, key, served = state
        key, sub = jax.random.split(key)
        toks = gen(params, prompts, sub)
        ntok = toks.shape[0] * toks.shape[1]
        metrics = {"tokens": jnp.asarray(ntok, jnp.int32)}
        return (params, key, served + 1), metrics

    return serve_step
