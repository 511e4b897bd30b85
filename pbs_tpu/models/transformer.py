"""Flagship workload: a LLaMA-style decoder-only transformer, TPU-first.

The reference contains no ML models (SURVEY.md §0); workloads there are
guest VMs. In PBS-T the schedulable tenant is a compiled training or
serving loop, and this transformer is the flagship job the framework
multiplexes, benchmarks, and checkpoints (the "small transformer train
loop" of SURVEY.md §7's minimum end-to-end slice).

TPU-first design choices:

- **Pure functional pytrees** (no Module framework): params are nested
  dicts, steps are jit-compiled pure functions — transforms compose.
- **bfloat16 compute, fp32 master params**: keeps the MXU fed at its
  native precision while optimizer math stays stable.
- **``lax.scan`` over stacked layer params**: one compiled layer body
  regardless of depth — compile time O(1) in n_layers, XLA still
  pipelines.
- **Static shapes everywhere**; causal masking via iota comparison (no
  dynamic slicing in the hot path).
- **Sharding by annotation**: forward code is single-device; distribution
  comes from `jax.sharding` constraints applied at jit boundaries
  (pbs_tpu.parallel) — mesh axes `dp` (batch), `tp` (heads/ff/vocab),
  and sequence-parallel residual streams between blocks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32_000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4  # GQA: kv heads < query heads
    d_ff: int = 1_408  # ~2.67x d_model, SwiGLU-adjusted
    max_seq: int = 1_024
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute dtype (MXU-native)
    # Remat the layer body: trade FLOPs for HBM (jax.checkpoint).
    remat: bool = False
    # Remat policy: "full" recomputes everything; "dots" saves weight
    # matmul outputs (dots_with_no_batch_dims_saveable) and recomputes
    # elementwise/attention — usually the best throughput point.
    remat_policy: str = "full"
    # Attention implementation: "xla" (fused by compiler), "pallas"
    # (pbs_tpu.ops.attention), "ring" (sequence-parallel ring
    # attention), "ulysses" (sequence-parallel via head-scattering
    # all-to-alls; needs H and Hkv divisible by the sp axis).
    attn_impl: str = "xla"
    # Intra-device block computation for the sequence-parallel impls
    # ("ring"/"ulysses"): "dense" (XLA einsum) or "flash" (Pallas
    # kernel — long chunks never materialize probabilities).
    ring_block: str = "dense"
    # Chunked cross-entropy: compute the head matmul + softmax over
    # n sequence chunks under jax.checkpoint, so the (B, S, vocab)
    # logits tensor (fp32: ~0.8 GB at the flagship shape) never
    # materializes — the loss tail's activation drops to O(S/n * V)
    # for ~one extra head-matmul pass of recompute in the backward.
    # 0/1 = off (materialized logits, the original path).
    loss_chunks: int = 0
    # Width of one attention head where the model publishes it apart
    # from its other widths; None is d_model // n_heads (every dense
    # configuration). Read through ``head_dim``.
    head_size: int | None = None
    # Layers that differ (attention kinds with their own head counts,
    # windows and rotary settings; dense beside routed-expert MLPs):
    # a ``models.plan.LayerPlan``. None is n_layers copies of the one
    # layer the widths above describe. Read by the serving engine; the
    # training step runs uniform configurations only.
    layer_plan: Any = None
    # The logits are ``h @ embed.T`` and the tree has no ``head``. Read
    # by the serving engine, which then serves through the planned
    # stack's program (``models.slot_programs.slot_program``); the training
    # step keeps its untied head.
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        return self.d_model // self.n_heads

    def bytes_per_token_step(self) -> int:
        """Rough HBM traffic per token per training step (params read
        fwd+bwd+update), for telemetry estimates."""
        return 6 * self.num_params() // max(1, self.max_seq)

    def num_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        hd = self.head_dim
        per_layer = (
            d * (self.n_heads * hd)  # wq
            + 2 * d * (self.n_kv_heads * hd)  # wk, wv
            + (self.n_heads * hd) * d  # wo
            + 3 * d * f  # w1, w3, w2
            + 2 * d  # norms
        )
        return v * d + self.n_layers * per_layer + d + d * v


# -- initialization ---------------------------------------------------------


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    """fp32 master params; layer params stacked on axis 0 for scan."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    hd, nh, nkv, L = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers

    def dense(key, shape):
        fan_in = shape[-2] if len(shape) > 1 else shape[-1]
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    ks = jax.random.split(k_layers, 7)
    layers = {
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "wq": dense(ks[0], (L, d, nh * hd)),
        "wk": dense(ks[1], (L, d, nkv * hd)),
        "wv": dense(ks[2], (L, d, nkv * hd)),
        "wo": dense(ks[3], (L, nh * hd, d)),
        "mlp_norm": jnp.ones((L, d), jnp.float32),
        "w1": dense(ks[4], (L, d, f)),  # gate
        "w3": dense(ks[5], (L, d, f)),  # up
        "w2": dense(ks[6], (L, f, d)),  # down
    }
    return {
        "embed": dense(k_emb, (cfg.vocab, d)) * np.sqrt(d),  # scaled emb
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "head": dense(k_head, (d, cfg.vocab)),
    }


# -- building blocks --------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    # Normalize in fp32 for stability, cast back to compute dtype.
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * weight.astype(x.dtype)


def rope_tables(cfg: TransformerConfig, seq: int) -> tuple[jax.Array, jax.Array]:
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    t = jnp.arange(seq, dtype=jnp.float32)
    ang = jnp.outer(t, freqs)  # (seq, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd). Rotate pairs (even, odd) halves."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: TransformerConfig,
    mesh=None,
) -> jax.Array:
    """(B, S, H, hd) GQA attention with causal iota mask — left to XLA
    to fuse; swap for the Pallas kernel ("pallas") or sequence-parallel
    ring attention ("ring", needs a mesh with an 'sp' axis) via
    cfg.attn_impl. Unknown impls are rejected loudly — never a silent
    dense fallback."""
    if cfg.attn_impl == "pallas":
        from pbs_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    if cfg.attn_impl == "ring":
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                "attn_impl='ring' needs a mesh with an 'sp' axis threaded "
                "through forward(..., mesh=...); use "
                "pbs_tpu.parallel.make_sharded_train with an sp mesh"
            )
        from pbs_tpu.parallel.ring_attention import ring_attention

        return ring_attention(
            q, k, v, mesh, axis="sp", causal=True,
            batch_axis="dp", head_axis="tp", block_impl=cfg.ring_block,
        )
    if cfg.attn_impl == "ulysses":
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                "attn_impl='ulysses' needs a mesh with an 'sp' axis "
                "threaded through forward(..., mesh=...); use "
                "pbs_tpu.parallel.make_sharded_train with an sp mesh"
            )
        from pbs_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, mesh, axis="sp", causal=True,
            batch_axis="dp", block_impl=cfg.ring_block,
        )
    if cfg.attn_impl != "xla":
        raise ValueError(
            f"unknown attn_impl {cfg.attn_impl!r}; "
            "expected 'xla', 'pallas', 'ring', or 'ulysses'"
        )
    B, S, H, hd = q.shape
    nkv = k.shape[2]
    group = H // nkv
    # (B, nkv, group, S, hd) queries against (B, nkv, S, hd) keys.
    qg = q.reshape(B, S, nkv, group, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)  # (B, nkv, S, hd)
    vt = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bngqh,bnkh->bngqk", qg, kt) / np.sqrt(hd)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    scores = jnp.where(cols <= rows, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqk,bnkh->bngqh", probs, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def layer_body(cfg: TransformerConfig, x: jax.Array, lp: dict,
               cos: jax.Array, sin: jax.Array, constrain,
               mesh=None, reduce=None, attn=None) -> jax.Array:
    """One transformer block. ``constrain`` re-applies the activation
    sharding between ops (sequence-parallel residual stream).

    ``reduce`` (default identity) wraps the two row-parallel matmul
    outputs (wo, w2) — the manual-collective seam: inside a
    ``shard_map`` region with Megatron-sharded weights these products
    are partial sums and the caller passes ``lax.psum(..., 'tp')``
    (pbs_tpu/parallel/pipeline._pipe_blocks); under annotation-driven
    sharding XLA inserts the same collectives itself and the default
    applies. Head reshapes use -1 so the body works on tp SHARDS
    (n_heads/tp local heads) as well as full weights.

    ``attn`` (default: dispatch on ``cfg.attn_impl`` via
    :func:`causal_attention`) is the attention seam — ``(q, k, v) ->
    out``, all (B, S, H, hd) — for callers already inside a manual
    ``shard_map`` region: the ring/ulysses impls wrap their own
    shard_map (illegal to nest), so the pp pipeline passes their
    per-device bodies here with its own mesh axes in scope."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    dt = cfg.dtype
    if reduce is None:
        reduce = lambda t: t  # noqa: E731 — identity seam

    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"].astype(dt)).reshape(B, S, -1, hd)
    k = (h @ lp["wk"].astype(dt)).reshape(B, S, -1, hd)
    v = (h @ lp["wv"].astype(dt)).reshape(B, S, -1, hd)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if attn is None:
        a = causal_attention(q, k, v, cfg, mesh)
    else:
        a = attn(q, k, v)
    x = constrain(x + reduce(a.reshape(B, S, -1) @ lp["wo"].astype(dt)))

    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(h @ lp["w1"].astype(dt))
    up = h @ lp["w3"].astype(dt)
    x = constrain(x + reduce((gate * up) @ lp["w2"].astype(dt)))
    return x


# -- forward / loss ---------------------------------------------------------


def forward_hidden(cfg: TransformerConfig, params: dict,
                   tokens: jax.Array, constrain=lambda x: x,
                   mesh=None) -> jax.Array:
    """tokens (B, S) int32 -> final normed hidden (B, S, d_model)."""
    B, S = tokens.shape
    dt = cfg.dtype
    x = constrain(params["embed"].astype(dt)[tokens])
    cos, sin = rope_tables(cfg, S)

    def body(x, lp, cos, sin):
        return layer_body(cfg, x, lp, cos, sin, constrain, mesh)

    if cfg.remat:
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "full":
            policy = None
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}; "
                "expected 'full' or 'dots'"
            )
        body = jax.checkpoint(body, policy=policy)

    def scan_fn(x, lp):
        return body(x, lp, cos, sin), None

    x, _ = jax.lax.scan(scan_fn, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def forward(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            constrain=lambda x: x, mesh=None) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab) fp32."""
    x = forward_hidden(cfg, params, tokens, constrain, mesh)
    dt = cfg.dtype
    return (x @ params["head"].astype(dt)).astype(jnp.float32)


def token_xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean cross-entropy of int targets under fp32 logits — the one
    loss tail shared by every model family / parallelism schedule."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def shift_targets_and_weights(tokens: jax.Array):
    """Causal-shift targets for a full-S forward: targets[b, s] =
    tokens[b, s+1], with the (targetless) last position zero-padded
    and masked out via the returned fp32 weights. The ONE copy of the
    parity-critical masking both the dense and MoE chunked losses
    use."""
    B, S = tokens.shape
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    weights = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32),
         jnp.zeros((B, 1), jnp.float32)], axis=1)
    return targets, weights


def chunked_head_xent(cfg: TransformerConfig, x: jax.Array,
                      head: jax.Array, targets: jax.Array,
                      weights: jax.Array, n_chunks: int) -> jax.Array:
    """Cross-entropy over the head WITHOUT materializing (B, S, vocab):
    scan over S/n sequence chunks, each computing its logits slab,
    fp32 log-softmax, and target gather, then discarding the slab.
    ``jax.checkpoint`` on the chunk body makes the backward recompute
    each slab in turn — peak loss-tail activation is O(S/n * vocab)
    instead of O(S * vocab), for ~one extra head-matmul pass.

    ``weights`` (B, S) float mask selects which positions count (the
    causal shift leaves the last position targetless). Exact: same
    fp32 reduction as the materialized path, so loss AND grads match
    to numerical noise (pinned by test)."""
    B, S, d = x.shape
    if S % n_chunks:
        raise ValueError(f"S={S} not divisible by loss_chunks={n_chunks}")
    C = S // n_chunks
    dt = cfg.dtype
    # (n, B, C, ...) chunk-major so lax.scan walks the sequence.
    xs = x.reshape(B, n_chunks, C, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n_chunks, C).transpose(1, 0, 2)
    ws = weights.reshape(B, n_chunks, C).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll(carry, xtw):
        xc, tc, wc = xtw
        logits = (xc @ head.astype(dt)).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return carry - jnp.sum(ll * wc), None

    total, _ = jax.lax.scan(chunk_nll, jnp.zeros((), jnp.float32),
                            (xs, ts, ws))
    return total / jnp.sum(weights)


def default_optimizer(learning_rate: float, mu_dtype: Any = None):
    """The framework-standard AdamW recipe (shared by all train steps).

    ``mu_dtype`` stores the first AND second Adam moments in a reduced
    dtype (pass ``jnp.bfloat16``): optimizer state drops from 2x to 1x
    the fp32 param bytes — at the flagship's ~700M that is 2.8 GB of
    HBM back, the difference between fitting batch 8 and not. Update
    math still runs in fp32 (optax upcasts per step); master params
    stay fp32, so only the moment *storage* is rounded.
    """
    import optax

    adam = optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=0.1,
                       mu_dtype=mu_dtype)
    if mu_dtype is None:
        return adam
    # optax's mu_dtype covers the first moment only; the second moment
    # (nu) dominates dynamic range, so rather than truncating it too we
    # round it through the same dtype at the chain boundary — a
    # GradientTransformation that casts nu in/out around the update.
    return _cast_nu(adam, mu_dtype)


def _cast_nu(tx, dtype):
    """Wrap ``tx`` (scale_by_adam-based) so the stored second moment is
    kept in ``dtype`` between steps (fp32 inside the update)."""
    import optax

    def _map_nu(state, cast):
        def walk(s):
            if isinstance(s, optax.ScaleByAdamState):
                return s._replace(nu=jax.tree.map(cast, s.nu))
            if isinstance(s, tuple) and type(s) is not tuple:  # NamedTuple
                return type(s)(*[walk(x) for x in s])
            if isinstance(s, tuple):
                return tuple(walk(x) for x in s)
            return s
        return walk(state)

    def init(params):
        st = tx.init(params)
        return _map_nu(st, lambda x: x.astype(dtype))

    def update(grads, state, params=None):
        st32 = _map_nu(state, lambda x: x.astype(jnp.float32))
        updates, new_state = tx.update(grads, st32, params)
        return updates, _map_nu(new_state, lambda x: x.astype(dtype))

    return optax.GradientTransformation(init, update)


def next_token_loss(cfg: TransformerConfig, params: dict,
                    tokens: jax.Array, constrain=lambda x: x,
                    mesh=None, full_seq: bool = False) -> jax.Array:
    """Causal LM loss: predict tokens[:, 1:] from tokens[:, :-1].

    ``full_seq=True`` runs forward over all S tokens and drops the last
    logit instead of slicing the input — mathematically identical for a
    causal model, but keeps the in-graph sequence length divisible by
    the sp axis for ring attention (S-1 rarely divides the ring size).
    """
    if cfg.loss_chunks > 1:
        # Chunked loss tail: forward ALL S tokens to hidden (so the
        # chunk count divides a power-of-two S, not S-1), then scan
        # the head with the last position masked out — identical
        # arithmetic to the materialized causal loss.
        x = forward_hidden(cfg, params, tokens, constrain, mesh)
        targets, weights = shift_targets_and_weights(tokens)
        return chunked_head_xent(cfg, x, params["head"], targets,
                                 weights, cfg.loss_chunks)
    if full_seq:
        logits = forward(cfg, params, tokens, constrain, mesh)
        return token_xent(logits[:, :-1], tokens[:, 1:])
    logits = forward(cfg, params, tokens[:, :-1], constrain, mesh)
    return token_xent(logits, tokens[:, 1:])


# -- training step ----------------------------------------------------------


def make_train_step(cfg: TransformerConfig, learning_rate: float = 3e-4,
                    constrain=lambda x: x, mesh=None,
                    full_seq: bool = False, mu_dtype: Any = None):
    """Returns (init_opt_state, train_step). AdamW via optax; donate-safe.

    ``train_step(state, tokens) -> (state, metrics)`` where state is
    (params, opt_state, step). The metrics dict feeds the TpuBackend
    telemetry channel (tokens counted for throughput attribution).
    ``mu_dtype`` reduces Adam moment storage (see default_optimizer).
    """
    import optax

    tx = default_optimizer(learning_rate, mu_dtype=mu_dtype)

    def init_opt_state(params):
        return tx.init(params)

    def train_step(state, tokens):
        params, opt_state, step = state
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(cfg, p, tokens, constrain, mesh,
                                      full_seq)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        ntok = tokens.shape[0] * (tokens.shape[1] - 1)
        metrics = {"loss": loss, "tokens": jnp.asarray(ntok, jnp.int32)}
        return (params, opt_state, step + 1), metrics

    return init_opt_state, train_step


def make_eval_step(cfg: TransformerConfig, constrain=lambda x: x,
                   mesh=None, full_seq: bool = False):
    def eval_step(params, tokens):
        return next_token_loss(cfg, params, tokens, constrain, mesh,
                               full_seq)

    return eval_step
