"""Pipeline parallelism: GPipe-style microbatch pipeline over a ``pp`` axis.

Where dp/tp/sp/ep are pure annotation (XLA infers the collectives),
pipelining is inherently a *schedule* — so this is the one place the
framework drops into ``shard_map`` and moves activations explicitly with
``lax.ppermute`` over the ICI ring (SURVEY.md §2e: the reference's only
"pipeline" analog is vCPU migration between pCPUs; this is the TPU-first
replacement, not a translation).

Design:

- The layer-stacked params (L, ...) are sharded ``P('pp', ...)``: stage i
  holds layers [i*L/pp, (i+1)*L/pp) — no resharding, the scan-over-layers
  layout *is* the pipeline layout.
- Inside ``shard_map`` each tick runs every stage on its current
  microbatch, then ``ppermute`` shifts activations one stage down the
  ring. M microbatches drain in M + pp - 1 ticks (the GPipe bubble;
  bubble fraction = (pp-1)/(M+pp-1), amortized by raising M).
- The batch stays sharded over ``dp`` *inside* the manual region (specs
  carry both axes), so dp x pp compose; tp/sp can ride the remaining
  in-stage axes via the activation constrainer as in the dense path.
- Backward is plain autodiff through the schedule: ppermute transposes
  to the reverse permute, param cotangents psum over dp at the shard_map
  boundary. Stage bodies are rematerialized (``jax.checkpoint``) so live
  activation memory is one microbatch per in-flight tick, the GPipe
  memory contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pbs_tpu.models.transformer import (
    TransformerConfig,
    default_optimizer,
    layer_body,
    rms_norm,
    rope_tables,
    token_xent,
)


def pipeline_layer_specs(tp: bool = False) -> dict:
    """Specs for the layer-stacked subtree: stage-sharded on axis 0.

    With ``tp`` the in-stage weights additionally shard Megatron-style
    over the ``tp`` axis: qkv/gate/up column-parallel (output dim),
    wo/w2 row-parallel (input dim); norms replicate over tp (the full
    residual stream is needed for the d-dim reduction)."""
    t = "tp" if tp else None
    return {
        "attn_norm": P("pp", None),
        "wq": P("pp", None, t),
        "wk": P("pp", None, t),
        "wv": P("pp", None, t),
        "wo": P("pp", t, None),
        "mlp_norm": P("pp", None),
        "w1": P("pp", None, t),
        "w3": P("pp", None, t),
        "w2": P("pp", t, None),
    }


def _full_tree_specs(layer_specs: dict) -> dict:
    """Full-tree specs around any stage subtree: embed/head replicated
    (they run outside the manual region, dp-sharded by activation),
    blocks per the given layer specs — ONE copy for the dense and MoE
    pipelines."""
    return {
        "embed": P(None, None),
        "layers": layer_specs,
        "final_norm": P(None),
        "head": P(None, None),
    }


def _shard_by_specs(params: dict, mesh: Mesh, specs: dict) -> dict:
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.tree.map(jax.device_put, params, shardings)


def pipeline_param_specs(cfg: TransformerConfig, tp: bool = False) -> dict:
    return _full_tree_specs(pipeline_layer_specs(tp))


def shard_pipeline_params(params: dict, mesh: Mesh,
                          cfg: TransformerConfig) -> dict:
    tp = mesh.shape.get("tp", 1) > 1
    return _shard_by_specs(params, mesh, pipeline_param_specs(cfg, tp))


def _validate_pipe_attn(cfg: TransformerConfig, tp: int, sp: int) -> None:
    """Shared attn-impl/mesh compatibility rules for the pipelined
    stage bodies (round-5: the former blanket attn_impl='xla' guard is
    lifted — the pipeline must compose with the framework's own
    kernels and the long-context impls, VERDICT r4 #4)."""
    if cfg.attn_impl not in ("xla", "pallas", "ring", "ulysses"):
        raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")
    if cfg.attn_impl in ("ring", "ulysses") and sp <= 1:
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} inside the pp schedule needs "
            "an 'sp' axis (>1) in the SAME mesh — the sequence-parallel "
            "bodies run in the pipe's own manual region"
        )
    if sp > 1 and cfg.attn_impl not in ("ring", "ulysses"):
        raise ValueError(
            f"an sp axis shards the sequence, but attn_impl="
            f"{cfg.attn_impl!r} attends only within the local chunk "
            "(silently block-diagonal); use 'ring' or 'ulysses'"
        )
    if cfg.attn_impl == "ulysses":
        if tp > 1:
            raise ValueError(
                "ulysses does not compose with tensor parallelism "
                "(both shard heads); use ring attention on tp meshes"
            )
        if cfg.n_heads % sp or cfg.n_kv_heads % sp:
            raise ValueError(
                f"ulysses needs n_heads ({cfg.n_heads}) and n_kv_heads "
                f"({cfg.n_kv_heads}) divisible by sp ({sp}); use ring "
                "attention for this shape"
            )


def _pipe_attn_seam(cfg: TransformerConfig, sp: int):
    """The per-device attention body for the pipelined stages, or None
    for the impls :func:`layer_body` dispatches itself ('xla' runs the
    einsum path; 'pallas' calls the flash kernel directly — Mosaic on
    chip, interpreter mode off-TPU — neither needs mesh axes).

    ring/ulysses CANNOT be reached through ``causal_attention`` here:
    their public wrappers open their own shard_map, and shard_map does
    not nest — so the pipe hands their per-device bodies to
    layer_body's ``attn`` seam with the pipe's 'sp' axis in scope."""
    if cfg.attn_impl == "ring":
        from pbs_tpu.parallel.ring_attention import (
            _ring_attention_local,
            _ring_attention_local_flash,
        )

        if cfg.ring_block == "flash":
            return functools.partial(
                _ring_attention_local_flash, axis_name="sp", causal=True)
        sm = 1.0 / float(cfg.head_dim) ** 0.5
        return functools.partial(
            _ring_attention_local, axis_name="sp", causal=True,
            sm_scale=sm)
    if cfg.attn_impl == "ulysses":
        from pbs_tpu.parallel.ulysses import _ulysses_local

        sm = 1.0 / float(cfg.head_dim) ** 0.5
        return functools.partial(
            _ulysses_local, axis_name="sp", causal=True, sm_scale=sm,
            block_impl=cfg.ring_block)
    return None


def _pipe_rope(cfg: TransformerConfig, S_local: int, sp: int):
    """Rope tables for the LOCAL sequence chunk: with an sp axis each
    device holds S/sp positions, so the global tables are sliced at the
    device's chunk offset (positions are global, storage is local)."""
    cos, sin = rope_tables(cfg, S_local * sp)
    if sp > 1:
        off = jax.lax.axis_index("sp") * S_local
        cos = jax.lax.dynamic_slice_in_dim(cos, off, S_local, 0)
        sin = jax.lax.dynamic_slice_in_dim(sin, off, S_local, 0)
    return cos, sin


def _pipe_blocks(cfg: TransformerConfig, mesh: Mesh, n_micro: int):
    """Builds the shard_map'd pipelined block-stack: (layers, xs) -> ys
    with xs/ys (M, mb, S, d) dp-sharded on mb (and, with a tp axis in
    the mesh, the in-stage weights Megatron-sharded over tp; with an
    sp axis, the sequence sharded and attention run via the ring or
    ulysses per-device bodies)."""
    pp = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    sp = mesh.shape.get("sp", 1)
    if cfg.n_layers % pp != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={pp}"
        )
    if tp > 1:
        if cfg.n_heads % tp or cfg.n_kv_heads % tp or cfg.d_ff % tp:
            raise ValueError(
                f"tp={tp} must divide n_heads={cfg.n_heads}, "
                f"n_kv_heads={cfg.n_kv_heads}, and d_ff={cfg.d_ff}"
            )
    _validate_pipe_attn(cfg, tp, sp)

    def pipe(layers, xs):
        # Manual per-device view: layers (L/pp, ...),
        # xs (M, mb/dp, S/sp, d).
        idx = jax.lax.axis_index("pp")
        cos, sin = _pipe_rope(cfg, xs.shape[2], sp)
        attn_fn = _pipe_attn_seam(cfg, sp)

        # With tp > 1 each device holds a Megatron shard of the stage
        # weights; layer_body's reduce seam makes the row-parallel
        # partial sums explicit psums over tp (the manual-collective
        # form of the annotation-driven sharding the dense path uses).
        reduce = (lambda t: jax.lax.psum(t, "tp")) if tp > 1 else None

        def stage(x):
            def scan_fn(x, lp):
                return layer_body(cfg, x, lp, cos, sin, lambda a: a,
                                  reduce=reduce, attn=attn_fn), None

            x, _ = jax.lax.scan(jax.checkpoint(scan_fn), x, layers)
            return x

        perm = [(i, i + 1) for i in range(pp - 1)]
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        for t in range(n_micro + pp - 1):  # static GPipe schedule
            x_in = jnp.where(idx == 0, xs[min(t, n_micro - 1)], state)
            y = stage(x_in)
            if t >= pp - 1:
                # Only the last stage's writes are ever read back.
                outs = outs.at[t - pp + 1].set(y)
            if perm:
                state = jax.lax.ppermute(y, "pp", perm)
        return outs

    s = "sp" if sp > 1 else None
    kwargs = dict(
        mesh=mesh,
        in_specs=(pipeline_layer_specs(tp > 1), P(None, "dp", s, None)),
        out_specs=P("pp", "dp", s, None),
    )
    return shard_map(pipe, check_vma=False, **kwargs)


def make_pipelined_loss(cfg: TransformerConfig, mesh: Mesh, n_micro: int):
    """Causal-LM loss with the block stack pipelined over ``pp``.

    Embedding/head/loss run outside the manual region under plain dp
    sharding; only the layer stack is scheduled.  With an ``sp`` axis
    the forward runs over all S tokens (S-1 rarely divides the ring
    size — the same full-seq trick as ``next_token_loss``) with the
    targetless last position masked out of the loss; mathematically
    identical for a causal model.
    """
    pipe = _pipe_blocks(cfg, mesh, n_micro)
    sp = mesh.shape.get("sp", 1)
    s = "sp" if sp > 1 else None
    mb_spec = NamedSharding(mesh, P(None, "dp", s, None))

    def loss_fn(params, tokens):
        B, S_full = tokens.shape
        if B % n_micro != 0:
            raise ValueError(f"batch {B} not divisible by M={n_micro}")
        full_seq = sp > 1
        inp = tokens if full_seq else tokens[:, :-1]
        S = S_full if full_seq else S_full - 1
        if S % sp:
            raise ValueError(f"seq {S} not divisible by sp={sp}")
        mb = B // n_micro
        dt = cfg.dtype
        x = params["embed"].astype(dt)[inp]
        xs = jax.lax.with_sharding_constraint(
            x.reshape(n_micro, mb, S, cfg.d_model), mb_spec
        )
        ys = pipe(params["layers"], xs)
        # Global ys is (pp*M, mb, S, d); the final M rows live on the
        # last stage — slicing them is a device-local read, not a gather.
        y = ys[-n_micro:].reshape(B, S, cfg.d_model)
        y = rms_norm(y, params["final_norm"], cfg.norm_eps)
        logits = (y @ params["head"].astype(dt)).astype(jnp.float32)
        if not full_seq:
            return token_xent(logits, tokens[:, 1:])
        from pbs_tpu.models.transformer import shift_targets_and_weights

        targets, weights = shift_targets_and_weights(tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.sum(ll * weights) / jnp.sum(weights)

    return loss_fn


def make_pipelined_train(
    cfg: TransformerConfig,
    mesh: Mesh,
    n_micro: int = 4,
    learning_rate: float = 3e-4,
    key: jax.Array | None = None,
):
    """Fully-sharded dp x pp train state + jitted step."""
    import optax

    from pbs_tpu.models.transformer import init_params

    key = key if key is not None else jax.random.PRNGKey(0)
    loss_fn = make_pipelined_loss(cfg, mesh, n_micro)
    tx = default_optimizer(learning_rate)

    def train_step(state, tokens):
        params, opt_state, step = state
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        ntok = tokens.shape[0] * (tokens.shape[1] - 1)
        return (params, opt_state, step + 1), {
            "loss": loss, "tokens": jnp.asarray(ntok, jnp.int32),
        }

    params = shard_pipeline_params(init_params(cfg, key), mesh, cfg)
    opt_state = jax.jit(tx.init)(params)
    state = (params, opt_state, jax.device_put(0))
    step = jax.jit(train_step, donate_argnums=(0,))
    return state, step


def pipeline_batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp", None))


# -- MoE pipeline: pp x ep (+dp) --------------------------------------------


def moe_pipeline_layer_specs(ep: bool = False) -> dict:
    """MoE stage subtree: layers stage-sharded on axis 0; with ``ep``
    the expert tensors additionally shard over the ep axis. Attention
    weights and the router replicate over ep (full-E routing is
    recomputed per ep shard — cheap next to expert FLOPs — and the
    expert combine is the one psum)."""
    e = "ep" if ep else None
    return {
        "attn_norm": P("pp", None),
        "wq": P("pp", None, None),
        "wk": P("pp", None, None),
        "wv": P("pp", None, None),
        "wo": P("pp", None, None),
        "mlp_norm": P("pp", None),
        "router": P("pp", None, None),
        "we1": P("pp", e, None, None),
        "we3": P("pp", e, None, None),
        "we2": P("pp", e, None, None),
    }


def _moe_pipe_blocks(cfg, mesh: Mesh, n_micro: int):
    """shard_map'd pipelined MoE block-stack: (layers, xs) ->
    (ys, aux (1,), drop (1,)). GPipe schedule identical to the dense
    pipe; each stage runs full-E routing and its LOCAL expert shard,
    psum-combining over ep. Bubble ticks are masked out of the aux
    accumulation — they process garbage activations and their aux
    would otherwise leak into the LOSS gradient."""
    from pbs_tpu.models.moe import (
        moe_layer_body,
        routed_expert_ffn,
        routing_groups,
        top_k_dispatch,
    )

    pp = mesh.shape["pp"]
    ep = mesh.shape.get("ep", 1)
    sp = mesh.shape.get("sp", 1)
    if cfg.n_layers % pp != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={pp}"
        )
    if cfg.n_experts % ep != 0:
        raise ValueError(
            f"ep={ep} must divide n_experts={cfg.n_experts}"
        )
    # Same composition rules as the dense pipe; with an sp axis the
    # router sees each device's LOCAL token chunk — routing is
    # per-token so expert OUTPUTS are unaffected (exactly so in
    # dropless mode, where capacity can never bind), only the
    # grouping of the aux statistic changes (it is pmean'd over sp).
    _validate_pipe_attn(cfg, tp=1, sp=sp)
    el = cfg.n_experts // ep

    def pipe(layers, xs):
        idx = jax.lax.axis_index("pp")
        cos, sin = _pipe_rope(cfg, xs.shape[2], sp)
        attn_fn = _pipe_attn_seam(cfg, sp)
        dt = cfg.dtype

        def sharded_ffn(h, lp):
            # The ep-manual routed FFN behind moe_layer_body's mlp
            # seam: full-E routing recomputed per shard (identical on
            # every ep device), expert compute on the LOCAL slice,
            # partial combines psum'd over ep.
            B_, S_, _ = h.shape
            g, G, Cg = routing_groups(cfg, B_ * S_)
            xg = h.reshape(G, g, cfg.d_model)
            logits = xg.astype(jnp.float32) @ lp["router"].astype(
                jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            dispatch, combine, aux, drop = jax.vmap(
                lambda p: top_k_dispatch(p, cfg.top_k, Cg)
            )(probs)
            if ep > 1:
                e0 = jax.lax.axis_index("ep") * el
                dispatch = jax.lax.dynamic_slice_in_dim(
                    dispatch, e0, el, 2)
                combine = jax.lax.dynamic_slice_in_dim(
                    combine, e0, el, 2)
            y = routed_expert_ffn(xg, dispatch, combine, lp, dt)
            if ep > 1:
                y = jax.lax.psum(y, "ep")
            return (y.reshape(B_, S_, cfg.d_model), jnp.mean(aux),
                    jnp.mean(drop))

        def block(x, lp):
            return moe_layer_body(
                cfg, x, lp, cos, sin, lambda a: a, lambda a: a,
                mesh=None, mlp=sharded_ffn, attn=attn_fn)

        def stage(x):
            def scan_fn(carry, lp):
                x, a, dr = carry
                x, a2, d2 = block(x, lp)
                return (x, a + a2, dr + d2), None

            (x, a, dr), _ = jax.lax.scan(
                jax.checkpoint(scan_fn), (x, 0.0, 0.0), layers)
            return x, a, dr

        perm = [(i, i + 1) for i in range(pp - 1)]
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        aux_acc = 0.0
        drop_acc = 0.0
        for t in range(n_micro + pp - 1):  # static GPipe schedule
            x_in = jnp.where(idx == 0, xs[min(t, n_micro - 1)], state)
            y, a, dr = stage(x_in)
            active = jnp.logical_and(t - idx >= 0, t - idx < n_micro)
            aux_acc = aux_acc + jnp.where(active, a, 0.0)
            drop_acc = drop_acc + jnp.where(active, dr, 0.0)
            if t >= pp - 1:
                outs = outs.at[t - pp + 1].set(y)
            if perm:
                state = jax.lax.ppermute(y, "pp", perm)
        # Sum over stages = sum over ALL layers x microbatches; the
        # ep shards computed identical full-E routing, so no ep sum.
        # With sp each shard routed its LOCAL chunk: average the aux
        # statistic over sp so the output is genuinely replicated on
        # that axis (its out spec claims so).
        aux_tot = jax.lax.psum(aux_acc, "pp")
        drop_tot = jax.lax.psum(drop_acc, "pp")
        if sp > 1:
            aux_tot = jax.lax.pmean(aux_tot, "sp")
            drop_tot = jax.lax.pmean(drop_tot, "sp")
        return (outs, jnp.reshape(aux_tot, (1,)),
                jnp.reshape(drop_tot, (1,)))

    s = "sp" if sp > 1 else None
    kwargs = dict(
        mesh=mesh,
        in_specs=(moe_pipeline_layer_specs(ep > 1),
                  P(None, "dp", s, None)),
        out_specs=(P("pp", "dp", s, None), P("dp"), P("dp")),
    )
    return shard_map(pipe, check_vma=False, **kwargs)


def make_pipelined_moe_train(
    cfg,
    mesh: Mesh,
    n_micro: int = 4,
    learning_rate: float = 3e-4,
    key: jax.Array | None = None,
):
    """dp x pp x ep MoE train state + jitted step. Loss = token xent
    + aux_loss_weight * load-balance aux (bubble-masked, normalized
    per layer per microbatch, matching ``moe_loss`` semantics when
    routing groups align — dropless mode or group size dividing the
    per-microbatch token count)."""
    import optax

    from pbs_tpu.models.moe import init_moe_params
    from pbs_tpu.models.transformer import (
        rms_norm as _rms,
        token_xent as _xent,
    )

    key = key if key is not None else jax.random.PRNGKey(0)
    pipe = _moe_pipe_blocks(cfg, mesh, n_micro)
    sp = mesh.shape.get("sp", 1)
    s = "sp" if sp > 1 else None
    mb_spec = NamedSharding(mesh, P(None, "dp", s, None))
    tx = default_optimizer(learning_rate)

    def loss_fn(params, tokens):
        B, S_full = tokens.shape
        if B % n_micro != 0:
            raise ValueError(f"batch {B} not divisible by M={n_micro}")
        # Same full-seq trick as the dense pipelined loss: with sp the
        # in-graph sequence must divide the axis (S-1 rarely does).
        full_seq = sp > 1
        inp = tokens if full_seq else tokens[:, :-1]
        S = S_full if full_seq else S_full - 1
        if S % sp:
            raise ValueError(f"seq {S} not divisible by sp={sp}")
        mb = B // n_micro
        dt = cfg.dtype
        x = params["embed"].astype(dt)[inp]
        xs = jax.lax.with_sharding_constraint(
            x.reshape(n_micro, mb, S, cfg.d_model), mb_spec
        )
        ys, aux_v, drop_v = pipe(params["layers"], xs)
        y = ys[-n_micro:].reshape(B, S, cfg.d_model)
        y = _rms(y, params["final_norm"], cfg.norm_eps)
        logits = (y @ params["head"].astype(dt)).astype(jnp.float32)
        if full_seq:
            from pbs_tpu.models.transformer import (
                shift_targets_and_weights,
            )

            targets, weights = shift_targets_and_weights(tokens)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
            lm = -jnp.sum(ll * weights) / jnp.sum(weights)
        else:
            lm = _xent(logits, tokens[:, 1:])
        aux = jnp.mean(aux_v) / (cfg.n_layers * n_micro)
        drop = jnp.mean(drop_v) / (cfg.n_layers * n_micro)
        return lm + cfg.aux_loss_weight * aux, (lm, aux, drop)

    def train_step(state, tokens):
        params, opt_state, step = state
        (_, (lm, aux, drop)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        ntok = tokens.shape[0] * (tokens.shape[1] - 1)
        return (params, opt_state, step + 1), {
            "loss": lm, "aux_loss": aux, "moe_drop_frac": drop,
            "tokens": jnp.asarray(ntok, jnp.int32),
        }

    specs = _full_tree_specs(
        moe_pipeline_layer_specs(mesh.shape.get("ep", 1) > 1))
    params = _shard_by_specs(init_moe_params(cfg, key), mesh, specs)
    opt_state = jax.jit(tx.init)(params)
    state = (params, opt_state, jax.device_put(0))
    step = jax.jit(train_step, donate_argnums=(0,))
    return state, step
