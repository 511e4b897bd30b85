"""Expert parallelism: shard the MoE expert axis over an ``ep`` mesh axis.

The scaling-book recipe applied to MoE: annotate the expert-stacked
weights and the (E, C, d) dispatch buffers with ``P('ep', ...)`` while
tokens stay batch-sharded over ``dp`` — XLA lowers the dispatch/combine
einsums into the token all-to-all over ICI. No hand-written collective;
the reference's closest communication analog is grant-table zero-copy
page exchange (``xen/common/grant_table.c``), here expressed entirely
through sharding annotations (SURVEY.md §2e, §5 "distributed
communication backend").
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pbs_tpu.models.moe import MoEConfig, init_moe_params, make_moe_train_step


def moe_param_specs(cfg: MoEConfig) -> dict:
    """Experts over ``ep``; attention + router replicated (an MoE mesh is
    dp x ep; a tp axis can be added orthogonally later)."""
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, None),
            "wk": P(None, None, None),
            "wv": P(None, None, None),
            "wo": P(None, None, None),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "we1": P(None, "ep", None, None),
            "we3": P(None, "ep", None, None),
            "we2": P(None, "ep", None, None),
        },
        "final_norm": P(None),
        "head": P(None, None),
    }


def _named(mesh: Mesh, tree):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_moe_params(params: dict, mesh: Mesh, cfg: MoEConfig) -> dict:
    return jax.tree.map(
        jax.device_put, params, _named(mesh, moe_param_specs(cfg))
    )


def moe_batch_sharding(mesh: Mesh) -> NamedSharding:
    # One definition of "how token batches shard" for every family —
    # a dense/MoE divergence here would be a silent parity break.
    from pbs_tpu.parallel.sharding import batch_sharding

    return batch_sharding(mesh)


def expert_constrainer(mesh: Mesh | None):
    """Pins (E, C, d) expert buffers to P('ep', None, None): the boundary
    where the token all-to-all materializes."""
    if mesh is None or "ep" not in mesh.axis_names:
        return lambda x: x
    spec = NamedSharding(mesh, P("ep", None, None))

    def constrain(x):
        if x.ndim == 3:
            return jax.lax.with_sharding_constraint(x, spec)
        return x

    return constrain


def residual_constrainer(mesh: Mesh | None):
    if mesh is None or "dp" not in mesh.axis_names:
        return lambda x: x
    seq = "sp" if "sp" in mesh.axis_names else None
    spec = NamedSharding(mesh, P("dp", seq, None))

    def constrain(x):
        if x.ndim == 3:
            return jax.lax.with_sharding_constraint(x, spec)
        return x

    return constrain


def make_sharded_moe_train(
    cfg: MoEConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    key: jax.Array | None = None,
):
    """Fully-sharded MoE train state + jitted step on a dp x ep mesh —
    or dp x ep x sp for long-context MoE (cfg.attn_impl "ring" or
    "ulysses": the sequence stays sharded through attention while the
    expert all-to-all rides ep). Opt-state layouts derive from the
    sharded params (propagation)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    seq_par = cfg.attn_impl in ("ring", "ulysses")
    if seq_par and "sp" not in mesh.axis_names:
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} requires an 'sp' axis in the "
            f"mesh; got axes {mesh.axis_names}"
        )
    init_opt, train_step = make_moe_train_step(
        cfg, learning_rate,
        constrain=residual_constrainer(mesh),
        constrain_ec=expert_constrainer(mesh),
        mesh=mesh if seq_par else None,
        full_seq=seq_par,
    )
    params = shard_moe_params(init_moe_params(cfg, key), mesh, cfg)
    opt_state = jax.jit(init_opt)(params)
    state = (params, opt_state, jax.device_put(0))
    step = jax.jit(train_step, donate_argnums=(0,))
    return state, step
