"""Sharding rules for the flagship transformer: dp + tp + sp by annotation.

The scaling-book recipe (SURVEY.md directive): pick a mesh, annotate
parameter and activation shardings, let XLA insert the collectives —
psum for dp gradient reduction, all-gathers around tp matmuls,
reduce-scatters for the sequence-parallel residual stream. No hand-rolled
NCCL analog exists or is needed; ICI collectives are compiled.

Layout (Megatron-style, re-derived for annotation form):

- embed (V, d)        -> P('tp', None)      vocab-sharded lookup
- wq/wk/wv (L, d, H)  -> P(None, None, 'tp') column-parallel
- wo (L, H, d)        -> P(None, 'tp', None) row-parallel
- w1/w3 (L, d, F)     -> P(None, None, 'tp') column-parallel
- w2 (L, F, d)        -> P(None, 'tp', None) row-parallel
- head (d, V)         -> P(None, 'tp')      vocab-sharded logits
- norms               -> replicated
- tokens (B, S)       -> P('dp', None)
- residual (B, S, d)  -> P('dp', 'tp', None): batch over dp, *sequence
  over tp* between blocks — sequence parallelism for the elementwise/
  norm regions, gathered by XLA where attention needs full sequence.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pbs_tpu.models.transformer import TransformerConfig, make_train_step


def param_specs(cfg: TransformerConfig) -> dict:
    return {
        "embed": P("tp", None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            "w1": P(None, None, "tp"),
            "w3": P(None, None, "tp"),
            "w2": P(None, "tp", None),
        },
        "final_norm": P(None),
        "head": P(None, "tp"),
    }


def _mesh_spec(mesh: Mesh, spec: P) -> P:
    """Drop axis names the mesh doesn't have (e.g. 'tp' specs on a
    dp x sp mesh) so one canonical spec table serves every mesh shape."""
    def keep(entry):
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            return kept or None
        return entry if entry in mesh.axis_names else None

    return P(*(keep(a) for a in spec))


def _named(mesh: Mesh, tree):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, _mesh_spec(mesh, spec)), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params: dict, mesh: Mesh, cfg: TransformerConfig) -> dict:
    shardings = _named(mesh, param_specs(cfg))
    return jax.tree.map(jax.device_put, params, shardings)


def quant_aware_shardings(specs: dict, params: dict, mesh: Mesh):
    """Shardings for a param tree that may hold int8-quantized leaves.

    A quantized leaf is ``{"q": int8 (same shape as the fp weight),
    "s": fp32 scales (same RANK, size 1 on the reduced axis -2)}``
    (models/quant._quantize_leaf). ``q`` takes the fp spec verbatim;
    ``s`` takes the fp spec with any sharding on axis -2 dropped —
    sharding a size-1 dimension is invalid, and the per-output-channel
    scales live on the LAST axis, which keeps its sharding (so a
    column-parallel weight's scales shard with its outputs and the
    fused dequant stays local). Plain leaves map 1:1."""
    def walk(spec, p):
        if isinstance(p, dict) and set(p) == {"q", "s"}:
            r = p["q"].ndim
            se = list(spec) + [None] * (r - len(list(spec)))
            se[r - 2] = None
            return {
                "q": NamedSharding(mesh, _mesh_spec(mesh, spec)),
                "s": NamedSharding(mesh, _mesh_spec(mesh, P(*se))),
            }
        if isinstance(p, dict):
            return {k: walk(spec[k], p[k]) for k in p}
        return NamedSharding(mesh, _mesh_spec(mesh, spec))

    return {k: walk(specs[k], params[k]) for k in params}


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches: batch over dp; sequence over sp when the mesh has
    a ring-attention axis (long-context inputs arrive pre-sharded)."""
    seq = "sp" if "sp" in mesh.axis_names else None
    return NamedSharding(mesh, P("dp", seq))


def slot_cache_kv_sharding(mesh: Mesh) -> NamedSharding:
    """KV slot-cache slabs ``(layer, slot, pos, n_kv, head_dim)``:
    shard the kv-head axis over tp, everything else replicated — the
    serving twin of the Megatron attention layout above. The single
    home for this spec: mesh-axis names stay inside ``parallel/`` (the
    ``serve-raw-mesh-axis`` rule, docs/ANALYSIS.md)."""
    if "tp" not in mesh.axis_names:
        raise ValueError(
            f"serving mesh needs a 'tp' axis; got {mesh.axis_names}")
    return NamedSharding(mesh, P(None, None, None, "tp", None))


def activation_constrainer(mesh: Mesh | None):
    """Returns the ``constrain`` fn threaded through the model: pins the
    residual stream (B, S, d).

    - tp-only mesh: P('dp','tp',None) — sequence parallelism rides the
      tp axis between blocks (Megatron sp), gathered where attention
      needs the full sequence.
    - sp mesh (ring attention): P('dp','sp',None) — the sequence stays
      sharded *through* attention; the ring rotates k/v instead of
      gathering.
    """
    if mesh is None:
        return lambda x: x
    if "sp" in mesh.axis_names:
        spec = NamedSharding(mesh, P("dp", "sp", None))
    elif "tp" in mesh.axis_names:
        spec = NamedSharding(mesh, P("dp", "tp", None))
    else:
        return lambda x: x

    def constrain(x):
        if x.ndim == 3:
            return jax.lax.with_sharding_constraint(x, spec)
        return x

    return constrain


def make_sharded_train(
    cfg: TransformerConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    key: jax.Array | None = None,
):
    """Build fully-sharded (params, opt_state, step) + jitted train step.

    Opt-state shardings are not spelled out: XLA sharding propagation
    derives mu/nu layouts from the sharded params flowing into the
    jitted init — the annotation-driven recipe end to end.
    """
    from pbs_tpu.models.transformer import init_params

    key = key if key is not None else jax.random.PRNGKey(0)
    constrain = activation_constrainer(mesh)
    # Sequence-parallel attention (ring / ulysses) needs the mesh
    # in-graph (shard_map) and a sequence length divisible by the sp
    # axis — full_seq keeps S intact in-graph.
    seq_par = cfg.attn_impl in ("ring", "ulysses")
    if seq_par and "sp" not in mesh.axis_names:
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} requires an 'sp' axis in the "
            f"mesh; got axes {mesh.axis_names}"
        )
    init_opt, train_step = make_train_step(
        cfg, learning_rate, constrain, mesh=mesh if seq_par else None,
        full_seq=seq_par,
    )

    # NamedSharding carries its mesh: no ambient mesh context needed.
    params = shard_params(init_params(cfg, key), mesh, cfg)
    opt_state = jax.jit(init_opt)(params)
    state = (params, opt_state, jax.device_put(0))
    step = jax.jit(train_step, donate_argnums=(0,))
    return state, step
