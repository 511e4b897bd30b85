"""The family of decoders whose layers differ: window beside full
grouped-query attention with their own head counts and rotary settings,
a per-head output gate, a leading dense SwiGLU layer and then routed
experts beside a shared one (Laguna-S-2.1, ``model_type: laguna``).
Serving only: the program's training step runs uniform stacks.

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: per layer its attention kind and
its MLP kind, and of each expert layer the share of the experts this
chip holds (``num_experts`` of ``deployment.experts_total``, from
``deployment.experts_first``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import moe_mixed_gqa_costs as costs
from benchmarks.reference import moe_mixed_attn as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        raise SystemExit(
            "benchmarks/families/moe-mixed-gqa.py: this program has no "
            "layer plan (pbs_tpu/models/plan.py): it cannot serve a "
            "decoder whose layers differ") from None
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    hd = c["head_dim"]

    def rope(rp: dict):
        return P.Rope(
            theta=float(rp["rope_theta"]),
            rotary_dim=int(hd * rp.get("partial_rotary_factor", 1)),
            factor=float(rp.get("factor", 1.0)),
            original_max=int(rp.get("original_max_position_embeddings", 0)),
            beta_fast=float(rp.get("beta_fast", 32)),
            beta_slow=float(rp.get("beta_slow", 1)),
            attention_factor=float(rp.get("attention_factor", 1.0)))

    if not c["norm_topk_prob"]:
        raise NotImplementedError(
            "the program's expert layer renormalises the chosen experts' "
            "weights (norm_topk_prob true); this configuration does not")
    attn, mlp, layers = [], [], []
    for l in range(n_layers):
        kind = c["layer_types"][l]
        a = P.AttnKind(
            name=kind,
            n_heads=c["num_attention_heads_per_layer"][l],
            window=c["sliding_window"] if kind == "sliding_attention"
            else None,
            rope=rope(c["rope_parameters"][kind]),
            head_gate=c["gating_types"][l] == "per_head")
        if c["mlp_layer_types"][l] == "dense":
            m = P.MlpKind("dense", c["intermediate_size"])
        else:
            m = P.MlpKind(
                "experts", c["moe_intermediate_size"],
                n_experts=c["deployment"]["experts_total"],
                top_k=c["num_experts_per_tok"],
                held=reference.held_range(c),
                shared_d_ff=c["shared_expert_intermediate_size"],
                routed_scale=float(c["moe_routed_scaling_factor"]))
        for kinds, k in ((attn, a), (mlp, m)):
            if k not in kinds:
                kinds.append(k)
        layers.append((attn.index(a), mlp.index(m)))
    return P.LayerPlan(tuple(attn), tuple(mlp), tuple(layers))


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    plan = layer_plan(c, n_layers)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, norm_eps=float(c["rms_norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]], head_size=c["head_dim"],
        layer_plan=plan, **extra)


def train_step(cfg, learning_rate: float):
    raise NotImplementedError(
        "the program trains uniform layer stacks only: the grouped expert "
        "layer has no backward here (ROADMAP R3), and by the count in "
        "PERF.md section 4 this model's floor cut leaves a 16 GB chip "
        "under 3 GB for a step's activations")


def serve_weights(c: dict, seed: int):
    """bfloat16 weights of the held share at the serving depth, made
    where the backend's rule table will place them."""
    from pbs_tpu.serve.partition import make_serve_mesh, rule_shardings

    sv = c["serve"]
    make = lambda s: reference.init_tree(  # noqa: E731
        c, s, sv["num_hidden_layers"], DTYPES[sv["weights_dtype"]])
    word = reference.seed_word(seed)
    shardings = rule_shardings(jax.eval_shape(make, word),
                               make_serve_mesh(tp=1, dp=1))
    return jax.jit(make, out_shardings=shardings)(word)


def serve_backend(name: str, cfg, c: dict, seed: int, engine_cls):
    from pbs_tpu.serve import ShardedServeBackend

    sv = c["serve"]
    return ShardedServeBackend(
        name, cfg, serve_weights(c, seed), tp=1, dp=1,
        n_slots=int(sv["slots"]), prompt_bucket=int(sv["prompt_bucket"]),
        max_len=int(sv["max_len"]), engine_cls=engine_cls)


# -- costs ------------------------------------------------------------------


def _expert_matmul_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("experts_touched") is None:
        return None
    return {"bytes": costs.expert_bytes(c, sizes["experts_touched"])}


def _kv_read_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("live_positions") is None:
        return None
    sv = c["serve"]
    return {"bytes": costs.kv_read_bytes(
        c, sv["num_hidden_layers"], sizes["live_positions"],
        sizes["live_window_positions"])}


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    kv, ex = _kv_read_cost(c, sizes), _expert_matmul_cost(c, sizes)
    if kv is None or ex is None:
        return None
    sv = c["serve"]
    return {"bytes": kv["bytes"] + ex["bytes"] + costs.other_weight_bytes(
        c, sv["num_hidden_layers"], sv["slots"])}


COSTS = {"expert_matmul": _expert_matmul_cost, "kv_read": _kv_read_cost,
         "decode_tick_moe": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's two programs over the held share, with their
    donation, built from the program's own planned forward (the engine
    compiles them inside a constructor that allocates on a device)."""
    from pbs_tpu.models.serving import slot_program

    sv = c["serve"]
    cfg = program_config(c, sv["num_hidden_layers"], sv["max_len"])
    prog = slot_program(cfg)
    params = on(jax.eval_shape(lambda: reference.init_tree(
        c, reference.seed_word(0), sv["num_hidden_layers"],
        DTYPES[sv["weights_dtype"]])))
    cache = on(jax.eval_shape(lambda: prog.init_cache(
        sv["slots"], sv["max_len"])))
    i32 = lambda *s: on(jax.ShapeDtypeStruct(s, jnp.int32))  # noqa: E731

    def decode(params, cache, last_tok, active):
        logits, new, _, route = prog.decode(params, cache, last_tok, active)
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return jnp.argmax(logits[:, 0], -1), route, new

    def prefill(params, cache, slot, prompt, plen):
        last, cache, _, route = prog.ingest(params, cache, slot, prompt,
                                            plen)
        return jnp.argmax(last), route, last, cache

    resident = {"serve_weights": params, "serve_kv": cache}
    tag = f"L={sv['num_hidden_layers']}"
    return [
        {"name": f"decode {tag}", "resident": resident,
         "fn": jax.jit(decode, donate_argnums=(1,)),
         "args": (params, cache, i32(sv["slots"]),
                  on(jax.ShapeDtypeStruct((sv["slots"],), bool)))},
        {"name": f"prefill {tag}", "resident": resident,
         "fn": jax.jit(prefill, donate_argnums=(1,)),
         "args": (params, cache, i32(), i32(sv["prompt_bucket"]), i32())}]
