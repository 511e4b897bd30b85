"""The family of decoders that mix gated delta-rule linear attention
(KDA: a recurrent float32 state a slot, a short convolution) with
softmax grouped-query attention that has no rotary and an elementwise
output gate, every layer's MLP many small routed experts behind a
sigmoid router beside a shared one (Solar-Open2-250B, ``model_type:
solar_open2``). Serving only.

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: per layer its mixer's kind
(``gqa_layers``) and of the expert layer the share this chip holds
(``n_routed_experts`` of ``deployment.experts_total``, from
``deployment.experts_first``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import moe_kda_gqa_costs as costs
from benchmarks.reference import moe_kda_gqa as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        plan = None
    if not hasattr(plan, "KdaKind"):
        raise SystemExit(
            "benchmarks/families/moe-kda-gqa.py: this program's layer "
            "plan (pbs_tpu/models/plan.py) has no delta-rule attention "
            "kind: it cannot serve a decoder whose per-slot state is not "
            "keys and values")
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    if c["first_k_dense_replace"] or not c["norm_topk_prob"]:
        raise NotImplementedError(
            "this family reads a stack whose every layer routes "
            "(first_k_dense_replace 0) and renormalises the chosen "
            "experts' scores (norm_topk_prob true)")
    H, hd, taps, rank = reference.kda_sizes(c)
    rope = P.Rope(theta=float(c["rope_theta"]), rotary_dim=int(
        c["head_dim"] * c["partial_rotary_factor"])) \
        if c["use_rope"] else None
    softmax = P.AttnKind("full", c["num_attention_heads"], None, rope,
                         wide_gate=bool(c["use_gqa_gate"]))
    kda = P.KdaKind("kda", H, hd, conv=taps, rank=rank)
    experts = P.MlpKind(
        "experts", c["moe_intermediate_size"],
        n_experts=c["deployment"]["experts_total"],
        top_k=c["num_experts_per_tok"], held=reference.held_range(c),
        shared_d_ff=reference.shared_width(c),
        routed_scale=float(c["routed_scaling_factor"]), scoring="sigmoid")
    kinds = [softmax if reference.is_softmax(c, l) else kda
             for l in range(n_layers)]
    attn = tuple(dict.fromkeys(kinds))
    return P.LayerPlan(attn, (experts,),
                       tuple((attn.index(k), 0) for k in kinds))


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
        "the program trains uniform layer stacks only: neither the "
        "chunked delta rule nor the grouped expert layer has a backward "
        "here (ROADMAP R3, R6), and by the count in PERF.md section 4 "
        "this model's floor cut is 20.7 GB at 16 bytes a parameter")


def serve_weights(c: dict, seed: int):
    """Weights of the held share at the serving depth, in the type they
    are held in, made where the backend's rule table will place them."""
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
# ``sizes`` holds what a reader found of: ``experts_touched`` and
# ``live_positions`` (``routed_roofline_pct``), ``busy_lanes`` and
# ``prompt_tokens`` (``kda_roofline_pct``); a cost whose size is missing
# has nothing to read.


def _layers(c: dict) -> int:
    return c["serve"]["num_hidden_layers"]


def _expert_matmul_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("experts_touched") is None:
        return None
    return {"bytes": costs.expert_bytes(c, sizes["experts_touched"])}


def _kv_read_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("live_positions") is None:
        return None
    return {"bytes": costs.kv_read_bytes(c, _layers(c),
                                         sizes["live_positions"])}


def _kda_state_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("busy_lanes") is None:
        return None
    return {"bytes": costs.kda_state_bytes(c, _layers(c),
                                           sizes["busy_lanes"])}


def _kda_prefill_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("prompt_tokens") is None:
        return None
    return {"flops": costs.kda_prefill_flops(c, _layers(c),
                                             sizes["prompt_tokens"])}


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    parts = [f(c, sizes) for f in (_kda_state_cost, _expert_matmul_cost,
                                   _kv_read_cost)]
    if any(p is None for p in parts):
        return None
    return {"bytes": sum(p["bytes"] for p in parts)
            + costs.other_weight_bytes(c, _layers(c), c["serve"]["slots"])}


COSTS = {"expert_matmul": _expert_matmul_cost, "kv_read": _kv_read_cost,
         "kda_state": _kda_state_cost, "kda_prefill": _kda_prefill_cost,
         "decode_tick_kda": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's two programs over the held share, with their
    donation, built from the program's own planned forward (the engine
    compiles them inside a constructor that allocates on a device); the
    prefill at its longest rung."""
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

    resident = {"serve_weights": params, "serve_state_and_kv": cache}
    tag = f"L={sv['num_hidden_layers']}"
    return [
        {"name": f"decode {tag}", "resident": resident,
         "fn": jax.jit(decode, donate_argnums=(1,)),
         "args": (params, cache, i32(sv["slots"]),
                  on(jax.ShapeDtypeStruct((sv["slots"],), bool)))},
        {"name": f"prefill {tag}", "resident": resident,
         "fn": jax.jit(prefill, donate_argnums=(1,)),
         "args": (params, cache, i32(), i32(sv["prompt_bucket"]), i32())}]
