"""The family of decoders whose mixer is a gated short convolution
alone (no state matrix, no decay: two multiplicative gates round a
depthwise filter of three taps, two rows of a lane's memory) in three
layers of four and grouped-query attention with RMS-normed 64-wide
heads in the fourth, each followed by a dense SwiGLU (the leading
layers) or by small sigmoid-routed SwiGLU experts with no shared one,
under a tied head (LFM2-24B-A2B, ``model_type: lfm2_moe``). Serving
only.

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: per layer its mixer kind
(``layer_types``) and its MLP kind (dense below ``num_dense_layers``),
and of each expert layer the share this chip holds (``num_experts`` of
``deployment.experts_total``, from ``deployment.experts_first``: all of
them in the configuration the benchmark has).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import moe_conv_gqa_costs as costs
from benchmarks.reference import moe_conv_gqa as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        plan = None
    if not hasattr(plan, "ConvKind"):
        raise SystemExit(
            "benchmarks/families/moe-conv-gqa.py: this program's layer "
            "plan (pbs_tpu/models/plan.py) has no gated-convolution kind "
            "and no softmax layer with normed heads: it cannot serve a "
            "decoder whose mixer is a short convolution alone")
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    if not c["norm_topk_prob"] or not c["use_expert_bias"] or c["conv_bias"]:
        raise NotImplementedError(
            "this family reads a sigmoid router that chooses by score "
            "plus a bias (use_expert_bias true) and renormalises the "
            "chosen scores (norm_topk_prob true), and a convolution "
            "without a bias (conv_bias false)")
    rp = c["rope_parameters"]
    if rp["rope_type"] != "default":
        raise NotImplementedError(
            f"this family reads plain rotary, not {rp['rope_type']!r}")
    kinds = {
        "conv": P.ConvKind("conv", c["hidden_size"], conv=c["conv_L_cache"]),
        "full_attention": P.AttnKind(
            "full", c["num_attention_heads"], None,
            P.Rope(theta=float(rp["rope_theta"])), qk_norm=True)}
    dense = P.MlpKind("dense", c["intermediate_size"])
    experts = P.MlpKind(
        "experts", c["moe_intermediate_size"],
        n_experts=c["deployment"]["experts_total"],
        top_k=c["num_experts_per_tok"], held=reference.held_range(c),
        routed_scale=float(c["routed_scaling_factor"]), scoring="sigmoid",
        renorm_eps=reference.RENORM_EPS)
    types = c["layer_types"][:n_layers]
    assert len(types) == n_layers, (len(types), n_layers)
    attn = tuple(kinds[k] for k in dict.fromkeys(types))
    mlps = tuple(m for m, there in (
        (dense, c["num_dense_layers"] > 0),
        (experts, n_layers > c["num_dense_layers"])) if there)
    return P.LayerPlan(attn, mlps, tuple(
        (attn.index(kinds[k]),
         mlps.index(dense if reference.is_dense(c, l) else experts))
        for l, k in enumerate(types)))


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    plan = layer_plan(c, n_layers)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, norm_eps=float(c["norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]], head_size=reference.head_dim(c),
        layer_plan=plan, tie_embeddings=bool(c["tie_word_embeddings"]),
        **extra)


def train_step(cfg, learning_rate: float):
    raise NotImplementedError(
        "the program trains uniform layer stacks only: no planned stack "
        "trains here and the grouped expert layer has no backward "
        "(ROADMAP R3, R4)")


def serve_weights(c: dict, seed: int):
    """Weights at the serving depth, in the type they are held in, made
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
# ``sizes`` holds what a reader found of: ``experts_touched`` and
# ``live_positions`` (``routed_roofline_pct``), ``busy_lanes`` and
# ``prompt_tokens`` (``kda_roofline_pct``); a cost whose size is missing
# has nothing to read.


def _layers(c: dict) -> int:
    return c["serve"]["num_hidden_layers"]


def _sized(size: str, unit: str, count):
    def cost(c: dict, sizes: dict) -> dict | None:
        if sizes.get(size) is None:
            return None
        return {unit: count(c, sizes[size])}

    return cost


_expert_matmul = _sized("experts_touched", "bytes", costs.expert_bytes)
_kv_read = _sized("live_positions", "bytes", lambda c, n: costs.kv_read_bytes(
    c, _layers(c), n))
_tails = _sized("busy_lanes", "bytes", lambda c, n: costs.tail_bytes(
    c, _layers(c), n))


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    parts = [f(c, sizes) for f in (_tails, _expert_matmul, _kv_read)]
    if any(p is None for p in parts):
        return None
    return {"bytes": sum(p["bytes"] for p in parts)
            + costs.other_weight_bytes(c, _layers(c), c["serve"]["slots"])}


COSTS = {
    "expert_matmul": _expert_matmul, "kv_read": _kv_read,
    "prefill_mxu": _sized(
        "prompt_tokens", "flops", lambda c, n: costs.prefill_flops(
            c, _layers(c), n)),
    "decode_tick_conv": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's programs over the stage, with their donation, built
    from the program's own planned forward (the engine compiles them
    inside a constructor that allocates on a device): the decode and the
    prefill at each of its rungs."""
    from pbs_tpu.models.serving import prefill_rungs, slot_program

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

    resident = {"serve_weights": params, "serve_tails_and_kv": cache}
    tag = f"L={sv['num_hidden_layers']}"
    return [
        {"name": f"decode {tag}", "resident": resident,
         "fn": jax.jit(decode, donate_argnums=(1,)),
         "args": (params, cache, i32(sv["slots"]),
                  on(jax.ShapeDtypeStruct((sv["slots"],), bool)))},
    ] + [
        {"name": f"prefill {tag} rung={rows}", "resident": resident,
         "fn": jax.jit(prefill, donate_argnums=(1,)),
         "args": (params, cache, i32(), i32(rows), i32())}
        for rows in prefill_rungs(sv["prompt_bucket"])]
