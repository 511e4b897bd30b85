"""The family of decoders whose blocks are one thing each: a Mamba-2
mixer (a float32 matrix state a head under one scalar decay, a short
convolution over x, B and C), a grouped-query softmax attention layer
with no rotary, or a layer of many small ungated relu^2 experts behind
a sigmoid router beside a shared one, in the order a pattern string
spells (NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type: nemotron_h``).
Serving only.

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: per block its one half
(``hybrid_override_pattern``: ``M`` and ``*`` a mixer alone, ``E`` an
MLP alone) and of the expert blocks the share this chip holds
(``n_routed_experts`` of ``deployment.experts_total``, from
``deployment.experts_first``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import moe_mamba2_gqa_costs as costs
from benchmarks.reference import moe_mamba2_gqa as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        plan = None
    if not hasattr(plan, "Mamba2Kind"):
        raise SystemExit(
            "benchmarks/families/moe-mamba2-gqa.py: this program's layer "
            "plan (pbs_tpu/models/plan.py) has no matrix-state (Mamba-2) "
            "kind and no block of one half: it cannot serve a decoder "
            "whose blocks are a mixer or an expert layer alone")
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    if c["n_group"] != 1 or c["topk_group"] != 1 or not c["norm_topk_prob"]:
        raise NotImplementedError(
            "this family reads a router without a group limit (n_group "
            "1, topk_group 1) that renormalises the chosen experts' "
            "scores (norm_topk_prob true)")
    if c["mlp_hidden_act"] != "relu2" or c["mamba_hidden_act"] != "silu":
        raise NotImplementedError(
            "this family reads relu2 experts and a silu Mamba-2 mixer")
    H, hd, G, N, taps = reference.mamba_sizes(c)
    kinds = {
        "M": P.Mamba2Kind("mamba2", H, hd, G, N, conv=taps),
        "*": P.AttnKind("full", c["num_attention_heads"], None, None),
        "E": P.MlpKind(
            "experts", c["moe_intermediate_size"],
            n_experts=c["deployment"]["experts_total"],
            top_k=c["num_experts_per_tok"], held=reference.held_range(c),
            shared_d_ff=c["n_shared_experts"]
            * c["moe_shared_expert_intermediate_size"],
            routed_scale=float(c["routed_scaling_factor"]),
            scoring="sigmoid", form="relu2")}
    letters = reference.pattern(c, n_layers)
    attn = tuple(kinds[k] for k in "M*" if k in letters)
    return P.LayerPlan(
        attn, (kinds["E"],),
        tuple((None, 0) if k == "E" else (attn.index(kinds[k]), None)
              for k in letters))


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    plan = layer_plan(c, n_layers)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, norm_eps=float(c["layer_norm_epsilon"]),
        dtype=DTYPES[c["compute_dtype"]], head_size=c["head_dim"],
        layer_plan=plan, **extra)


def train_step(cfg, learning_rate: float):
    raise NotImplementedError(
        "the program trains uniform layer stacks only: neither the "
        "chunked matrix-state scan nor the grouped expert layer has a "
        "backward here (ROADMAP R3, R23)")


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


def _chunk() -> int:
    """Positions a chunk of the program's matrix form holds."""
    from pbs_tpu.models.mamba2 import MAMBA2_CHUNK

    return MAMBA2_CHUNK


def _sized(size: str, unit: str, count):
    def cost(c: dict, sizes: dict) -> dict | None:
        if sizes.get(size) is None:
            return None
        return {unit: count(c, sizes[size])}

    return cost


_expert_matmul = _sized("experts_touched", "bytes", costs.expert_bytes)
_kv_read = _sized("live_positions", "bytes", lambda c, n: costs.kv_read_bytes(
    c, _layers(c), n))
_state = _sized("busy_lanes", "bytes", lambda c, n: costs.mamba2_state_bytes(
    c, _layers(c), n))


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    parts = [f(c, sizes) for f in (_state, _expert_matmul, _kv_read)]
    if any(p is None for p in parts):
        return None
    return {"bytes": sum(p["bytes"] for p in parts)
            + costs.other_weight_bytes(c, _layers(c), c["serve"]["slots"])}


COSTS = {
    "expert_matmul": _expert_matmul, "kv_read": _kv_read,
    "mamba2_state": _state,
    "mamba2_scan": _sized(
        "prompt_tokens", "flops", lambda c, n: costs.mamba2_scan_flops(
            c, _layers(c), n, _chunk())),
    "prefill_mxu": _sized(
        "prompt_tokens", "flops", lambda c, n: costs.prefill_flops(
            c, _layers(c), n, _chunk())),
    "decode_tick_mamba2": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's programs over the held share, with their donation,
    built from the program's own planned forward (the engine compiles
    them inside a constructor that allocates on a device): the decode
    and the prefill at each of its rungs."""
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

    resident = {"serve_weights": params, "serve_state_and_kv": cache}
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
