"""The family of decoders whose attention keeps one latent row a
position, not keys and values a head (multi-head latent attention), and
attends only the positions a learned indexer picks (DeepSeek sparse
attention), over a leading dense SwiGLU layer and then many routed
experts behind a sigmoid router beside a shared one (GLM-5,
``model_type: glm_moe_dsa``). Serving only.

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: one latent attention kind for
every layer, and of the expert layers the share this chip holds
(``n_routed_experts`` of ``deployment.experts_total``, from
``deployment.experts_first``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import moe_mla_dsa_costs as costs
from benchmarks.reference import moe_mla_dsa as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        plan = None
    if not hasattr(plan, "MlaKind"):
        raise SystemExit(
            "benchmarks/families/moe-mla-dsa.py: this program's layer "
            "plan (pbs_tpu/models/plan.py) has no latent attention "
            "kind: it cannot serve a decoder whose cache is a latent "
            "row and an indexer's key a position")
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    rp = c["rope_parameters"]
    if rp["rope_type"] != "default" or not c["norm_topk_prob"] \
            or c["n_group"] != 1 or c["topk_group"] != 1 \
            or c["scoring_func"] != "sigmoid" or c["attention_bias"] \
            or c["rope_interleave"] != c["indexer_rope_interleave"]:
        raise NotImplementedError(
            "this family reads a stack with plain rotary (no YaRN), the "
            "same pairing in the mixer and its indexer, no attention "
            "bias, and a sigmoid router without group limits that "
            "renormalises the chosen experts' scores")
    z = reference.mixer_sizes(c)
    mla = P.MlaKind(
        "mla", z["H"], z["qr"], z["kvr"], z["n"], z["e"], z["v"], z["J"],
        z["D"], z["topk"],
        P.Rope(theta=float(rp["rope_theta"]), rotary_dim=z["e"],
               interleave=bool(c["rope_interleave"])))
    mlps = (P.MlpKind("dense", c["intermediate_size"]),
            P.MlpKind(
                "experts", c["moe_intermediate_size"],
                n_experts=c["deployment"]["experts_total"],
                top_k=c["num_experts_per_tok"],
                held=reference.held_range(c),
                shared_d_ff=reference.shared_width(c),
                routed_scale=float(c["routed_scaling_factor"]),
                scoring="sigmoid"))
    return P.LayerPlan((mla,), mlps, tuple(
        (0, 0 if reference.is_dense(c, l) else 1) for l in range(n_layers)))


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    plan = layer_plan(c, n_layers)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, norm_eps=float(c["rms_norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]], head_size=c["qk_head_dim"],
        layer_plan=plan, **extra)


def train_step(cfg, learning_rate: float):
    raise NotImplementedError(
        "the program trains uniform layer stacks only: neither the "
        "selecting latent attention nor the grouped expert layer has a "
        "backward here (ROADMAP R3, R5), and by the count in PERF.md "
        "section 4 this model's floor cut is 43 GB at 16 bytes a "
        "parameter")


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
# ``sizes`` holds what ``readers/select_roofline_pct.py`` found of:
# ``live_positions`` and ``chosen_positions`` (the program's
# ``ENG_SELECT``), ``experts_touched`` (``ENG_ROUTE``) and, for a prompt
# forward at the bucket's rows, ``prompt_tokens``; a cost whose size is
# missing has nothing to read.


def _layers(c: dict) -> int:
    return c["serve"]["num_hidden_layers"]


def _sized(size: str, unit: str, count, layered: bool = True):
    def cost(c: dict, sizes: dict) -> dict | None:
        if sizes.get(size) is None:
            return None
        args = (c, _layers(c), sizes[size]) if layered else (c, sizes[size])
        return {unit: count(*args)}

    return cost


_latent_read = _sized("chosen_positions", "bytes", costs.latent_read_bytes)
_index_read = _sized("live_positions", "bytes", costs.index_read_bytes)
_expert_matmul = _sized("experts_touched", "bytes", costs.expert_bytes,
                        layered=False)


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    parts = [f(c, sizes) for f in (_latent_read, _index_read,
                                   _expert_matmul)]
    if any(p is None for p in parts):
        return None
    return {"bytes": sum(p["bytes"] for p in parts)
            + costs.other_tick_bytes(c, _layers(c), c["serve"]["slots"])}


COSTS = {"latent_read": _latent_read, "index_read": _index_read,
         "expert_matmul": _expert_matmul,
         "mla_prefill": _sized("prompt_tokens", "flops",
                               costs.mla_prefill_flops),
         "decode_tick_dsa": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's programs over the held share, with their donation,
    built from the program's own planned forward (the engine compiles
    them inside a constructor that allocates on a device): the decode
    and the prefill at each of its rungs."""
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
    bucket = sv["prompt_bucket"]
    return [
        {"name": f"decode {tag}", "resident": resident,
         "fn": jax.jit(decode, donate_argnums=(1,)),
         "args": (params, cache, i32(sv["slots"]),
                  on(jax.ShapeDtypeStruct((sv["slots"],), bool)))},
        *({"name": f"prefill {tag} rung={rung}", "resident": resident,
           "fn": jax.jit(prefill, donate_argnums=(1,)),
           "args": (params, cache, i32(), i32(rung), i32())}
          for rung in (bucket // 2, bucket))]
