"""The family of decoders whose attention keeps one latent row a
position and attends every earlier one (multi-head latent attention
*without* an indexer, under YaRN), over leading dense SwiGLU layers and
then many routed experts behind a group-limited sigmoid router beside a
shared one, with a multi-token-prediction module that drafts for the
model itself (DeepSeek-V3, ``model_type: deepseek_v3``). Serving only.

**Which of the two latent families a configuration belongs to.**
``moe-mla-dsa`` (GLM-5) reads a stack whose latent attention *selects*:
a learned indexer's key a position beside the latent row, the
``index_topk`` best of them attended, plain rotary, a router without a
group limit, and its multi-token-prediction layer left out; its file
refuses YaRN and group limits (it is the benchmark's file, and stays).
This family reads a stack whose latent attention chooses nothing (no
``index_*`` keys), whose rotary is YaRN's with the magnitude correction
in the softmax's scale, whose router limits a token to ``topk_group``
of ``n_group`` groups, and whose ``num_nextn_predict_layers`` module is
**run**: the plan gets a drafting block, and the engine verifies two
positions a lane a tick. A configuration with an indexer belongs there,
one without here; a configuration with both an indexer and a drafting
module would need the verify window under a choice, which the program
does not write (``plan.LayerPlan.takes_window``).

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: one latent attention kind for
every layer, of the expert layers the share this chip holds
(``n_routed_experts`` of ``deployment.experts_total``, from
``deployment.experts_first``), and the drafting block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import moe_mla_mtp_costs as costs
from benchmarks.reference import moe_mla_mtp as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        plan = None
    if not hasattr(getattr(plan, "LayerPlan", None), "draft_kinds"):
        raise SystemExit(
            "benchmarks/families/moe-mla-mtp.py: this program's layer "
            "plan (pbs_tpu/models/plan.py) has no drafting block: it "
            "cannot serve a decoder whose multi-token-prediction module "
            "drafts for it")
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    rs = c["rope_scaling"]
    if rs["type"] != "yarn" or not c["norm_topk_prob"] \
            or c["scoring_func"] != "sigmoid" or c["attention_bias"] \
            or c["topk_method"] != "noaux_tc" \
            or c["num_nextn_predict_layers"] != 1:
        raise NotImplementedError(
            "this family reads a stack with YaRN rotary, no attention "
            "bias, a group-limited sigmoid router (noaux_tc) that "
            "renormalises the chosen experts' scores, and one "
            "multi-token-prediction layer")
    z = reference.mixer_sizes(c)
    factor = float(rs["factor"])
    mla = P.MlaKind(
        "mla", z["H"], z["qr"], z["kvr"], z["n"], z["e"], z["v"],
        rope=P.Rope(
            theta=float(c["rope_theta"]), rotary_dim=z["e"], factor=factor,
            original_max=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            attention_factor=reference.yarn_get_mscale(
                factor, float(rs["mscale"]))
            / reference.yarn_get_mscale(factor, float(rs["mscale_all_dim"])),
            interleave=True),
        mscale=reference.yarn_get_mscale(factor,
                                         float(rs["mscale_all_dim"])))
    mlps = (P.MlpKind("dense", c["intermediate_size"]),
            P.MlpKind(
                "experts", c["moe_intermediate_size"],
                n_experts=c["deployment"]["experts_total"],
                top_k=c["num_experts_per_tok"],
                held=reference.held_range(c),
                shared_d_ff=reference.shared_width(c),
                routed_scale=float(c["routed_scaling_factor"]),
                scoring="sigmoid", renorm_eps=1e-20,
                n_group=c["n_group"], topk_group=c["topk_group"]))
    return P.LayerPlan((mla,), mlps, tuple(
        (0, 0 if reference.is_dense(c, l) else 1) for l in range(n_layers)),
        draft=(0, 1))


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    plan = layer_plan(c, n_layers)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, norm_eps=float(c["rms_norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]],
        head_size=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        layer_plan=plan, **extra)


def train_step(cfg, learning_rate: float):
    raise NotImplementedError(
        "the program trains uniform layer stacks only: neither latent "
        "attention nor the grouped expert layer has a backward here "
        "(ROADMAP R3, R5), and by the count in PERF.md section 4 this "
        "model's floor cut is 50.5 GB at 16 bytes a parameter")


def serve_weights(c: dict, seed: int):
    """Weights of the held share at the serving depth, the drafting
    module among them, in the type they are held in, made where the
    backend's rule table will place them."""
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
# ``sizes`` holds what ``readers/select_roofline_pct.py`` or
# ``readers/kda_roofline_pct.py`` found of: ``chosen_positions`` (the
# program's ``ENG_SELECT``: two queries a busy lane), ``experts_touched``
# (``ENG_ROUTE``) and, for a prompt forward at the bucket's rows,
# ``prompt_tokens``; a cost whose size is missing has nothing to read.


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
_expert_matmul = _sized("experts_touched", "bytes", costs.expert_bytes,
                        layered=False)


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    parts = [f(c, sizes) for f in (_latent_read, _expert_matmul)]
    if any(p is None for p in parts):
        return None
    return {"bytes": sum(p["bytes"] for p in parts)
            + costs.other_tick_bytes(c, _layers(c), c["serve"]["slots"])}


COSTS = {"latent_read": _latent_read, "expert_matmul": _expert_matmul,
         "mla_prefill": _sized("prompt_tokens", "flops",
                               costs.mla_prefill_flops),
         "prefill_mxu": _sized("prompt_tokens", "flops",
                               costs.prefill_flops),
         "decode_tick_mtp": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's programs over the held share, with their donation,
    built from the program's own planned forward (the engine compiles
    them inside a constructor that allocates on a device): the drafting
    tick and the prefill at each of its rungs."""
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

    def decode(params, cache, active):
        toks, new, route, *_ = prog.draft_tick(params, cache, active)
        return jnp.concatenate([toks.reshape(-1), route]), new

    def prefill(params, cache, slot, prompt, plen):
        last, cache, _, route = prog.ingest(params, cache, slot, prompt,
                                            plen)
        return cache["cur"][slot], route, last, cache

    resident = {"serve_weights": params, "serve_state_and_kv": cache}
    tag = f"L={sv['num_hidden_layers']}+mtp"
    bucket = sv["prompt_bucket"]
    return [
        {"name": f"decode {tag}", "resident": resident,
         "fn": jax.jit(decode, donate_argnums=(1,)),
         "args": (params, cache,
                  on(jax.ShapeDtypeStruct((sv["slots"],), bool)))},
        *({"name": f"prefill {tag} rung={rung}", "resident": resident,
           "fn": jax.jit(prefill, donate_argnums=(1,)),
           "args": (params, cache, i32(), i32(rung), i32())}
          for rung in (bucket // 2, bucket))]
