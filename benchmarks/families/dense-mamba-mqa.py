"""The family of decoders that mix selective state-space layers
(Mamba-1: a recurrent float32 state a slot, a short convolution) with
multi-query softmax attention that has no rotary, a dense SwiGLU after
every mixer and a tied embedding (AI21-Jamba2-3B, ``model_type:
jamba``). Serving only.

The five parts ``benchmarks/families/dense-gqa.py`` lists. What the
program is told is a layer plan (``pbs_tpu/models/plan.py``) read off
the configuration's Hugging Face keys: per layer its mixer's kind
(``attn_layer_period`` / ``attn_layer_offset``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import dense_mamba_mqa_costs as costs
from benchmarks.reference import dense_mamba_mqa as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _plan_module():
    try:
        from pbs_tpu.models import plan
    except ImportError:
        plan = None
    if not hasattr(plan, "MambaKind"):
        raise SystemExit(
            "benchmarks/families/dense-mamba-mqa.py: this program's "
            "layer plan (pbs_tpu/models/plan.py) has no state-space "
            "kind: it cannot serve a decoder whose per-slot state is a "
            "selective scan's")
    return plan


def layer_plan(c: dict, n_layers: int):
    P = _plan_module()
    if c["num_experts"] != 1 or c["mamba_proj_bias"] \
            or not c["mamba_conv_bias"] or c["sliding_window"]:
        raise NotImplementedError(
            "this family reads a stack whose every MLP is dense "
            "(num_experts 1), whose state-space projections have no "
            "bias and whose convolution has one, and whose attention "
            "sees every earlier position")
    C, N, R, taps = reference.mamba_sizes(c)
    full = P.AttnKind("full", c["num_attention_heads"], None, None)
    mamba = P.MambaKind("mamba", C, N, R, conv=taps)
    kinds = [full if reference.is_attention(c, l) else mamba
             for l in range(n_layers)]
    attn = tuple(dict.fromkeys(kinds))
    return P.LayerPlan(attn, (P.MlpKind("dense", c["intermediate_size"]),),
                       tuple((attn.index(k), 0) for k in kinds))


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    plan = layer_plan(c, n_layers)
    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, norm_eps=float(c["rms_norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]],
        head_size=c["hidden_size"] // c["num_attention_heads"],
        layer_plan=plan, tie_embeddings=bool(c["tie_word_embeddings"]),
        **extra)


def train_step(cfg, learning_rate: float):
    raise NotImplementedError(
        "the program trains uniform layer stacks only: the selective "
        "scan has no backward here (ROADMAP R23), and by the count in "
        "PERF.md section 4 one whole period of this model is 22.9 GB at "
        "16 bytes a parameter")


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
# ``sizes`` holds what ``readers/kda_roofline_pct.py`` found of:
# ``busy_lanes``, ``live_positions`` and, for a prompt forward at the
# bucket's rows, ``prompt_tokens``; a cost whose size is missing has
# nothing to read.


def _layers(c: dict) -> int:
    return c["serve"]["num_hidden_layers"]


def _sized(size: str, unit: str, count):
    def cost(c: dict, sizes: dict) -> dict | None:
        if sizes.get(size) is None:
            return None
        return {unit: count(c, _layers(c), sizes[size])}

    return cost


_kv_read = _sized("live_positions", "bytes", costs.kv_read_bytes)
_mamba_state = _sized("busy_lanes", "bytes", costs.mamba_state_bytes)


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    parts = [f(c, sizes) for f in (_mamba_state, _kv_read)]
    if any(p is None for p in parts):
        return None
    return {"bytes": sum(p["bytes"] for p in parts)
            + costs.other_tick_bytes(c, _layers(c), c["serve"]["slots"])}


COSTS = {"kv_read": _kv_read, "mamba_state": _mamba_state,
         "mamba_scan": _sized("prompt_tokens", "bytes",
                              costs.mamba_scan_bytes),
         "prefill_mxu": _sized("prompt_tokens", "flops",
                               costs.prefill_flops),
         "decode_tick_ssm": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The engine's two programs over the whole model, with their
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
        logits, new, _, _ = prog.decode(params, cache, last_tok, active)
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return jnp.argmax(logits[:, 0], -1), new

    def prefill(params, cache, slot, prompt, plen):
        last, cache, _, _ = prog.ingest(params, cache, slot, prompt, plen)
        return jnp.argmax(last), last, cache

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
