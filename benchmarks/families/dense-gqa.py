"""The dense grouped-query decoder family (InternLM2, Mistral; Llama
style): everything about a model that the harness does not know.

A configuration file names its family (``"family": "dense-gqa"``) and
``Spec.family`` loads ``benchmarks/families/<name>.py`` by that name,
the way it loads a reader. A family gives the harness five things:

- ``reference``: the plain float32 reference (``seed_word``,
  ``init_tree``, ``score_tokens``, ``train_readings``, ``leaf_norms``,
  ``sketch``, ``B1``; the int8 control through its own ``matmul``);
- ``program_config(c, n_layers, max_seq, **extra)``: the program's
  configuration object from a configuration file's keys. ``n_layers`` is
  one integer that the family reads in its own terms;
- ``train_step(cfg, learning_rate)`` and ``serve_backend(...)``: the
  program's step factory, and the gateway backend over the stamping
  engine with whatever the engine needs beyond the configuration;
- ``COSTS``: ``{cost name: function of (c, sizes)}``, the operations
  (``flops``) and bytes a program needs, from shapes; a metric file's
  ``"cost"`` picks one (``benchmarks/readers/roofline_pct.py``);
- ``sizing(c, on)``: the programs ``benchmarks/tools/size_cells.py``
  compiles for a described chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families import dense_gqa_costs as costs
from benchmarks.reference import model as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]], **extra)


def train_step(cfg, learning_rate: float):
    from pbs_tpu.models import make_train_step

    return make_train_step(cfg, learning_rate=learning_rate)


def serve_weights(c: dict, seed: int):
    """bfloat16 weights of the serving depth, made where the backend's
    rule table will place them, so placement copies nothing."""
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


def _train_step_cost(c: dict, _sizes: dict) -> dict:
    tr = c["train"]
    return {"flops": costs.train_step_flops(
        c, tr["num_hidden_layers"], tr["batch"], tr["seq"])}


def _decode_tick_cost(c: dict, sizes: dict) -> dict | None:
    if sizes.get("live_positions") is None:
        return None
    sv = c["serve"]
    return {"bytes": costs.decode_tick_bytes(
        c, sv["num_hidden_layers"], sv["slots"], sizes["live_positions"])}


COSTS = {"train_step": _train_step_cost, "decode_tick": _decode_tick_cost}


# -- sizing -----------------------------------------------------------------


def sizing(c: dict, on) -> list[dict]:
    """The programs to compile for a described chip, each
    ``{"name", "fn", "args", "resident"}``: ``fn`` jitted with the
    donation the program runs it with, ``args`` as shapes placed by
    ``on``, ``resident`` the labelled trees that stay on the device
    between calls. The train step is the program's own; the serving
    programs are the engine's two (slot prefill, slot decode), rebuilt
    from the engine's own ``_slot_forward`` / ``ingest_slot_prompt``
    because the engine compiles them inside a constructor that
    allocates on a device."""
    from pbs_tpu.models.serving import (
        _slot_forward, ingest_slot_prompt, init_slot_cache)

    word = reference.seed_word(0)
    sv = c["serve"]
    cfg = program_config(c, sv["num_hidden_layers"], sv["max_len"])
    params = on(jax.eval_shape(lambda: reference.init_tree(
        c, word, sv["num_hidden_layers"], DTYPES[sv["weights_dtype"]])))
    cache = on(jax.eval_shape(lambda: init_slot_cache(
        cfg, sv["slots"], sv["max_len"])))
    i32 = lambda *s: on(jax.ShapeDtypeStruct(s, jnp.int32))  # noqa: E731

    def decode(params, cache, last_tok, active):
        logits, new, _ = _slot_forward(cfg, params, last_tok[:, None],
                                       cache, cache["pos"])
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return jnp.argmax(logits[:, 0], -1), new

    def prefill(params, cache, slot, prompt, plen):
        last, cache, _ = ingest_slot_prompt(cfg, params, cache, slot,
                                            prompt, plen)
        return jnp.argmax(last), last, cache

    resident = {"serve_weights": params, "serve_kv": cache}
    tag = f"L={sv['num_hidden_layers']}"
    out = [
        {"name": f"decode {tag}", "resident": resident,
         "fn": jax.jit(decode, donate_argnums=(1,)),
         "args": (params, cache, i32(sv["slots"]),
                  on(jax.ShapeDtypeStruct((sv["slots"],), bool)))},
        {"name": f"prefill {tag}", "resident": resident,
         "fn": jax.jit(prefill, donate_argnums=(1,)),
         "args": (params, cache, i32(), i32(sv["prompt_bucket"]), i32())}]
    if "train" in c:
        tr = c["train"]
        tcfg = program_config(
            c, tr["num_hidden_layers"], tr["seq"], remat=tr["remat"],
            remat_policy=tr.get("remat_policy", "full"))
        init_opt, step = train_step(tcfg, float(tr["learning_rate"]))
        tparams = jax.eval_shape(lambda: reference.init_tree(
            c, word, tr["num_hidden_layers"], jnp.float32))
        state = on((tparams, jax.eval_shape(init_opt, tparams),
                    jax.ShapeDtypeStruct((), jnp.int32)))
        out.append(
            {"name": f"train L={tr['num_hidden_layers']} B={tr['batch']}",
             "resident": {"train_state": state},
             "fn": jax.jit(step, donate_argnums=(0,)),
             "args": (state, on(jax.ShapeDtypeStruct(
                 (tr["batch"], tr["seq"]), jnp.int32)))})
    return out
