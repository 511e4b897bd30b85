"""A second family added by files only (a test fixture, not a
configuration of the benchmark): the program's own mixture-of-experts
decoder, ``pbs_tpu/models/moe.py``. The parts a run needs of the five
``benchmarks/families/dense-gqa.py`` has (no ``sizing``: the test
compiles nothing for a described chip).

The program runs with ``dropless=True``, so that no token is dropped and
the reference needs no capacity rule. ``ShardedServeBackend`` can take
neither an ``mlp_fn`` nor a tree with expert leaves (its rule table
names none), so the backend is the gateway's ``BatcherBackend`` over the
stamping engine, given ``mlp_fn=moe_slot_mlp(cfg)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import moe_top2 as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(c: dict, n_layers: int, max_seq: int, **extra):
    from pbs_tpu.models.moe import MoEConfig

    return MoEConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=max_seq, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=DTYPES[c["compute_dtype"]], n_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"],
        aux_loss_weight=float(c["aux_loss_weight"]), dropless=True, **extra)


def train_step(cfg, learning_rate: float):
    from pbs_tpu.models.moe import make_moe_train_step

    return make_moe_train_step(cfg, learning_rate=learning_rate)


def serve_backend(name: str, cfg, c: dict, seed: int, engine_cls):
    from pbs_tpu.gateway.backends import BatcherBackend
    from pbs_tpu.models.moe import moe_slot_mlp

    sv = c["serve"]
    weights = jax.jit(lambda s: reference.init_tree(
        c, s, sv["num_hidden_layers"], DTYPES[sv["weights_dtype"]]))(
            reference.seed_word(seed))
    return BatcherBackend(name, engine_cls(
        cfg, weights, n_slots=int(sv["slots"]),
        prompt_bucket=int(sv["prompt_bucket"]), max_len=int(sv["max_len"]),
        mlp_fn=moe_slot_mlp(cfg)))


def _expert_matmul_cost(c: dict, _sizes: dict) -> dict:
    """The expert matmuls of one decode tick: every slot's token through
    its top-k experts (three matmuls of d x f each), and the weights of
    all experts read once (the dense dispatch touches every expert)."""
    sv = c["serve"]
    d, f = c["hidden_size"], c["intermediate_size"]
    itemsize = jnp.dtype(sv["weights_dtype"]).itemsize
    return {"flops": 2.0 * 3 * d * f * c["num_experts_per_tok"]
            * sv["slots"] * sv["num_hidden_layers"],
            "bytes": 3.0 * d * f * c["num_experts"] * itemsize
            * sv["num_hidden_layers"]}


COSTS = {"expert_matmul": _expert_matmul_cost}

