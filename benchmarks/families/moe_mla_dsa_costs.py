"""What a decode tick and a prompt's ingestion of the ``moe-mla-dsa``
family *need*, from shapes alone (``c`` is a configuration file's dict;
Hugging Face key names): what the model has to read and multiply,
whatever implements it.

A tick decodes one token a slot, so every part of it is bound by the
bytes it has to move:

- the latent rows: in every layer each *busy* lane's query attends the
  ``min(live, index_topk)`` positions its indexer picked, and a
  position is one row of ``kv_lora_rank + qk_rope_head_dim`` values
  **for all heads** (a count of one row a head would read 64 times what
  the model needs);
- the indexer's keys: every live position's ``index_head_dim`` values,
  once a busy lane and layer (what is not scored cannot be ruled out);
- the routed experts: the three matrices of every *held expert that a
  tick's tokens touched* (counted by the program, ``ENG_ROUTE``);
- everything else once: the mixers' projections and norms, the dense
  layer, routers, shared experts, the head, one embedding row and the
  three new rows a slot and layer.

A prompt's ingestion multiplies. Its mixers, apart from the projections:
per query at position ``t`` and layer, a score against each of the
``t + 1`` positions it sees where it has to choose (``t >=
index_topk``; below that every position is chosen unscored):
``2 x index_n_heads x index_head_dim`` operations a pair; and attention
over the ``min(t + 1, index_topk)`` chosen: per pair, in the form that
reads a row once for all heads, ``2 x heads x ((kv_lora_rank +
qk_rope_head_dim) + kv_lora_rank)``.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many of the first ``n_layers`` layers have each MLP."""
    dense = min(c["first_k_dense_replace"], n_layers)
    return {"dense": dense, "sparse": n_layers - dense}


def latent_row_bytes(c: dict) -> int:
    """What attention reads of one position, for all heads."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * _itemsize(c)


def index_row_bytes(c: dict) -> int:
    """What the indexer reads of one position."""
    return c["index_head_dim"] * _itemsize(c)


def latent_read_bytes(c: dict, n_layers: int,
                      chosen_positions: float) -> float:
    """``chosen_positions``: ``min(live, index_topk)`` summed over the
    busy lanes of one tick."""
    return chosen_positions * latent_row_bytes(c) * n_layers


def index_read_bytes(c: dict, n_layers: int, live_positions: float) -> float:
    """``live_positions``: positions a lane's query sees, summed over
    the busy lanes of one tick."""
    return live_positions * index_row_bytes(c) * n_layers


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, experts_touched: float) -> float:
    """``experts_touched``: held experts that got at least one token,
    summed over the expert layers of one tick."""
    return experts_touched * expert_params(c) * _itemsize(c)


def mixer_params(c: dict) -> int:
    """A mixer's matrices (the two down-projections, the two
    up-projections, the output projection, the indexer's three), its
    four norms and the LayerNorm's bias."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    qr, kvr = c["q_lora_rank"], c["kv_lora_rank"]
    n, e, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
               c["v_head_dim"])
    J, D = c["index_n_heads"], c["index_head_dim"]
    return (d * qr + qr * H * (n + e) + d * (kvr + e) + kvr * H * (n + v)
            + H * v * d + qr * J * D + d * D + d * J
            + d + qr + kvr + 2 * D)


def pairs_scored(c: dict, prompt_tokens: float) -> float:
    """Query-position pairs the indexer has to score in one layer of a
    prompt of ``prompt_tokens``: every seen position of every query
    that sees more than ``index_topk``."""
    n, k = prompt_tokens, c["index_topk"]
    return (n * (n + 1) - k * (k + 1)) / 2 if n > k else 0.0


def pairs_attended(c: dict, prompt_tokens: float) -> float:
    """Pairs attention runs over in one layer: ``min(t + 1,
    index_topk)`` a query."""
    n, k = prompt_tokens, c["index_topk"]
    m = min(n, k)
    return m * (m + 1) / 2 + max(n - k, 0) * k


def mla_prefill_flops(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """The indexer's scores and the attention under the choice, of one
    prompt's ingestion (the projections around them are not in it)."""
    H, kvr, e = (c["num_attention_heads"], c["kv_lora_rank"],
                 c["qk_rope_head_dim"])
    return n_layers * (
        pairs_scored(c, prompt_tokens)
        * 2 * c["index_n_heads"] * c["index_head_dim"]
        + pairs_attended(c, prompt_tokens) * 2 * H * ((kvr + e) + kvr))


def other_tick_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    """Every weight that is not a routed expert, once; an embedding row
    and the new latent, rotary and indexer rows a slot."""
    d = c["hidden_size"]
    n = layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    shared = c["n_shared_experts"] * c["moe_intermediate_size"]
    params = n_layers * mixer_params(c) \
        + n["dense"] * (d + 3 * d * c["intermediate_size"]) \
        + n["sparse"] * (d + d * total + 3 * d * shared) \
        + d + d * c["vocab_size"] + n_slots * d
    rows = n_layers * n_slots * (latent_row_bytes(c) + index_row_bytes(c))
    return params * _itemsize(c) + n["sparse"] * total * 4 + rows
