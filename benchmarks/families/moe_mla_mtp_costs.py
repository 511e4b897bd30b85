"""What a decode tick and a prompt's ingestion of the ``moe-mla-mtp``
family *need*, from shapes alone (``c`` is a configuration file's dict;
Hugging Face key names): what the model has to read and multiply,
whatever implements it.

A tick verifies **two positions a slot** (the lane's last token and the
token drafted to follow it) through the stack and runs the drafting
block over the same two, so every part of it is still bound by the
bytes it has to move, and every weight is read once for both:

- the latent rows: in every latent layer (the stack's and the drafting
  block's: ``mixers``) each *busy* lane's two queries attend the rows
  up to their own positions, and a position is one row of
  ``kv_lora_rank + qk_rope_head_dim`` values **for all heads and for
  both queries**: a lane's rows are read once, the second query's one
  row more than the first's. ``ENG_SELECT`` counts a row a query
  (``chosen_positions``: two rows a busy lane, ``p + 1`` and ``p + 2``
  positions), so the rows read are half of it (and half a row a lane,
  left out);
- the routed experts: the three matrices of every *held expert that a
  tick's tokens touched* (counted by the program, ``ENG_ROUTE``, the
  drafting block's expert layer among them);
- everything else once: the mixers' projections and norms, the dense
  layer, routers, shared experts, the drafting block's projection and
  norms, the head (read by the stack's logits and by the draft's: once,
  as any weight), two embedding rows and the two new rows a slot and
  latent layer.

A prompt's ingestion multiplies: two operations a matrix weight a token
(of the routed experts a token's ``num_experts_per_tok`` choices times
the held share), the drafting block's among them, and per (query,
position) pair of the causal triangle the scores and the values in the
cheaper of attention's two forms, a head at a time: ``2 x heads x
((qk_nope + qk_rope) + v)``.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many blocks a tick runs of each kind: the first ``n_layers``
    layers' MLPs, and with the drafting module (an expert layer's kind)
    the mixers and expert layers in all."""
    dense = min(c["first_k_dense_replace"], n_layers)
    draft = int(bool(c["num_nextn_predict_layers"]))
    return {"dense": dense, "sparse": n_layers - dense + draft,
            "mixers": n_layers + draft, "draft": draft}


def latent_row_bytes(c: dict) -> int:
    """What attention reads of one position, for all heads."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * _itemsize(c)


def latent_read_bytes(c: dict, n_layers: int,
                      chosen_positions: float) -> float:
    """``chosen_positions``: what ``ENG_SELECT`` sums over a tick's
    queries, two a busy lane: the rows read are a lane's once."""
    return (chosen_positions / 2.0 * latent_row_bytes(c)
            * layer_kinds(c, n_layers)["mixers"])


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, experts_touched: float) -> float:
    """``experts_touched``: held experts that got at least one token,
    summed over the expert layers of one tick."""
    return experts_touched * expert_params(c) * _itemsize(c)


def mixer_matrix_params(c: dict) -> int:
    """A mixer's matrices: the two down-projections, the two
    up-projections and the output projection."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    qr, kvr = c["q_lora_rank"], c["kv_lora_rank"]
    n, e, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
               c["v_head_dim"])
    return (d * qr + qr * H * (n + e) + d * (kvr + e) + kvr * H * (n + v)
            + H * v * d)


def mixer_params(c: dict) -> int:
    """The matrices and the three norms."""
    return mixer_matrix_params(c) + c["hidden_size"] + c["q_lora_rank"] \
        + c["kv_lora_rank"]


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["n_shared_experts"] \
        * c["moe_intermediate_size"]


def dense_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def draft_join_params(c: dict) -> int:
    """The drafting module outside its block: ``eh_proj`` and its three
    norms."""
    d = c["hidden_size"]
    return 2 * d * d + 3 * d


def other_tick_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    """Every weight that is not a routed expert, once; two embedding
    rows and the two new latent rows a slot and latent layer."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    params = n["mixers"] * mixer_params(c) \
        + n["dense"] * (d + dense_params(c)) \
        + n["sparse"] * (d + d * total + shared_params(c)) \
        + n["draft"] * draft_join_params(c) \
        + d + d * c["vocab_size"] + 2 * n_slots * d
    rows = n["mixers"] * 2 * n_slots * latent_row_bytes(c)
    return params * _itemsize(c) + n["sparse"] * total * 4 + rows


def pairs_attended(prompt_tokens: float) -> float:
    """(query, position) pairs of one layer's causal triangle."""
    return prompt_tokens * (prompt_tokens + 1) / 2


def mla_prefill_flops(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """Attention's scores and values of one prompt's ingestion, every
    latent layer (the projections around them are not in it)."""
    H = c["num_attention_heads"]
    pair = 2 * H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                    + c["v_head_dim"])
    return layer_kinds(c, n_layers)["mixers"] \
        * pairs_attended(prompt_tokens) * pair


def prefill_flops(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """The products of a prompt of ``prompt_tokens`` real positions,
    the drafting block's pass over it included, and the logits of its
    last position twice (the stack's and the draft's)."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / total
    weights = n["mixers"] * mixer_matrix_params(c) \
        + n["dense"] * dense_params(c) \
        + n["sparse"] * (d * total + shared_params(c)
                         + routed * expert_params(c)) \
        + n["draft"] * 2 * d * d
    return (2 * weights * prompt_tokens
            + mla_prefill_flops(c, n_layers, prompt_tokens)
            + (1 + n["draft"]) * 2 * d * c["vocab_size"])
