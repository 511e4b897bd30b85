"""What a decode tick and a prompt's forward of the ``moe-conv-gqa``
family *need*, from shapes alone (``c`` is a configuration file's dict;
Hugging Face key names): what the layers have to read, write and
multiply, whatever implements them.

A tick decodes one token a slot, so every part of it is bound by the
bytes it has to move:

- the routed experts: the three matrices of every *held expert that a
  tick's tokens touched* (counted by the program, ``ENG_ROUTE``); with
  every expert of a layer held and hundreds of lanes that is all of
  them, nine tenths of the weights;
- keys and values: every live position of every slot, in the attention
  layers, at what a position holds (``2 x num_key_value_heads x
  head_dim`` entries: the cache lies two 64-wide heads to a row of 128
  lanes, so a position costs no more than that);
- the convolutions' tails: every *busy* lane's last ``conv_L_cache -
  1`` rows of ``hidden_size``, read once and written once, in each
  convolution layer (an idle lane's tail needs nothing);
- everything else once: the mixers' projections, filters and norms, the
  dense layers, routers, the tied embedding (the head reads all of it),
  one new position of keys and values a slot.

A prompt's forward multiplies: two operations a matrix weight a token
(of the experts, a token's ``num_experts_per_tok`` choices, all of them
held), the causal half of the attention layers' squares, the filter's
taps, and the logits of the last position.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many of the first ``n_layers`` layers are of each kind."""
    types = c["layer_types"][:n_layers]
    dense = min(c["num_dense_layers"], n_layers)
    return {"conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "dense": dense, "sparse": n_layers - dense}


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, experts_touched: float) -> float:
    """``experts_touched``: held experts that got at least one token,
    summed over the expert layers of one tick."""
    return experts_touched * expert_params(c) * _itemsize(c)


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one position in one attention layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * _itemsize(c)


def kv_read_bytes(c: dict, n_layers: int, live_positions: float) -> float:
    """``live_positions``: positions held, summed over slots."""
    return (kv_bytes_per_position(c)
            * layer_kinds(c, n_layers)["attention"] * live_positions)


def tail_bytes_per_lane(c: dict) -> int:
    """One lane's tail in one convolution layer."""
    return (c["conv_L_cache"] - 1) * c["hidden_size"] * _itemsize(c)


def tail_bytes(c: dict, n_layers: int, busy_lanes: float) -> float:
    """Read once and written once, each busy lane, each convolution
    layer."""
    return (busy_lanes * layer_kinds(c, n_layers)["conv"] * 2
            * tail_bytes_per_lane(c))


def conv_matrix_params(c: dict) -> int:
    """A convolution mixer's matrices: ``d -> 3d`` in, ``d -> d`` out."""
    return 4 * c["hidden_size"] ** 2


def conv_mixer_params(c: dict) -> int:
    """The matrices, the filter and the operator norm."""
    return conv_matrix_params(c) + (c["conv_L_cache"] + 1) * c["hidden_size"]


def attention_matrix_params(c: dict) -> int:
    d, hd = c["hidden_size"], head_dim(c)
    return 2 * d * hd * (c["num_attention_heads"]
                         + c["num_key_value_heads"])


def attention_mixer_params(c: dict) -> int:
    """The four matrices, the operator norm and the two head norms."""
    return attention_matrix_params(c) + c["hidden_size"] + 2 * head_dim(c)


def dense_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def other_weight_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    """Everything a tick reads once: every weight but the routed
    experts' (the tied embedding whole, as the head; the row a slot
    embeds is one of them), one new position of keys and values a slot
    in each attention layer."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    params = n["conv"] * conv_mixer_params(c) \
        + n["attention"] * attention_mixer_params(c) \
        + n["dense"] * (dense_params(c) + d) \
        + n["sparse"] * (d + d * total) \
        + d + d * c["vocab_size"]
    return (params * _itemsize(c) + n["sparse"] * total * 4
            + n["attention"] * n_slots * kv_bytes_per_position(c))


def prefill_flops(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """The products of a prompt of ``prompt_tokens`` real positions: two
    operations a matrix weight a token (of the routed experts, a
    token's ``num_experts_per_tok`` choices times the held share); in
    an attention layer the causal half of ``q k^T`` and of ``p v``; in a
    convolution layer the two gates and the filter's taps a channel;
    the logits of the last position."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    routed = c["num_experts_per_tok"] * c["num_experts"] / total
    weights = n["conv"] * conv_matrix_params(c) \
        + n["attention"] * attention_matrix_params(c) \
        + n["dense"] * dense_params(c) \
        + n["sparse"] * (d * total + routed * expert_params(c))
    scores = n["attention"] * 2 * c["num_attention_heads"] * head_dim(c) \
        * prompt_tokens ** 2
    gates = n["conv"] * (2 + 2 * c["conv_L_cache"]) * d * prompt_tokens
    return (2 * weights * prompt_tokens + scores + gates
            + 2 * d * c["vocab_size"])
