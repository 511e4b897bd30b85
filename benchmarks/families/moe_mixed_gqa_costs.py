"""Bytes a decode tick of the ``moe-mixed-gqa`` family *needs*, from
shapes alone (``c`` is a configuration file's dict; Hugging Face key
names). A tick decodes one token a slot, so every part is bound by the
bytes it has to read, not by its operations.

Three parts, each with a kernel metric of its own, and their sum:

- the routed experts: the three matrices of every *held expert that a
  tick's tokens touched* (counted by the program, ``ENG_ROUTE``), never
  of all that are held: a grouped product that skips an untouched
  expert is not above its roofline for it;
- keys and values: in a full layer at every live position of every
  slot, in a window layer at the live positions inside the window;
- everything else once: attention projections and gates, norms, the
  dense layer, routers, shared experts, the head, one embedding row and
  one new position of keys and values a slot.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many of the first ``n_layers`` layers are of each kind."""
    types = c["layer_types"][:n_layers]
    mlps = c["mlp_layer_types"][:n_layers]
    return {"full": types.count("full_attention"),
            "window": types.count("sliding_attention"),
            "dense": mlps.count("dense"), "sparse": mlps.count("sparse")}


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, experts_touched: float) -> float:
    """``experts_touched``: held experts that got at least one token,
    summed over the expert layers of one tick."""
    return experts_touched * expert_params(c) * _itemsize(c)


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one position in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * _itemsize(c)


def kv_read_bytes(c: dict, n_layers: int, live_positions: float,
                  live_window_positions: float) -> float:
    """``live_positions``: positions held, summed over slots;
    ``live_window_positions``: the same with each slot's count clipped
    to ``sliding_window``."""
    n = layer_kinds(c, n_layers)
    return kv_bytes_per_position(c) * (
        n["full"] * live_positions + n["window"] * live_window_positions)


def attention_params(c: dict, layer: int) -> int:
    """Projections, output gate and the two norms of one layer."""
    d, hd = c["hidden_size"], c["head_dim"]
    nq = c["num_attention_heads_per_layer"][layer] * hd
    nkv = c["num_key_value_heads"] * hd
    return 2 * d * nq + 2 * d * nkv + d * (nq // hd) + 2 * d


def other_weight_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    d = c["hidden_size"]
    n = layer_kinds(c, n_layers)
    params = sum(attention_params(c, l) for l in range(n_layers))
    params += n["dense"] * 3 * d * c["intermediate_size"]
    params += n["sparse"] * (
        d * c["deployment"]["experts_total"]
        + 3 * d * c["shared_expert_intermediate_size"])
    params += d + d * c["vocab_size"] + n_slots * d
    return (params * _itemsize(c)
            + n_layers * n_slots * kv_bytes_per_position(c))
