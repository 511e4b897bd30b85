"""What a decode tick and a prompt's ingestion of the ``moe-kda-gqa``
family *need*, from shapes alone (``c`` is a configuration file's dict;
Hugging Face key names): what the recurrence and the layers around it
have to read, write and multiply, whatever implements them.

A tick decodes one token a slot, so every part of it is bound by the
bytes it has to move:

- the recurrent state: every *busy* lane's float32 ``(dk, dv)`` matrix
  a head and its convolution tail, read once and written once, in each
  delta-rule layer (an idle lane's state needs nothing);
- the routed experts: the three matrices of every *held expert that a
  tick's tokens touched* (counted by the program, ``ENG_ROUTE``);
- keys and values: every live position of every slot, in the softmax
  layers;
- everything else once: the mixers' projections, filters, low-rank
  pairs and gates, norms, routers, shared experts, the head, one
  embedding row and one new position of keys and values a slot.

A prompt's ingestion multiplies: per real prompt token and delta-rule
layer the mixer's matrix products and the recurrence itself (decay,
``S^T k``, the rank-one correction, ``S^T q``: ``6 dk dv`` a head).
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many of the first ``n_layers`` layers are of each kind."""
    softmax = sum(1 for l in range(n_layers) if l in c["gqa_layers"])
    return {"softmax": softmax, "kda": n_layers - softmax}


def kda_sizes(c: dict) -> tuple[int, int, int, int]:
    """(heads, head size, convolution kernel, low rank)."""
    lin = c["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"], lin["head_dim"])


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, experts_touched: float) -> float:
    """``experts_touched``: held experts that got at least one token,
    summed over the expert layers of one tick."""
    return experts_touched * expert_params(c) * _itemsize(c)


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one position in one softmax layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * _itemsize(c)


def kv_read_bytes(c: dict, n_layers: int, live_positions: float) -> float:
    """``live_positions``: positions held, summed over slots."""
    return (kv_bytes_per_position(c) * layer_kinds(c, n_layers)["softmax"]
            * live_positions)


def state_bytes_per_lane(c: dict) -> int:
    """One lane's recurrent state in one delta-rule layer: the float32
    matrices and the convolution tail (q, k and v side by side)."""
    H, hd, taps, _ = kda_sizes(c)
    return H * hd * hd * 4 + (taps - 1) * 3 * H * hd * _itemsize(c)


def kda_state_bytes(c: dict, n_layers: int, busy_lanes: float) -> float:
    """Read once and written once, each busy lane, each layer."""
    return (busy_lanes * layer_kinds(c, n_layers)["kda"] * 2
            * state_bytes_per_lane(c))


def kda_matrix_params(c: dict) -> int:
    """A delta-rule mixer's matrices: q, k, v and output projections,
    the decay's and the gate's low-rank pairs, beta."""
    d = c["hidden_size"]
    H, hd, _, rank = kda_sizes(c)
    w = H * hd
    return 4 * d * w + 2 * (d * rank + rank * w) + d * H


def kda_mixer_params(c: dict) -> int:
    """The matrices, the three filters and the two norms, held in the
    weights' type."""
    H, hd, taps, _ = kda_sizes(c)
    return (kda_matrix_params(c) + 3 * taps * H * hd + hd
            + c["hidden_size"])


def kda_float32_params(c: dict) -> int:
    """``A_log`` and ``dt_bias``: float32 whatever the matrices are."""
    H, hd, _, _ = kda_sizes(c)
    return H + H * hd


def softmax_mixer_params(c: dict) -> int:
    """Projections, the elementwise gate and the norm."""
    d, hd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return 3 * d * nq + 2 * d * nkv + d


def kda_prefill_flops(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """Per real prompt token and delta-rule layer: two operations a
    matrix weight, and the recurrence's ``6 dk dv`` a head."""
    H, hd, _, _ = kda_sizes(c)
    return (prompt_tokens * layer_kinds(c, n_layers)["kda"]
            * (2 * kda_matrix_params(c) + 6 * hd * hd * H))


def other_weight_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    d = c["hidden_size"]
    n = layer_kinds(c, n_layers)
    params = n["kda"] * kda_mixer_params(c) \
        + n["softmax"] * softmax_mixer_params(c)
    total = c["deployment"]["experts_total"]
    shared = c["n_shared_experts"] * c["moe_intermediate_size"]
    params += n_layers * (d + d * total + 3 * d * shared)
    params += d + d * c["vocab_size"] + n_slots * d
    float32 = n["kda"] * kda_float32_params(c) + n_layers * total
    return (params * _itemsize(c) + float32 * 4
            + n["softmax"] * n_slots * kv_bytes_per_position(c))
