"""What a decode tick and a prompt's ingestion of the ``dense-mamba-mqa``
family *need*, from shapes alone (``c`` is a configuration file's dict;
Hugging Face key names): what the recurrence and the layers around it
have to read, write and multiply, whatever implements them.

A tick decodes one token a slot, so every part of it is bound by the
bytes it has to move:

- the recurrent state: every *busy* lane's float32 ``(d_state,
  d_inner)`` matrix and its convolution tail, read once and written
  once, in each state-space layer (an idle lane's state needs nothing);
- keys and values: every live position of every slot, in the attention
  layers (one KV head: 512 bytes a position a layer in bfloat16);
- everything else once: every weight (the tied embedding is the head:
  the logits read all of it), one new position of keys and values a
  slot.

A prompt's ingestion multiplies: per real prompt token two operations a
matrix weight, and in the attention layers the causal scores and their
product with the values. Its selective scan multiplies nothing the MXU
takes; what a fused scan has to *move* is, per real position and
state-space layer, ``x``, ``dt`` and ``z`` in and ``y`` out at
``d_inner`` and ``B`` and ``C`` at ``d_state`` in the activations' type,
and the final state once.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many of the first ``n_layers`` layers are of each kind."""
    attention = sum(
        1 for l in range(n_layers)
        if l % c["attn_layer_period"] == c["attn_layer_offset"])
    return {"attention": attention, "mamba": n_layers - attention}


def mamba_sizes(c: dict) -> tuple[int, int, int, int]:
    """(d_inner, d_state, dt_rank, convolution kernel)."""
    return (c["mamba_expand"] * c["hidden_size"], c["mamba_d_state"],
            c["mamba_dt_rank"], c["mamba_d_conv"])


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one position in one attention layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * _itemsize(c)


def kv_read_bytes(c: dict, n_layers: int, live_positions: float) -> float:
    """``live_positions``: positions held, summed over slots."""
    return (kv_bytes_per_position(c)
            * layer_kinds(c, n_layers)["attention"] * live_positions)


def state_bytes_per_lane(c: dict) -> int:
    """One lane's recurrent state in one state-space layer: the float32
    matrix and the convolution tail."""
    C, N, _, taps = mamba_sizes(c)
    return C * N * 4 + (taps - 1) * C * _itemsize(c)


def mamba_state_bytes(c: dict, n_layers: int, busy_lanes: float) -> float:
    """Read once and written once, each busy lane, each layer."""
    return (busy_lanes * layer_kinds(c, n_layers)["mamba"] * 2
            * state_bytes_per_lane(c))


def mamba_scan_bytes(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """The least a fused scan moves for a prompt of ``prompt_tokens``
    real positions, all state-space layers."""
    C, N, _, _ = mamba_sizes(c)
    per_position = (4 * C + 2 * N) * _itemsize(c)
    return layer_kinds(c, n_layers)["mamba"] * (
        prompt_tokens * per_position + C * N * 4)


def mamba_matrix_params(c: dict) -> int:
    """A state-space mixer's matrices: in, x, dt and out projections."""
    d = c["hidden_size"]
    C, N, R, _ = mamba_sizes(c)
    return d * 2 * C + C * (R + 2 * N) + R * C + C * d


def mamba_mixer_params(c: dict) -> int:
    """The matrices, the filter and its bias, the layer's norm and the
    three small ones, held in the weights' type."""
    C, N, R, taps = mamba_sizes(c)
    return (mamba_matrix_params(c) + taps * C + C + c["hidden_size"]
            + R + 2 * N)


def mamba_float32_params(c: dict) -> int:
    """``A_log``, ``D`` and ``dt_bias``: float32 whatever the matrices
    are."""
    C, N, _, _ = mamba_sizes(c)
    return N * C + 2 * C


def attention_matrix_params(c: dict) -> int:
    d = c["hidden_size"]
    return 2 * d * d + 2 * d * c["num_key_value_heads"] * head_dim(c)


def mlp_matrix_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def weight_bytes(c: dict, n_layers: int) -> int:
    """The tree the program serves: every layer, the tied embedding
    once, the last norm."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    held = n["mamba"] * mamba_mixer_params(c) \
        + n["attention"] * (attention_matrix_params(c) + d) \
        + n_layers * (mlp_matrix_params(c) + d) \
        + c["vocab_size"] * d + d
    return held * _itemsize(c) + n["mamba"] * mamba_float32_params(c) * 4


def prefill_flops(c: dict, n_layers: int, prompt_tokens: float) -> float:
    """The matrix products of a prompt of ``prompt_tokens`` real
    positions: two operations a matrix weight a token; in an attention
    layer the causal half of ``q k^T`` and of ``p v`` (heads x head
    size x tokens^2 each way); the logits of the last position."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    weights = n["mamba"] * mamba_matrix_params(c) \
        + n["attention"] * attention_matrix_params(c) \
        + n_layers * mlp_matrix_params(c)
    scores = n["attention"] * 2 * d * prompt_tokens ** 2
    return 2 * weights * prompt_tokens + scores + 2 * d * c["vocab_size"]


def other_tick_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    """Every weight once and one new position of keys and values a
    slot."""
    return weight_bytes(c, n_layers) + layer_kinds(c, n_layers)[
        "attention"] * n_slots * kv_bytes_per_position(c)
