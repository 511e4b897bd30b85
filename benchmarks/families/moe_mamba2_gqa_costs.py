"""What a decode tick and a prompt's forward of the ``moe-mamba2-gqa``
family *need*, from shapes alone (``c`` is a configuration file's dict;
Hugging Face key names): what the blocks have to read, write and
multiply, whatever implements them.

A tick decodes one token a slot, so every part of it is bound by the
bytes it has to move:

- the Mamba-2 state: every *busy* lane's float32 ``(head_dim, d_state)``
  matrix a head and its convolution tail, read once and written once, in
  each Mamba-2 block (an idle lane's state needs nothing);
- the routed experts: the **two** matrices (up and down: the relu^2
  expert has no gate) of every *held expert that a tick's tokens
  touched* (counted by the program, ``ENG_ROUTE``);
- keys and values: every live position of every slot, in the attention
  blocks;
- everything else once: the mixers' projections, filters and norms,
  routers, shared experts, the head, one embedding row and one new
  position of keys and values a slot.

A prompt's forward multiplies. In a Mamba-2 block's scan, per real
position: its dot products with the positions of its chunk at or before
it (``C_t . B_u``, one a group; times the input, one a head: the causal
half of the chunk's square) and two products with the state a head
(what the position adds to it, what it reads off it). In the whole
forward besides: two operations a matrix weight a token (of the experts,
the share of a token's ``num_experts_per_tok`` choices that falls on
the held ones), and the causal half of the attention blocks' squares.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _itemsize(c: dict) -> int:
    return ITEMSIZE[c["serve"]["weights_dtype"]]


def layer_kinds(c: dict, n_layers: int) -> dict:
    """How many of the first ``n_layers`` blocks are of each kind."""
    letters = c["hybrid_override_pattern"][:n_layers]
    return {"mamba": letters.count("M"), "attention": letters.count("*"),
            "experts": letters.count("E")}


def mamba_sizes(c: dict) -> tuple[int, int, int, int, int]:
    """(heads, head size, groups, state size, convolution kernel)."""
    return (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
            c["ssm_state_size"], c["conv_kernel"])


def conv_channels(c: dict) -> int:
    """x, B and C side by side."""
    H, P, G, N, _ = mamba_sizes(c)
    return H * P + 2 * G * N


def expert_params(c: dict) -> int:
    """One routed expert: up and down, no gate."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_bytes(c: dict, experts_touched: float) -> float:
    """``experts_touched``: held experts that got at least one token,
    summed over the expert blocks of one tick."""
    return experts_touched * expert_params(c) * _itemsize(c)


def kv_bytes_per_position(c: dict) -> int:
    """Keys and values of one position in one attention block."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * _itemsize(c)


def kv_read_bytes(c: dict, n_layers: int, live_positions: float) -> float:
    """``live_positions``: positions held, summed over slots."""
    return (kv_bytes_per_position(c)
            * layer_kinds(c, n_layers)["attention"] * live_positions)


def state_bytes_per_lane(c: dict) -> int:
    """One lane's state in one Mamba-2 block: the float32 matrices and
    the convolution tail."""
    H, P, _, N, taps = mamba_sizes(c)
    return H * P * N * 4 + (taps - 1) * conv_channels(c) * _itemsize(c)


def mamba2_state_bytes(c: dict, n_layers: int, busy_lanes: float) -> float:
    """Read once and written once, each busy lane, each block."""
    return (busy_lanes * layer_kinds(c, n_layers)["mamba"] * 2
            * state_bytes_per_lane(c))


def mamba2_scan_flops(c: dict, n_layers: int, prompt_tokens: float,
                      chunk: int) -> float:
    """The three products of the matrix form at ``chunk`` positions a
    chunk, per real position and Mamba-2 block: against the ``(chunk +
    1) / 2`` positions of its chunk at or before it, ``C . B`` a group
    and the weighted input a head; into the state and out of it, ``2 P
    N`` a head each."""
    H, P, G, N, _ = mamba_sizes(c)
    pairs = (chunk + 1) / 2
    per_position = pairs * (G * 2 * N + H * 2 * P) + H * 4 * P * N
    return prompt_tokens * layer_kinds(c, n_layers)["mamba"] * per_position


def mamba2_matrix_params(c: dict) -> int:
    """A Mamba-2 mixer's matrices: the in-projection (z, x, B, C, dt)
    and the out-projection."""
    H, P, _, _, _ = mamba_sizes(c)
    inner = H * P
    return c["hidden_size"] * (inner + conv_channels(c) + H + inner)


def mamba2_mixer_params(c: dict) -> int:
    """The matrices, the filter and its bias, the block's norm and the
    gated one, held in the weights' type."""
    H, P, _, _, taps = mamba_sizes(c)
    return (mamba2_matrix_params(c) + (taps + 1) * conv_channels(c)
            + H * P + c["hidden_size"])


def mamba2_float32_params(c: dict) -> int:
    """``A_log``, ``D`` and ``dt_bias``: float32 whatever the matrices
    are."""
    return 3 * c["mamba_num_heads"]


def attention_matrix_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    return 2 * d * hd * (c["num_attention_heads"]
                         + c["num_key_value_heads"])


def shared_params(c: dict) -> int:
    return 2 * c["hidden_size"] * c["moe_shared_expert_intermediate_size"]


def other_weight_bytes(c: dict, n_layers: int, n_slots: int) -> float:
    """Everything a tick reads once: every weight but the routed
    experts', one embedding row a slot, one new position of keys and
    values a slot in each attention block."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    params = n["mamba"] * mamba2_mixer_params(c) \
        + n["attention"] * (attention_matrix_params(c) + d) \
        + n["experts"] * (d + d * total + shared_params(c)) \
        + d + d * c["vocab_size"] + n_slots * d
    float32 = n["mamba"] * mamba2_float32_params(c) + n["experts"] * total
    return (params * _itemsize(c) + float32 * 4
            + n["attention"] * n_slots * kv_bytes_per_position(c))


def prefill_flops(c: dict, n_layers: int, prompt_tokens: float,
                  chunk: int) -> float:
    """The products of a prompt of ``prompt_tokens`` real positions: two
    operations a matrix weight a token (of the routed experts, the held
    share of a token's choices: ``num_experts_per_tok`` x held /
    total); the Mamba-2 blocks' scans; in an attention block the causal
    half of ``q k^T`` and of ``p v``; the logits of the last position."""
    d, n = c["hidden_size"], layer_kinds(c, n_layers)
    total = c["deployment"]["experts_total"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / total
    weights = n["mamba"] * mamba2_matrix_params(c) \
        + n["attention"] * attention_matrix_params(c) \
        + n["experts"] * (d * total + shared_params(c)
                          + routed * expert_params(c))
    scores = n["attention"] * 2 * c["num_attention_heads"] \
        * c["head_dim"] * prompt_tokens ** 2
    return (2 * weights * prompt_tokens + scores
            + mamba2_scan_flops(c, n_layers, prompt_tokens, chunk)
            + 2 * d * c["vocab_size"])
