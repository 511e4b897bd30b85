"""Operations and bytes a program *needs*, from shapes alone.

XLA's ``cost_analysis()`` bills the body of the layer ``lax.scan`` once
(PERF.md section 5), so nothing here reads it. ``c`` is a configuration
file's dict (Hugging Face key names); ``n_layers`` is the depth as run.
"""

from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    hd = d // c["num_attention_heads"]
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * nq + 2 * d * nkv + nq * d + 3 * d * f


def matmul_params(c: dict, n_layers: int) -> int:
    """Weights that are multiplied: the layers and the head (the
    embedding is a gather)."""
    return (n_layers * layer_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def train_step_flops(c: dict, n_layers: int, batch: int, seq: int) -> float:
    """Forward + backward of one step on ``batch`` rows of ``seq``
    tokens, of which ``seq - 1`` positions are fed. 6 FLOPs per matmul
    weight per position, plus causal attention (QK^T and PV, half of
    the square, three passes). Recomputation is not counted."""
    s = seq - 1
    hd = c["hidden_size"] // c["num_attention_heads"]
    attn_fwd = 2 * (2 * batch * c["num_attention_heads"] * s * s * hd) / 2
    return (6.0 * matmul_params(c, n_layers) * batch * s
            + 3.0 * n_layers * attn_fwd)


def kv_bytes_per_position(c: dict, n_layers: int, itemsize: int = 2) -> int:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 2 * n_layers * c["num_key_value_heads"] * hd * itemsize


def decode_tick_bytes(c: dict, n_layers: int, n_slots: int,
                      live_positions: float, itemsize: int = 2) -> float:
    """Bytes one decode tick has to move: every matmul weight and norm
    once, one embedding row per slot, the keys and values of the
    positions that are live, and one new position per slot written."""
    d = c["hidden_size"]
    weights = (matmul_params(c, n_layers) + (2 * n_layers + 1) * d
               + n_slots * d) * itemsize
    kv = kv_bytes_per_position(c, n_layers, itemsize)
    return weights + kv * (live_positions + n_slots)
