"""The flagship configuration: the one model shape every root script,
``chip_smoke.py``, ``tpu_tests/`` and the co-location tests agree on."""

from __future__ import annotations

import jax.numpy as jnp

from pbs_tpu.models.transformer import TransformerConfig


def flagship_config(tiny: bool = False) -> TransformerConfig:
    """~700M Llama-shaped decoder (``tiny=True``: the d_model-128 shape
    the CPU tests and rehearsals run)."""
    if tiny:
        return TransformerConfig(
            vocab=256, d_model=128, n_layers=2, n_heads=8, n_kv_heads=4,
            d_ff=256, max_seq=128, dtype=jnp.float32,
        )
    # d_model 2048 (MXU-friendly tiles), GQA 16/8, SwiGLU, bf16,
    # remat("dots"): a roofline-motivated starting point, not a
    # measured optimum (PERF.md says what has been measured).
    return TransformerConfig(
        vocab=32_768, d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8,
        d_ff=5_632, max_seq=1024, dtype=jnp.bfloat16,
        remat=True, remat_policy="dots",
    )
