"""KV-cache serving path: prefill/decode consistency, generation."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import (
    flagship_config,
    forward,
    forward_with_cache,
    init_cache,
    init_params,
    make_generate,
    make_serve_step,
    prefill,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = flagship_config(tiny=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_cached_forward_matches_full_forward(tiny):
    """Prefill logits must equal the training-path forward on the same
    tokens — the cache changes memory layout, not math."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab,
                                jnp.int32)
    full = forward(cfg, params, tokens)
    cache = init_cache(cfg, 2, max_len=32)
    cached, cache = forward_with_cache(cfg, params, tokens, cache)
    np.testing.assert_allclose(np.asarray(full), np.asarray(cached),
                               rtol=2e-4, atol=2e-4)
    assert int(cache["pos"]) == 16


def test_incremental_decode_matches_prefill(tiny):
    """Feeding tokens one at a time through the cache must reproduce
    the all-at-once logits (the KV cache is exact, not approximate)."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab,
                                jnp.int32)
    all_at_once, _ = forward_with_cache(
        cfg, params, tokens, init_cache(cfg, 1, max_len=8))
    cache = init_cache(cfg, 1, max_len=8)
    step_logits = []
    for i in range(8):
        lg, cache = forward_with_cache(cfg, params, tokens[:, i:i + 1], cache)
        step_logits.append(lg[:, 0])
    inc = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(all_at_once), np.asarray(inc),
                               rtol=2e-3, atol=2e-3)


def test_generate_greedy_deterministic_and_jittable(tiny):
    cfg, params = tiny
    gen = jax.jit(make_generate(cfg, max_new_tokens=6, temperature=0.0))
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, cfg.vocab,
                                jnp.int32)
    a = gen(params, prompt, jax.random.PRNGKey(7))
    b = gen(params, prompt, jax.random.PRNGKey(8))  # greedy: key-invariant
    assert a.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generate_matches_stepwise_greedy(tiny):
    """The scanned decode loop must agree with a hand-rolled greedy
    loop over prefill + single-token steps."""
    cfg, params = tiny
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 4), 0, cfg.vocab,
                                jnp.int32)
    gen = make_generate(cfg, max_new_tokens=5, temperature=0.0)
    fast = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))

    cache = init_cache(cfg, 1, max_len=4 + 5)
    last, cache = prefill(cfg, params, prompt, cache)
    toks = []
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    for _ in range(5):
        toks.append(int(tok[0]))
        lg, cache = forward_with_cache(cfg, params, tok[:, None], cache)
        tok = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(fast[0], np.array(toks))


def test_serve_step_is_a_schedulable_job(tiny):
    """The serving loop plugs into the runtime as a Job step_fn."""
    cfg, params = tiny
    serve = jax.jit(make_serve_step(cfg, max_new_tokens=4))
    prompts = jnp.zeros((2, 3), jnp.int32)
    state = (params, jax.random.PRNGKey(0), 0)
    state, metrics = serve(state, prompts)
    assert int(state[2]) == 1
    assert int(metrics["tokens"]) == 2 * 4
