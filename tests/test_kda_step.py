"""The one-pass recurrent step (``pbs_tpu/ops/kda_step.py``) in Pallas
interpret mode, at the kernel's own tiling (heads of 128 channels, a
block of 8 or 16 heads): against the ``jax.numpy`` step the CPU lowers
(``models/kda.py::state_step``) and against the delta rule in float64
on the host. What the chip's compiler makes of it is
``tests/test_tpu_compile.py``'s to say, and what the chip computes
``tpu_tests/``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models.kda import state_step
from pbs_tpu.ops.kda_step import kda_state_step

HD = 128
TICKS = 3
LANES = {"all-active": lambda B: np.ones(B, bool),
         "one-idle": lambda B: np.arange(B) != 1,
         "all-idle": lambda B: np.zeros(B, bool)}
#: (beta's range, the log decay's range): beta past one is a negative
#: eigenvalue of the transition; e^-30 a token empties a channel at once
REGIMES = {"mixed": ((0.0, 2.0), (np.log(1e-3), np.log(1.6))),
           "negative-eigenvalue": ((1.0, 2.0), (np.log(1e-3), np.log(1.6))),
           "fast-decay": ((0.0, 2.0), (np.log(29.0), np.log(30.0)))}


def tick_inputs(B, H, regime, tick):
    """One tick's alpha, k, q, v (B, H, HD) and beta (B, H) as the
    mixer gives them: unit keys, queries of length ``HD ** -0.5``."""
    (b_lo, b_hi), (g_lo, g_hi) = REGIMES[regime]
    ks = jax.random.split(jax.random.PRNGKey(97 * tick + B + H), 5)
    q, k, v = (jax.random.normal(kk, (B, H, HD), jnp.float32)
               for kk in ks[:3])
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    g = -jnp.exp(jax.random.uniform(ks[3], (B, H, HD), jnp.float32,
                                    g_lo, g_hi))
    beta = jax.random.uniform(ks[4], (B, H), jnp.float32, b_lo, b_hi)
    return jnp.exp(g), unit(k), unit(q) * HD ** -0.5, v, beta


def recurrence64(state, alpha, k, q, v, beta, active):
    """``S <- Diag(alpha) S; S <- S + beta k (v - S^T k)^T; o = S^T q``
    for the active lanes, float64 on the host."""
    alpha, k, q, v, beta = (np.asarray(t, np.float64)
                            for t in (alpha, k, q, v, beta))
    new = state * alpha[..., None]
    seen = np.einsum("bhkv,bhk->bhv", new, k)
    new = new + beta[..., None, None] * k[..., None] \
        * (v - seen)[:, :, None, :]
    o = np.einsum("bhkv,bhk->bhv", new, q)
    return o, np.where(active[:, None, None, None], new, state)


def close(got, want, tol=1e-5):
    """To ``tol`` of the largest entry: a float32 sum of 128 products
    is a few roundings of its largest term, not of each entry."""
    return float(np.abs(np.asarray(got, np.float64) - want).max()) \
        <= tol * max(float(np.abs(want).max()), 1e-30)


@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("lanes", list(LANES))
@pytest.mark.parametrize("B,H", [(3, 8), (2, 16)])
def test_the_kernel_steps_a_state_as_the_recurrence_does(B, H, lanes,
                                                          regime):
    """Three ticks chained from a seeded state: the kernel's state and
    output stay within 1e-5 of the float64 recurrence's and of the
    ``jax.numpy`` step's, tick after tick; an idle lane's state comes
    out bit for bit as it went in."""
    active = LANES[lanes](B)
    kernel = jax.jit(functools.partial(kda_state_step, interpret=True))
    oracle = jax.jit(state_step)
    first = 0.5 * jax.random.normal(jax.random.PRNGKey(B * H),
                                    (B, H, HD, HD), jnp.float32)
    got, numpy_way, want = first, first, np.asarray(first, np.float64)
    for tick in range(TICKS):
        ins = tick_inputs(B, H, regime, tick)
        o, got = kernel(got, *ins, jnp.asarray(active))
        o_np, numpy_way = oracle(numpy_way, *ins, jnp.asarray(active))
        o64, want = recurrence64(want, *ins, active)
        assert got.dtype == jnp.float32 and o.shape == (B, H, HD)
        assert close(got, want) and close(numpy_way, want), tick
        assert close(got, np.asarray(numpy_way, np.float64)), tick
        live = np.flatnonzero(active)
        if len(live):       # an idle lane's output is nobody's
            assert close(np.asarray(o)[live], o64[live]), tick
            assert close(np.asarray(o)[live],
                         np.asarray(o_np, np.float64)[live]), tick
    idle = np.flatnonzero(~active)
    np.testing.assert_array_equal(
        np.asarray(got)[idle].view(np.uint32),
        np.asarray(first)[idle].view(np.uint32))
