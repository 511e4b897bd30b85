"""The one-pass prompt scan (``pbs_tpu/ops/mamba_scan.py``) in Pallas
interpret mode, at the kernel's own tiling (16 states, tiles of 128
channels, blocks of 128 positions): against the ``jax.numpy`` scan the
CPU lowers (``models/mamba.py::mamba_scan``) and against the recurrence
a position at a time in float64 on the host. What the chip's compiler
makes of it is ``tests/test_tpu_compile.py``'s to say, and what the
chip computes ``tpu_tests/``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models.mamba import mamba_scan
from pbs_tpu.ops import mamba_scan as kernel_module
from pbs_tpu.ops.mamba_scan import BLOCK, mamba_prompt_scan, mamba_scan_tiles

N, C = 16, 256


@pytest.fixture(autouse=True)
def two_tiles_of_channels(monkeypatch):
    """A tile of 128 channels, so that 256 are two grid rows."""
    monkeypatch.setattr(kernel_module, "STATE_BYTES", 4 * N * 128)


def kernel(*args):
    return jax.jit(functools.partial(mamba_prompt_scan, interpret=True))(
        *args)


def recurrence64(x, dt, bm, cm, a_log):
    """``h <- exp(dt A) h + (dt x) B; y = sum_n h C`` a position at a
    time, float64 on the host: (y (S, C), the last state (N, C))."""
    x, dt, bm, cm, a_log = (np.asarray(t, np.float64)
                            for t in (x, dt, bm, cm, a_log))
    A = -np.exp(a_log)
    h, out = np.zeros(a_log.shape), np.zeros(x.shape)
    for t in range(len(x)):
        h = np.exp(dt[t][None, :] * A) * h \
            + (dt[t] * x[t])[None, :] * bm[t][:, None]
        out[t] = (h * cm[t][:, None]).sum(0)
    return out, h


def inputs(plen: int, rung: int, fast: bool = False):
    """A prompt of ``plen`` positions padded to ``rung``: steps
    log-uniform in [0.001, 0.1] (``fast``: up to 30) against A = -(1 ..
    16), seeded by the length alone; the padding enters with ``dt`` 0
    and garbage in everything else."""
    keys = jax.random.split(jax.random.PRNGKey(plen), 4)
    x = jax.random.normal(keys[0], (plen, C), jnp.float32)
    bm, cm = (jax.random.normal(k, (plen, N), jnp.float32)
              for k in keys[1:3])
    dt = jnp.exp(jax.random.uniform(
        keys[3], (plen, C), jnp.float32, np.log(1e-3),
        np.log(30.0 if fast else 0.1)))
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(
        1, N + 1, dtype=jnp.float32)[:, None], (N, C)))
    pad = lambda t, fill: jnp.pad(  # noqa: E731
        t, ((0, rung - plen), (0, 0)), constant_values=fill)
    return pad(x, 7.0), pad(dt, 0.0), pad(bm, 7.0), pad(cm, 7.0), a_log


def close(got, want, tol=1e-5):
    """To ``tol`` of the largest entry: a float32 sum of 16 products is
    a few roundings of its largest term, not of each entry."""
    return float(np.abs(np.asarray(got, np.float64) - want).max()) \
        <= tol * max(float(np.abs(want).max()), 1e-30)


def bits(a):
    return np.asarray(a).view(np.uint32)


#: 1, one under, at and over the ``jax.numpy`` scan's chunk, a whole
#: block and a whole rung of two, and lengths inside the second block
LENGTHS = [(1, BLOCK), (63, BLOCK), (64, BLOCK), (65, BLOCK),
           (BLOCK, BLOCK), (BLOCK + 1, 2 * BLOCK), (200, 2 * BLOCK),
           (2 * BLOCK, 2 * BLOCK), (100, 3 * BLOCK)]


@pytest.mark.parametrize("plen,rung", LENGTHS)
def test_the_kernel_scans_a_prompt_as_the_recurrence_does(plen, rung):
    """The kernel's outputs over the prompt's real positions and its
    final state lie within 1e-5 of the float64 recurrence's over the
    exact length and of the ``jax.numpy`` scan's; rows of a block that
    lies wholly behind the prompt are zeros, whatever the padding
    held."""
    args = inputs(plen, rung)
    assert mamba_scan_tiles((rung, C, N))
    y, h = kernel(*args, jnp.int32(plen))
    assert y.shape == (rung, C) and h.shape == (N, C)
    assert y.dtype == h.dtype == jnp.float32
    want_y, want_h = recurrence64(*(t[:plen] for t in args[:4]), args[4])
    y_np, h_np = jax.jit(mamba_scan)(*args)
    assert close(y[:plen], want_y) and close(h, want_h)
    assert close(y[:plen], np.asarray(y_np[:plen], np.float64))
    assert close(h, np.asarray(h_np, np.float64))
    behind = -(-plen // BLOCK) * BLOCK
    assert not np.asarray(y[behind:]).any()


def test_a_decay_of_e_to_the_minus_thirty_a_token_neither_overflows_nor_nans():
    """Every exponent is ``<= 0``: channels that forget at e^-30 ..
    e^-480 a token underflow to zero and the rest agree with
    float64."""
    args = inputs(2 * BLOCK, 2 * BLOCK, fast=True)
    assert float((args[1] * jnp.exp(args[4])[-1]).max()) > 400
    y, h = kernel(*args, jnp.int32(2 * BLOCK))
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(h)).all()
    want_y, want_h = recurrence64(*args)
    assert close(y, want_y) and close(h, want_h)


@pytest.mark.parametrize("plen", [1, 100, BLOCK, 200])
def test_the_same_positions_at_two_rungs_leave_the_same_bits(plen):
    """A position's arithmetic knows nothing of the rung: the state and
    the real rows of ``y`` are the same bits at two and at three blocks,
    and the same again when no block is passed over (the kernel told
    the whole rung is real: the padding's ``dt`` 0 alone makes it a
    no-op)."""
    runs = []
    for rung, told in ((2 * BLOCK, plen), (3 * BLOCK, plen),
                       (3 * BLOCK, 3 * BLOCK)):
        y, h = kernel(*inputs(plen, rung), jnp.int32(told))
        runs.append((bits(y[:plen]), bits(h)))
    for y, h in runs[1:]:
        assert np.array_equal(y, runs[0][0])
        assert np.array_equal(h, runs[0][1])
    assert runs[0][1].any()


def test_a_shape_the_tiling_does_not_take_is_said_so():
    assert mamba_scan_tiles((2048, 5120, 16))
    assert mamba_scan_tiles((1024, 5120, 16))
    assert not mamba_scan_tiles((24, 64, 8))        # the tests' toy model
    assert not mamba_scan_tiles((2048, 5120 + 64, 16))
    assert not mamba_scan_tiles((2048 + 64, 5120, 16))
    assert not mamba_scan_tiles((2048, 5120, 128))  # B and C: one row
