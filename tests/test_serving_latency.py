"""The research claim, end to end: sub-step latency bounding protects
serving TTFT under co-tenancy.

This is the user-visible form of the reference's 100 µs slice
(sched_credit.c:52): a batch tenant with LONG compiled steps shares
the lane with a continuous-batching serving tenant. Monolithic batch
steps floor the quantum at a full step, so requests arriving mid-
quantum wait out the whole thing; micro-stepped batch steps
(micro_per_step + make-micro-style chunks) give the scheduler
sub-step boundaries, and serving TTFT drops accordingly.

Two forms:

- **Deterministic (default)**: the engine's latency stats run on an
  injected virtual clock, so TTFT/latency percentiles are *exact*
  scripted numbers — no load-dependent margins (the SimBackend peer of
  this pin, wake-to-dispatch p99, lives in ``test_microstep.py``).
- **Wall-clock (opt-in, ``PBST_WALLCLOCK_TESTS=1``)**: the original
  end-to-end co-tenancy run with a coarse 2x margin — real jit work,
  real scheduler, machine-load sensitive by nature."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import ContinuousBatcher, TransformerConfig, init_params
from pbs_tpu.runtime import Job, Partition, SchedParams
from pbs_tpu.telemetry.source import TpuBackend

TINY = dict(vocab=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq=64, dtype=jnp.float32)


def _slow_chunk(ms_per_chunk=25, n=320):
    """A compiled chunk taking ~ms_per_chunk on CPU."""

    @jax.jit
    def chunk(x):
        for _ in range(24):
            x = jnp.tanh(x @ x / n) + 0.01
        return x

    x0 = jnp.ones((n, n), jnp.float32)
    chunk(x0).block_until_ready()
    # calibrate repetitions inside the host fn to land near the target
    t0 = time.perf_counter()
    chunk(x0).block_until_ready()
    per = (time.perf_counter() - t0) * 1e3
    reps = max(1, int(ms_per_chunk / max(per, 0.1)))
    return chunk, x0, reps


def _ttft_under_cotenancy(micro: bool, n_requests=6) -> float:
    cfg = TransformerConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32)

    chunk, x0, reps = _slow_chunk()
    K = 8  # micro chunks per batch step

    def mono_step(x):  # one LONG monolithic step (~K chunks long)
        for _ in range(K * reps):
            x = chunk(x)
        return x

    def micro_chunk(x):  # one chunk = 1/K of the step
        for _ in range(reps):
            x = chunk(x)
        return x

    def serve_step(st):
        eng.step()
        return st + 1

    part = Partition("p", source=TpuBackend())
    if micro:
        part.add_job(Job("batch", micro_step_fn=micro_chunk,
                         micro_per_step=K, state=x0,
                         params=SchedParams(weight=256, tslice_us=100)))
    else:
        part.add_job(Job("batch", step_fn=mono_step, state=x0,
                         params=SchedParams(weight=256, tslice_us=100)))
    svc = part.add_job(Job("svc", step_fn=serve_step, state=0,
                           params=SchedParams(weight=256, tslice_us=100,
                                              boost_on_wake=True)))
    # warm both tenants (compile outside the measurement)
    part.run(max_rounds=4)

    for i in range(n_requests):
        # Pin the race deterministically: the request ARRIVES (submit
        # starts the TTFT clock) while the svc tenant is off the lane
        # and the batch tenant takes exactly one quantum. What that
        # quantum COSTS is the whole experiment: a monolithic step
        # floors it at the full step; micro-stepping floors it at one
        # chunk (the 100 µs slice analog).
        part.sleep_job(svc)
        eng.submit([1 + i, 2], max_new_tokens=2)
        part.run(max_rounds=1)  # batch tenant's quantum
        part.wake_job(svc)  # BOOST: svc served at the next boundary
        part.run(max_rounds=4)
    deadline = time.monotonic() + 60
    while eng.has_work() and time.monotonic() < deadline:
        part.run(max_rounds=4)
    st = eng.stats()
    assert st["completed"] >= n_requests - 1, st
    return st["ttft_p99_s"]


@pytest.mark.skipif(
    not os.environ.get("PBST_WALLCLOCK_TESTS"),
    reason="wall-clock timing on shared CI; opt in: PBST_WALLCLOCK_TESTS=1")
def test_microstepping_bounds_serving_ttft_wallclock():
    ttft_mono = _ttft_under_cotenancy(micro=False)
    ttft_micro = _ttft_under_cotenancy(micro=True)
    # monolithic: a request admitted after the batch quantum begins
    # waits out ~K chunks; micro-stepped: ~1 chunk. Coarse 2x margin
    # on an expected ~Kx effect keeps this robust on loaded CI.
    assert ttft_micro * 2 < ttft_mono, (ttft_micro, ttft_mono)


# ---------------------------------------------------------------------------
# Deterministic: engine latency stats on a virtual clock
# ---------------------------------------------------------------------------


def _engine_on_virtual_clock():
    cfg = TransformerConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    vt = [0.0]
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32, clock=lambda: vt[0])
    return eng, vt


def test_ttft_accounting_is_exact_on_virtual_clock():
    """Scripted arrival/step times produce EXACT percentile stats —
    the deterministic pin of the TTFT accounting path."""
    eng, vt = _engine_on_virtual_clock()
    # One step() = admit + prefill (token 1 from the prompt's last
    # logits) + one decode token enqueued, booked by the next step() —
    # so 3 tokens span three steps.
    r0 = eng.submit([1, 2, 3], max_new_tokens=3)
    vt[0] = 0.010
    eng.step()  # admits; token 1 at t=10ms (TTFT), token 2 enqueued
    vt[0] = 0.020
    assert eng.step() == []  # token 3 enqueued, token 2 booked
    vt[0] = 0.025
    done = list(eng.step())  # token 3 booked -> completion at t=25ms
    assert [c.request_id for c in done] == [r0]
    assert done[0].ttft_s == pytest.approx(0.010, abs=1e-9)
    assert done[0].latency_s == pytest.approx(0.025, abs=1e-9)
    st = eng.stats()
    assert st["ttft_p50_s"] == pytest.approx(0.010, abs=1e-6)
    assert st["ttft_p99_s"] == pytest.approx(0.010, abs=1e-6)


def test_ttft_is_scheduler_delay_plus_step_virtual():
    """The co-tenancy claim in its deterministic form: TTFT is exactly
    (time the engine waited for the lane) + (one step). A request that
    arrives while a monolithic batch quantum holds the lane for 500 ms
    of virtual time pays all of it; one that waits a 10 ms micro-chunk
    pays 10 ms. The K x gap is exact here — the wall-clock variant
    only demonstrates it survives reality."""
    # Monolithic co-tenant: lane busy 500 ms before the engine steps.
    eng, vt = _engine_on_virtual_clock()
    eng.submit([1, 2], max_new_tokens=1)
    vt[0] = 0.500
    done = list(eng.step())
    assert done[0].ttft_s == pytest.approx(0.500, abs=1e-9)
    mono_p99 = eng.stats()["ttft_p99_s"]

    # Micro-stepped co-tenant: lane frees at the 10 ms chunk boundary.
    eng2, vt2 = _engine_on_virtual_clock()
    eng2.submit([1, 2], max_new_tokens=1)
    vt2[0] = 0.010
    done2 = list(eng2.step())
    assert done2[0].ttft_s == pytest.approx(0.010, abs=1e-9)
    assert eng2.stats()["ttft_p99_s"] * 50 == pytest.approx(
        mono_p99, rel=1e-6)
