"""SURVEY.md §7 minimum end-to-end slice, realized: a real (tiny)
transformer train loop and a real KV-cache batch-inference loop
co-scheduled on one partition by the credit scheduler with the adaptive
feedback policy — the TPU re-expression of two co-located guests under
the PMU-feedback credit scheduler."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from pbs_tpu.models import flagship_config
from pbs_tpu.runtime import Job, Partition, SchedParams
from pbs_tpu.sched import FeedbackPolicy
from pbs_tpu.telemetry import Counter
from pbs_tpu.telemetry.source import TpuBackend
from pbs_tpu.utils.clock import MonotonicClock


@pytest.fixture(scope="module")
def tiny_world():
    from pbs_tpu.models import (
        init_params,
        make_serve_step,
        make_train_step,
    )

    cfg = flagship_config(tiny=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    init_opt, train_step = make_train_step(cfg, learning_rate=1e-3)
    serve_step = make_serve_step(cfg, max_new_tokens=4)
    return cfg, params, init_opt, train_step, serve_step


def test_train_and_serve_multiplexed_by_credit(tiny_world):
    cfg, params, init_opt, train_step, serve_step = tiny_world
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab, jnp.int32)
    prompts = jnp.zeros((2, 4), jnp.int32)

    be = TpuBackend(clock=MonotonicClock())
    part = Partition("colo", source=be, scheduler="credit")
    fb = FeedbackPolicy(part)

    jit_train = jax.jit(train_step)
    train_state = (params, jax.jit(init_opt)(params), 0)
    train = part.add_job(Job(
        "train",
        step_fn=lambda s: jit_train(s, tokens),
        state=train_state,
        params=SchedParams(weight=512, boost_on_wake=False),
        max_steps=40,
    ))

    jit_serve = jax.jit(serve_step)
    serve = part.add_job(Job(
        "serve",
        step_fn=lambda s: jit_serve(s, prompts),
        state=(params, jax.random.PRNGKey(0), 0),
        params=SchedParams(weight=256, boost_on_wake=True),
        max_steps=40,
    ))

    part.run(max_rounds=400)

    # both tenants made real progress on real compiled steps
    assert train.steps_retired() == 40
    assert serve.steps_retired() == 40
    # training actually trained (step counter advanced in state)
    assert int(train.state[2]) == 40
    # serving actually served (requests counter advanced)
    assert int(serve.state[2]) == 40
    # telemetry flowed: device time attributed per tenant, tokens counted
    t_dev = int(train.contexts[0].counters[Counter.DEVICE_TIME_NS])
    s_dev = int(serve.contexts[0].counters[Counter.DEVICE_TIME_NS])
    assert t_dev > 0 and s_dev > 0
    assert int(train.contexts[0].counters[Counter.TOKENS]) == 40 * 2 * 31
    assert int(serve.contexts[0].counters[Counter.TOKENS]) == 40 * 2 * 4
    # the feedback policy observed both tenants
    names = {row["job"] for row in fb.dump()}
    assert names == {"train", "serve"}


def test_speculative_engine_as_scheduled_tenant(tiny_world):
    """The full serving stack as a scheduler tenant: a SpeculativeBatcher
    wrapped by make_continuous_serve_step co-scheduled against a real
    train loop — engine ticks are the BOOSTed tenant's quanta, spec
    throughput lands in the TOKENS ledger."""
    from pbs_tpu.models import (
        SpeculativeBatcher,
        make_continuous_serve_step,
    )

    cfg, params, init_opt, train_step, _ = tiny_world
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab, jnp.int32)

    be = TpuBackend(clock=MonotonicClock())
    part = Partition("colo-spec", source=be, scheduler="credit")

    jit_train = jax.jit(train_step)
    train = part.add_job(Job(
        "train",
        step_fn=lambda s: jit_train(s, tokens),
        state=(params, jax.jit(init_opt)(params), 0),
        params=SchedParams(weight=512, boost_on_wake=False),
        max_steps=25,
    ))

    eng = SpeculativeBatcher(cfg, params, cfg, params, k=3, n_slots=2,
                             prompt_bucket=8, max_len=64)
    reqs = iter([([1, 2, 3], 6), ([4, 5], 6), ([6, 7, 8], 6)])

    def feed(step):
        try:
            return [next(reqs)]
        except StopIteration:
            return []

    serve_step = make_continuous_serve_step(eng, next_requests=feed)
    serve = part.add_job(Job(
        "svc",
        step_fn=serve_step,
        state={"step": 0, "completed": 0},
        params=SchedParams(weight=256, boost_on_wake=True),
        max_steps=25,
    ))

    part.run(max_rounds=400)
    assert train.steps_retired() == 25
    assert eng.stats()["completed"] == 3
    assert eng.stats()["spec_acceptance"] == 1.0  # self-draft
    # Spec throughput is exact goodput in the tenant's TOKENS ledger.
    assert int(serve.contexts[0].counters[Counter.TOKENS]) == \
        eng.stats()["tokens_emitted"]
