"""The research story end to end: three tenants, one chip, measured
feedback scheduling.

    python examples/multi_tenant.py

A training tenant (long memory-bound steps), a latency-sensitive
serving tenant (BOOST on wake), and a *foreign* tenant — a plain
``jax.jit`` callable that knows nothing about the framework — share
one device under the adaptive credit scheduler. The feedback policy
reads each tenant's measured telemetry (XLA-profiler sampling for the
foreign one: the HVM vPMU analog) and adapts per-tenant quanta, the
PBS claim rebuilt TPU-first. Runs on JAX's default device: under a
minute with JAX_PLATFORMS=cpu, and on the chip where there is one.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pbs_tpu.models import TransformerConfig, init_params, make_train_step
from pbs_tpu.runtime import Job, Partition, SchedParams
from pbs_tpu.sched import FeedbackPolicy
from pbs_tpu.telemetry import Counter
from pbs_tpu.telemetry.source import TpuBackend
from pbs_tpu.utils.compile_cache import setup_compilation_cache

TINY = dict(vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq=64, dtype=jnp.float32)


def main() -> None:
    setup_compilation_cache()
    cfg = TransformerConfig(**TINY)
    key = jax.random.PRNGKey(0)

    # Tenant 1: training (the bulk workload).
    params = init_params(cfg, key)
    init_opt, train_step = make_train_step(cfg, learning_rate=1e-3)
    tokens = jax.random.randint(key, (4, 64), 0, cfg.vocab, jnp.int32)
    step = jax.jit(train_step)

    def train_fn(state):
        state, m = step(state, tokens)
        return state, {"tokens": m["tokens"]}

    train = Job("train", step_fn=train_fn,
                state=(params, jax.jit(init_opt)(params), 0),
                params=SchedParams(weight=512), max_steps=40)
    # Cooperative tenants can opt into measured telemetry too: every
    # 4th step runs under the XLA profiler.
    train.profile_every = 4

    # Tenant 2: latency-sensitive serving (BOOST on wake).
    gen_params = init_params(cfg, jax.random.PRNGKey(1))

    @jax.jit
    def tiny_serve(p, prompt):
        from pbs_tpu.models import forward

        return jnp.argmax(forward(cfg, p, prompt)[:, -1], axis=-1)

    prompt = jnp.ones((1, 8), jnp.int32)

    def serve_fn(served):
        tiny_serve(gen_params, prompt).block_until_ready()
        return served + 1

    serve = Job("serve", step_fn=serve_fn, state=0,
                params=SchedParams(weight=256, tslice_us=100,
                                   boost_on_wake=True), max_steps=30)

    # Tenant 3: a FOREIGN guest — any jitted callable, zero protocol.
    n = 192

    @jax.jit
    def guest_kernel(a, s):
        for _ in range(20):
            a = jnp.tanh(a) * s + 0.1
        return a

    guest = Job.foreign("guest", guest_kernel, jnp.ones((n, n)), 0.5,
                        profile_every=2, max_steps=30)

    be = TpuBackend(profile_every=0)  # only the per-job overrides sample
    part = Partition("demo", source=be)
    fb = FeedbackPolicy(part)  # default 1 ms metric tick
    for j in (train, serve, guest):
        part.add_job(j)
    part.run()

    print(f"{'tenant':<8} {'steps':>5} {'device_ms':>10} "
          f"{'stall_rate':>10} {'tslice_us':>9}")
    for j in (train, serve, guest):
        dev_ms = sum(int(c.counters[Counter.DEVICE_TIME_NS])
                     for c in j.contexts) / 1e6
        print(f"{j.name:<8} {j.steps_retired():>5} {dev_ms:>10.1f} "
              f"{j.stall_rate:>10.1f} {j.params.tslice_us:>9}")
    m = be.measured("guest")
    if m is not None:
        print(f"\nforeign tenant measured WITHOUT cooperation: "
              f"{m.n_ops} ops sampled, stall_frac={m.stall_frac:.2f} "
              f"(source={m.source})")
    print("feedback ticks:", fb.state_of(guest).ticks)


if __name__ == "__main__":
    main()
