"""Build each tenant the way ``chip_smoke.py`` does, at a cell's sizes.

The recipe is the program's (``Job`` -> ``Partition("credit")`` +
``FeedbackPolicy`` over a ``TpuBackend``; ``Gateway`` ->
``ShardedServeBackend`` -> ``ContinuousBatcher``); the sizes come from
the configuration file; what is particular to a model (its program
configuration, step factory, serving backend and reference) comes from
the configuration's family (``benchmarks/families/``); the weights come
from ``--seed`` through the reference's weight definition, made on the
device in one jitted call in the type they are held in (the trainer's
float32 masters, the server's bfloat16).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from pbs_tpu.gateway import Gateway, TenantQuota
from pbs_tpu.models import make_continuous_serve_step
from pbs_tpu.runtime import Job, Partition, SchedParams
from pbs_tpu.sched import FeedbackPolicy
from pbs_tpu.telemetry.source import TpuBackend

from .engine import Book, StampingBatcher


class TsliceLog:
    """Partition recorder: each tenant's ``tslice_us`` as the feedback
    policy leaves it, tick by tick, and steps per quantum."""

    def __init__(self):
        self.by_job: dict[str, list[int]] = {}
        self.quanta: dict[str, list[int]] = {}

    def on_quantum(self, _lane, ctx, _quantum_ns, n_units, *_rest) -> None:
        q = self.quanta.setdefault(ctx.job.name, [0, 0])
        q[0] += 1
        q[1] += int(n_units)

    def on_feedback(self, _now_ns, job, _state) -> None:
        self.by_job.setdefault(job.name, []).append(job.params.tslice_us)

    def settled(self, names, quiet_ticks: int) -> bool:
        """No tenant's slice changed over its last ``quiet_ticks``
        policy ticks."""
        for name in names:
            tail = self.by_job.get(name, [])[-quiet_ticks:]
            if len(tail) < quiet_ticks or len(set(tail)) != 1:
                return False
        return True


def make_partition(name: str):
    be = TpuBackend()
    # A host span around each quantum (dispatch, wait, accounting), so
    # that idle time outside it is the scheduler loop's own.
    execute = be.execute

    def quantum(ctx, n_steps):
        with jax.profiler.TraceAnnotation("bench.quantum"):
            return execute(ctx, n_steps)

    be.execute = quantum
    part = Partition(name, source=be, scheduler="credit")
    fb = FeedbackPolicy(part)
    part.recorder = TsliceLog()
    return be, part, fb


class Trainer:
    """The train tenant: a donated jitted step over float32 masters and
    AdamW, fed a fresh seeded row each step."""

    def __init__(self, fam, c: dict, seed: int, rows, be: TpuBackend):
        tr, ref = c["train"], fam.reference
        self.cfg = fam.program_config(
            c, tr["num_hidden_layers"], tr["seq"], remat=tr["remat"],
            remat_policy=tr.get("remat_policy", "full"))
        self.lr = float(tr["learning_rate"])
        self.rows = rows
        self.steps = 0
        self.step_times: list[float] = []  # when each step was dispatched
        self.tokens_per_step = rows.shape[1] * (rows.shape[2] - 1)
        self.first_losses: list = []
        init_opt, train_step = fam.train_step(self.cfg, self.lr)
        self.step = jax.jit(train_step, donate_argnums=(0,))
        params = jax.jit(lambda s: ref.init_tree(
            c, s, tr["num_hidden_layers"], jnp.float32))(ref.seed_word(seed))
        state = (params, jax.jit(init_opt)(params), 0)
        with be.compile_meter.attribute("train"):
            compiled = self.step.lower(state, rows[0]).compile()
        self.job = Job("train", step_fn=self._step_fn, state=state,
                       compiled=compiled,
                       params=SchedParams(weight=int(tr["weight"]),
                                          boost_on_wake=False))

    def _step_fn(self, st):
        row = self.rows[self.steps % len(self.rows)]
        with jax.profiler.TraceAnnotation("bench.train_step"):
            st, m = self.step(st, row)
        self.steps += 1
        self.step_times.append(time.monotonic())
        if len(self.first_losses) < 3:
            self.first_losses.append(m["loss"])
        return st, {"tokens": m["tokens"]}


class Server:
    """The serve tenant: the family's gateway backend over the stamping
    engine, with the benchmark's book attached."""

    def __init__(self, fam, c: dict, seed: int):
        sv = c["serve"]
        self.cfg = fam.program_config(c, sv["num_hidden_layers"],
                                      sv["max_len"])
        self.backend = fam.serve_backend("engine", self.cfg, c, seed,
                                         engine_cls=StampingBatcher)
        self.engine = self.backend.engine
        self.book = self.engine.book = Book()

    def gateway(self) -> Gateway:
        big = 1 << 20  # the traffic is sized so that nothing is shed
        return Gateway(
            [self.backend], max_queued=big,
            quotas={"bench": TenantQuota(rate=1e9, burst=1e9,
                                         slo="interactive",
                                         max_queued=big)})

    def job(self, feed, weight: int) -> Job:
        return Job(
            "serve",
            step_fn=make_continuous_serve_step(self.engine,
                                               next_requests=feed),
            state={"step": 0, "completed": 0},
            params=SchedParams(weight=weight, boost_on_wake=True))
