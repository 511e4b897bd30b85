"""The three ways a cell's tenants share the chip. A traffic file's
``tenancy`` field picks one; nothing here looks at a cell's name.

Each tenancy builds its tenants (``setup``), drives the measured window
(``window``) and can let go of everything it put on the device
(``free``), so that the reference check runs after the window in the
memory the program gave back.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import build
from .engine import now
from .traffic import Requests, arrivals, train_rows

LEDGER = ("DEVICE_TIME_NS", "TOKENS", "STEPS_RETIRED")


class Tenancy:
    """What every tenancy leaves behind for the metrics."""

    def __init__(self, family, config: dict, traffic: dict, seed: int,
                 seconds: float):
        self.fam = family
        self.c, self.t, self.seed = config, traffic, seed
        self.seconds = seconds
        self.gw = None
        self.trainer = self.server = self.part = None
        self.t0 = self.t1 = 0.0
        self.trace_t0 = self.trace_t1 = None
        self.train_steps_window = 0
        self.ledger_trace: dict[str, dict[str, int]] = {}
        self.settle: dict = {}
        self.first_steps: dict | None = None
        self.phases: list[tuple[str, float]] = []
        self._mark_t = now()

    def mark(self, name: str) -> None:
        """Seconds since the last mark, for the set-up's own breakdown."""
        t = now()
        self.phases.append((name, round(t - self._mark_t, 2)))
        self._mark_t = t

    # -- pieces ------------------------------------------------------------

    def _ledger(self) -> dict[str, dict[str, int]]:
        from pbs_tpu.telemetry import Counter

        out = {}
        for job in self.part.jobs if self.part else ():
            sums = self.part.ledger.snapshot(job.contexts[0].ledger_slot)
            out[job.name] = {n: int(sums[Counter[n]]) for n in LEDGER}
        return out

    def window(self, seconds: float, tracer) -> None:
        """Drive ``part.run`` for ``seconds``; with a tracer, the last
        ``trace_s`` of it under the profiler."""
        clock = self.part.clock
        steps0 = self.trainer.steps
        self.t0 = now()
        end_ns = clock.now_ns() + int(seconds * 1e9)
        if tracer is not None:
            self.part.run(until_ns=end_ns - int(tracer.seconds * 1e9))
            tracer.start()
            before, self.trace_t0 = self._ledger(), now()
        self.part.run(until_ns=end_ns)
        self.t1 = now()
        if tracer is not None:
            self.trace_t1 = self.t1
            after = self._ledger()
            self.ledger_trace = {
                j: {k: after[j][k] - before[j][k] for k in LEDGER}
                for j in after}
            tracer.stop()
        self.train_steps_window = self.trainer.steps - steps0
        for job in self.part.jobs:
            if job.error is not None:
                raise RuntimeError(f"job {job.name} failed: {job.error}")

    def _first_steps(self) -> None:
        """The trainer's first three steps, through the partition the
        window will drive, and what the check reads from them: each
        loss, the first gradient's per-leaf norm and sketch (Adam's
        first moment after one step is 0.1 g), the parameters' change
        after three."""
        import jax
        import jax.numpy as jnp

        ref = self.fam.reference
        tr, job = self.trainer, self.trainer.job
        c, seed = self.c, self.seed
        n_layers = c["train"]["num_hidden_layers"]

        def mu_of(opt_state):
            found = [s for s in jax.tree.leaves(
                opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                if hasattr(s, "mu")]
            return found[0].mu

        while tr.steps < 1:
            self.part.run(max_rounds=1)
        if tr.steps != 1:
            raise RuntimeError(f"first quantum ran {tr.steps} steps")
        def first_gradient(mu):
            g = jax.tree.map(lambda m: m / (1 - ref.B1), mu)
            return ref.leaf_norms(g), ref.sketch(g)

        grad, sketch = jax.jit(first_gradient)(mu_of(job.state[1]))
        while tr.steps < 3:
            self.part.run(max_rounds=1)
        dparam = jax.jit(lambda p, s: ref.leaf_norms(jax.tree.map(
            jnp.subtract, p, ref.init_tree(c, s, n_layers, jnp.float32))))(
                job.state[0], ref.seed_word(seed))
        self.first_steps = {
            "loss": [float(x) for x in tr.first_losses[:3]],
            "grad_norm": {k: float(v) for k, v in grad.items()},
            "grad_sketch": {k: jax.device_get(v) for k, v in sketch.items()},
            "dparam_norm": {k: float(v) for k, v in dparam.items()},
            "steps_taken": tr.steps,
            "rows": [np.array(tr.rows[i]) for i in range(3)],
        }

    def _make_trainer(self) -> None:
        rows = train_rows(self.c["train"], self.c["vocab_size"], self.seed)
        self.be, self.part, self.fb = build.make_partition("bench")
        self.mark("rows")
        self.trainer = build.Trainer(self.fam, self.c, self.seed, rows,
                                     self.be)
        self.mark("trainer")
        self.part.add_job(self.trainer.job)
        self._first_steps()
        self.mark("first_steps")

    def _settle(self, names) -> None:
        """Co-resident (or solo) warm-up that ends when the feedback
        policy has left every tenant's ``tslice_us`` alone for
        ``quiet_ticks`` of its ticks."""
        s = self.t["settle"]
        log, t_start = self.part.recorder, now()
        while True:
            self.part.run(until_ns=self.part.clock.now_ns() + int(0.25e9))
            waited = now() - t_start
            ok = log.settled(names, int(s["quiet_ticks"]))
            if (ok and waited >= s["min_s"]) or waited >= s["max_s"]:
                break
        self.settle = {
            "settled": ok, "seconds": waited,
            "tslice_us": {j.name: j.params.tslice_us
                          for j in self.part.jobs},
            "policy_ticks": {n: len(log.by_job.get(n, [])) for n in names},
            "steps_per_quantum": {
                n: q[1] / q[0] for n, q in log.quanta.items() if q[0]}}
        self.mark("settle")
        if not ok:
            raise RuntimeError(f"tslice_us did not settle: {self.settle}")

    def free(self) -> None:
        self.trainer = self.server = self.part = self.be = self.fb = None
        self.gw = None
        gc.collect()


class Train(Tenancy):
    def setup(self) -> None:
        self._make_trainer()
        self._settle(["train"])


class ClosedLoop:
    """``clients`` callers, each sending its next request when the last
    one completed, with no think time. They start one after another,
    evenly over the first ``ramp_s`` seconds (0: all at once): admitted
    in one tick, the callers' prefills put the serving
    tenant some hundreds of milliseconds into credit debt, the scheduler
    takes seconds to collect it, and the requests alive meanwhile are a
    slow mode of their own in the window's tail (PERF.md section 6,
    PR 27)."""

    def __init__(self, serve: dict, book, requests):
        self.book, self.requests = book, requests
        n, ramp = int(serve["clients"]), float(serve["ramp_s"])
        self.starts = [k * ramp / n for k in range(n)][::-1]
        self.t_start = None
        self.owed = self.seen = 0

    def feed(self, _tick: int):
        if self.t_start is None:
            self.t_start = now()
        while self.starts and self.starts[-1] <= now() - self.t_start:
            self.starts.pop()
            self.owed += 1
        self.owed += self.book.completions - self.seen
        self.seen = self.book.completions
        out = []
        for _ in range(self.owed):
            prompt, max_new = next(self.requests)
            self.book.expect(prompt, max_new, due=now())
            out.append((prompt, max_new))
        self.owed = 0
        return out


class Colo(Tenancy):
    def setup(self) -> None:
        self._make_trainer()
        self.server = build.Server(self.fam, self.c, self.seed)
        self.mark("server")
        loop = ClosedLoop(self.t["serve"], self.server.book, Requests(
            self.t["serve"], self.c["vocab_size"], self.seed))
        self.part.add_job(self.server.job(
            loop.feed, int(self.c["serve"]["weight"])))
        self._settle(["train", "serve"])


class Serve(Tenancy):
    """Open loop through the gateway; this process is the pump."""

    def setup(self) -> None:
        self.server = build.Server(self.fam, self.c, self.seed)
        self.mark("server")
        self.gw = self.server.gateway()
        self.requests = Requests(self.t["serve"], self.c["vocab_size"],
                                 self.seed)
        self.next = 0
        self.late_s: list[float] = []
        sv = self.t["serve"]
        self.t_origin = now()
        self.due = [self.t_origin + a for a in arrivals(
            sv, self.seed, float(sv["warmup_s"]) + self.seconds)]
        self.t0 = self.t_origin + float(sv["warmup_s"])
        self._pump(self.t0, float("inf"))
        self.mark("warmup")

    def _pump(self, until: float, submit_until: float) -> None:
        gw, book = self.gw, self.server.book
        while True:
            t = now()
            if t >= until:
                return
            while (self.next < len(self.due)
                   and self.due[self.next] <= min(t, submit_until)):
                due = self.due[self.next]
                self.next += 1
                prompt, max_new = next(self.requests)
                rec = book.expect(prompt, max_new, due=due)
                res = gw.submit("bench", {"prompt": prompt,
                                          "max_new": max_new})
                self.late_s.append(now() - due)
                rec["shed"] = not res.admitted
            if gw.busy():
                gw.tick()
            else:
                nxt = (self.due[self.next] if self.next < len(self.due)
                       else until)
                time.sleep(max(0.0, min(nxt, until) - now()))

    def window(self, seconds: float, tracer) -> None:
        sv = self.t["serve"]
        end = self.t0 + seconds
        depth0 = self.gw.queue.depth()
        if tracer is not None:
            self._pump(end - tracer.seconds, float("inf"))
            tracer.start()
            self.trace_t0 = now()
        self._pump(end, float("inf"))
        self.t1 = end
        self.backlog = (depth0, self.gw.queue.depth())
        if tracer is not None:
            self.trace_t1 = now()
            tracer.stop()
        # No more arrivals; keep ticking until every request that was
        # due in the window has its first token (or the drain times out).
        book, deadline = self.server.book, now() + float(sv["drain_s"])
        while now() < deadline and any(
                not r["stamps"] and not r["shed"] for r in book.requests
                if self.t0 <= r["due"] < self.t1):
            self._pump(min(deadline, now() + 0.05), end)


KINDS = {"train": Train, "colo": Colo, "serve": Serve}
