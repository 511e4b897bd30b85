"""``ShardedServeBackend``: a real partitioned transformer behind the
gateway's duck-typed :class:`~pbs_tpu.gateway.backends.Backend` surface.

This is ROADMAP item 1's payload: the admission/fairness/journal/span
stack has only ever fronted ``SimServeBackend`` or a hand-built
engine; this backend owns the whole serving bring-up — rule-table
parameter partitioning (serve/partition.py), mesh construction, the
:class:`~pbs_tpu.models.serving.ContinuousBatcher` slot engine — and
exposes it as just another backend, journal- and SLO-visible like the
sims. Per-stage span coverage rides the ``exec_hook`` seam: one EXEC
record when the prompt enters the prefill pipeline (the inherited
``BatcherBackend`` wiring), one when the request wins a decode slot,
one at retirement — fired from the engine's own admission and
retirement (its ``admit_hook``/``retire_hook``), at the moment they
happen. Repeated EXECs while inflight are legal span transitions
(obs/spans._NEXT_STATE), so a request's timeline shows where inside
the backend its time went; each EXEC carries the engine's request id,
which the engine's own ``ENG_ADMIT``/``ENG_PREFILL``/``ENG_RETIRE``
records carry too (docs/TRACING.md).

Two clock modes: ``clock="wall"`` (default) for real benchmarks;
``clock="virtual"`` slaves the engine's latency accounting to the
``now_ns`` the harness passes into ``dispatch_request``/``poll``, so
chaos runs are deterministic and same-seed-same-digest holds with a
real model in the loop.

Catalog requests (``{"tick": ...}`` payloads with a cost attribute)
are served too: a deterministic prompt is synthesized from the request
id and ``max_new`` tokens from its cost, so one decode token per
gateway tick keeps service time cost-proportional — the same shape the
sim backends present to the fairness machinery.
"""

from __future__ import annotations

import zlib

from pbs_tpu.gateway.backends import BatcherBackend
from pbs_tpu.gateway.fairqueue import Request
from pbs_tpu import knobs
from pbs_tpu.utils.clock import MonotonicClock

#: Default decode-slot count (declared knob; the autopilot can canary
#: it like any scheduler knob).
DECODE_SLOTS = knobs.default("serve.backend.decode_slots")


def synth_payload(req: Request, bucket: int, max_len: int,
                  vocab: int) -> tuple[list, int]:
    """Deterministic (prompt, max_new) for a catalog request. Prompt
    tokens derive from crc32 of the rid (str hashing is salted per
    process — the injector's rule), max_new from the request cost so a
    cost-8 batch job holds its slot ~8 engine ticks, mirroring the
    sim's cost-proportional service times."""
    h = zlib.crc32(req.rid.encode())
    plen = 1 + h % max(1, min(int(bucket), 8))
    prompt = [1 + (h >> (i % 24)) % (vocab - 1) for i in range(plen)]
    budget = max(1, int(max_len) - int(bucket) - 1)
    max_new = max(1, min(int(req.cost), budget))
    return prompt, max_new


class ShardedServeBackend(BatcherBackend):
    """Rule-partitioned serving engine as a gateway backend.

    Who places what: the weights go onto a ``(dp, tp)`` mesh by the
    serve rule table, once, through ``serve.partition.place`` (a tree
    its maker laid out under ``rule_shardings`` is already there, and
    the call hands each leaf back as it came); the slot engine takes
    them as handed and places its own cache on the same mesh
    (``mesh=``). 1x1 on a one-chip box: the same code path, the
    collectives no-ops.
    """

    def __init__(self, name: str, cfg, params=None, *, tp: int = 1,
                 dp: int = 1, n_slots: int | None = None,
                 prompt_bucket: int = 16, max_len: int | None = None,
                 seed: int = 0, clock: str = "wall",
                 prefix_cache_size: int = 0, engine_cls=None):
        import jax

        from pbs_tpu.models.serving import ContinuousBatcher
        from pbs_tpu.obs.trace import host_phase
        from pbs_tpu.serve.partition import (
            make_serve_mesh, make_shard_and_gather_fns,
        )

        if clock not in ("wall", "virtual"):
            raise ValueError(f"clock must be 'wall' or 'virtual', "
                             f"got {clock!r}")
        if params is None:
            from pbs_tpu.models.slot_programs import slot_program

            params = slot_program(cfg).init_params(jax.random.PRNGKey(seed))
        self.cfg = cfg
        self.mesh = make_serve_mesh(tp=tp, dp=dp)
        # Rule-table placement first (hard error on an uncovered
        # leaf), THEN the engine: a tree the table cannot place never
        # reaches a compile.
        shard_fn, self._gather_fn = make_shard_and_gather_fns(self.mesh)
        with host_phase("serve.place", sum(
                x.nbytes for x in jax.tree.leaves(params))):
            params = jax.block_until_ready(shard_fn(params))
        self._virtual = clock == "virtual"
        self._now_ns = 0
        self._wall = MonotonicClock()  # clock="wall": the gateway's own
        engine_cls = engine_cls or ContinuousBatcher
        engine = engine_cls(
            cfg, params,
            n_slots=int(n_slots if n_slots is not None else DECODE_SLOTS),
            prompt_bucket=prompt_bucket, max_len=max_len, seed=seed,
            mesh=self.mesh, prefix_cache_size=prefix_cache_size,
            clock=(lambda: self._now_ns * 1e-9) if self._virtual
            else None)
        super().__init__(name, engine)
        engine.admit_hook = engine.retire_hook = self._slot_event
        self.synth_dispatches = 0
        self.disagg_stages = ("prefill", "decode", "retire")

    # -- clock + payload seams -------------------------------------------

    def _observe(self, now_ns: int) -> None:
        if self._virtual and now_ns > self._now_ns:
            self._now_ns = int(now_ns)

    def dispatch_request(self, req: Request, now_ns: int) -> None:
        self._observe(now_ns)
        if "prompt" not in req.payload:
            prompt, max_new = synth_payload(
                req, self.engine.bucket, self.engine.max_len,
                self.cfg.vocab)
            req.payload = dict(req.payload,
                               prompt=prompt, max_new=max_new)
            self.synth_dispatches += 1
        super().dispatch_request(req, now_ns)

    def poll(self, now_ns: int):
        self._observe(now_ns)
        return super().poll(now_ns)

    def _slot_event(self, engine_rid: int, _slot: int) -> None:
        """The engine gave a request a decode slot, or retired it: one
        more EXEC on the request's chain, stamped when it happened
        (virtual clock: the tick's ``now_ns``)."""
        if self.exec_hook is None:
            return
        req = self._by_engine_rid.get(engine_rid)
        if req is not None:
            self.exec_hook(req, self._now_ns if self._virtual
                           else self._wall.now_ns())

    # -- observability ----------------------------------------------------

    def gather_params(self) -> dict:
        """Fully-replicated (host-readable) param tree — the
        checkpoint-save path, and the roundtrip identity surface
        tests/test_serve.py pins byte-for-byte."""
        return self._gather_fn(self.engine.params)

    def stats(self) -> dict:
        """Engine SLO stats + the placement facts a fleet dashboard
        needs to tell two serve backends apart."""
        import jax
        import numpy as np

        leaves = jax.tree_util.tree_leaves(self.engine.params)
        return {
            **self.engine.stats(),
            "backend": self.name,
            "mesh": {a: int(s) for a, s in
                     zip(self.mesh.axis_names, self.mesh.devices.shape)},
            "param_leaves": len(leaves),
            "param_bytes": int(sum(
                np.prod(x.shape) * x.dtype.itemsize for x in leaves)),
            "synth_dispatches": self.synth_dispatches,
            "bypass_submits": self.bypass_submits,
        }
