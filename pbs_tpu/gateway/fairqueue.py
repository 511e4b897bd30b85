"""Weighted deficit-round-robin fair queue with SLO classes.

Admission decides *whether* a request enters the gateway; this queue
decides *in what order* admitted requests reach a backend — the same
separation the scheduler proper makes between job admission and the
runqueue. Two levels:

- **Class level** — a fixed dispatch cycle over the SLO classes
  (default 4 interactive slots to 1 batch slot, work-conserving: an
  empty class donates its slot). Interactive traffic therefore owns a
  guaranteed majority of dispatch opportunities — a flooding batch
  tenant CANNOT starve interactive TTFT — while batch keeps a floor
  share and is never starved either.
- **Tenant level (within a class)** — classic deficit round robin
  (Shreedhar & Varghese) over per-tenant FIFOs: each visit tops the
  tenant's deficit up by a quantum scaled by its weight
  (``quantum * weight / 256``, the SchedParams scale), and the tenant
  dispatches while its deficit covers the head request's ``cost``.
  Cost-aware: a tenant submitting few huge requests and one submitting
  many small ones get the same long-run cost share per weight.

Requeue (backend loss) goes to the *front* of the tenant FIFO with the
deficit topped up to cover it: re-dispatching a casualty must not charge
the tenant a second time or put it behind its own later arrivals.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any

from pbs_tpu import knobs
from pbs_tpu.gateway.admission import BATCH, INTERACTIVE, SLO_CLASSES

#: Class dispatch cycle: interactive-heavy, batch floor-share. The
#: 4:1 shape is declared per class in the knob registry
#: (gateway.fairqueue.interactive_slots / batch_slots).
DEFAULT_CLASS_CYCLE = (
    (INTERACTIVE,) * knobs.default("gateway.fairqueue.interactive_slots")
    + (BATCH,) * knobs.default("gateway.fairqueue.batch_slots"))
#: Deficit top-up per DRR visit at weight 256, in cost units.
DEFAULT_QUANTUM = knobs.default("gateway.fairqueue.drr_quantum")


@dataclasses.dataclass
class Request:
    """One admitted request moving through the gateway."""

    rid: str
    tenant: str
    slo: str
    cost: int
    payload: Any
    submit_ns: int
    #: Phantom delay charged by an injected ``gateway.admit``/``delay``
    #: fault — added to the measured queue delay at dispatch.
    penalty_ns: int = 0
    dispatch_ns: int = -1
    queue_delay_ns: int = -1  # sealed at (last) dispatch
    backend: str | None = None
    requeues: int = 0
    #: Wait already pushed into the feedback channel for this request
    #: (sentinel exports while queued + dispatch-time settlement).
    #: Every report sends ``current wait - reported_wait_ns`` and
    #: advances this watermark, so a request's delay reaches the
    #: scheduler exactly once no matter how many feedback periods or
    #: requeues it lives through.
    reported_wait_ns: int = 0
    #: The id the backend's engine knows this request by, once it has
    #: reached one (``BatcherBackend``); -1 = none. SPAN_EXEC carries
    #: it, so the engine's own records join the request's chain by
    #: identifier (docs/TRACING.md).
    engine_rid: int = -1


class DeficitRoundRobin:
    """The two-level queue. Single-threaded by design: the gateway owns
    it and pumps it from one loop (no locks — nothing here is shared)."""

    def __init__(self, quantum: int = DEFAULT_QUANTUM,
                 class_cycle: tuple[str, ...] = DEFAULT_CLASS_CYCLE):
        if not class_cycle or set(class_cycle) - set(SLO_CLASSES):
            raise ValueError(f"class_cycle must draw from {SLO_CLASSES}")
        self.quantum = int(quantum)
        self._cycle = tuple(class_cycle)
        self._cursor = 0  # position in the class cycle
        # Per class: tenant -> FIFO, tenant -> deficit, visit ring.
        self._fifos: dict[str, dict[str, deque[Request]]] = {
            c: {} for c in SLO_CLASSES}
        self._deficit: dict[str, dict[str, float]] = {
            c: {} for c in SLO_CLASSES}
        self._ring: dict[str, deque[str]] = {c: deque() for c in SLO_CLASSES}
        self._weights: dict[str, int] = {}
        self._depth = 0
        #: DRR deficit the last :meth:`pop`'d tenant had LEFT after
        #: paying for the dispatched request — the span layer attaches
        #: it to SPAN_DISPATCH so a timeline shows how much credit the
        #: tenant dispatched on (docs/TRACING.md).
        self.last_deficit = 0.0

    # -- intake ----------------------------------------------------------

    def set_weight(self, tenant: str, weight: int) -> None:
        self._weights[tenant] = max(1, int(weight))

    def _activate(self, cls: str, tenant: str, front: bool) -> deque:
        fifo = self._fifos[cls].get(tenant)
        if fifo is None:
            fifo = self._fifos[cls][tenant] = deque()
        if not fifo and tenant not in self._ring[cls]:
            if front:
                self._ring[cls].appendleft(tenant)
            else:
                self._ring[cls].append(tenant)
            self._deficit[cls].setdefault(tenant, 0.0)
        return fifo

    def push(self, req: Request) -> None:
        self._activate(req.slo, req.tenant, front=False).append(req)
        self._depth += 1

    def requeue_front(self, req: Request) -> None:
        """Re-admit a casualty of backend loss at the head of its
        tenant's FIFO, deficit topped up to cover it — requeue is a
        gateway failure being repaired, never a second charge."""
        fifo = self._activate(req.slo, req.tenant, front=True)
        fifo.appendleft(req)
        d = self._deficit[req.slo]
        d[req.tenant] = max(d.get(req.tenant, 0.0), float(req.cost))
        self._depth += 1

    # -- federation handoff (docs/GATEWAY.md "Federation") ---------------

    def take_tenant(self, cls: str, tenant: str
                    ) -> tuple[list[Request], float]:
        """Remove and return a tenant's queued FIFO and its carried DRR
        deficit — the handoff payload a draining or dead gateway hands
        to the federation. The requests keep their FIFO order and the
        deficit travels with them, so the tenant resumes its dispatch
        cycle at the adopting gateway instead of restarting with fresh
        credit (or, worse, forfeiting credit it had already earned)."""
        fifo = self._fifos[cls].pop(tenant, None)
        reqs = list(fifo) if fifo else []
        self._depth -= len(reqs)
        deficit = self._deficit[cls].pop(tenant, 0.0)
        try:
            self._ring[cls].remove(tenant)
        except ValueError:
            pass  # tenant had nothing queued here
        return reqs, deficit

    def restore_tenant(self, cls: str, tenant: str,
                       requests: list[Request],
                       deficit: float = 0.0) -> None:
        """Inverse of :meth:`take_tenant` at the adopting gateway:
        requests enter at the FRONT in their original order (they are
        casualties of a gateway drain/death being repaired, not new
        arrivals) and the carried deficit merges with any local credit
        (max, never sum — a handoff must not double a tenant's
        credit)."""
        if not requests:
            return
        fifo = self._activate(cls, tenant, front=True)
        for r in reversed(requests):
            fifo.appendleft(r)
        self._depth += len(requests)
        d = self._deficit[cls]
        d[tenant] = max(d.get(tenant, 0.0), float(deficit))

    def tenants(self, cls: str) -> list[str]:
        """Tenants with queued requests in ``cls``, sorted (the
        deterministic iteration order handoff loops rely on)."""
        return sorted(t for t, f in self._fifos[cls].items() if f)

    def pending(self, cls: str | None = None):
        """Iterate every queued request (one class, or all), in
        deterministic (class, tenant, FIFO) order. Read-only observer
        surface: the autopilot canary guard ages stuck requests
        against their SLO target with it (docs/AUTOPILOT.md)."""
        for c in ((cls,) if cls is not None else SLO_CLASSES):
            fifos = self._fifos[c]
            for tenant in sorted(fifos):
                yield from fifos[tenant]

    # -- dispatch order --------------------------------------------------

    def _quantum_for(self, tenant: str) -> float:
        return self.quantum * self._weights.get(tenant, 256) / 256.0

    def _pop_class(self, cls: str) -> Request | None:
        ring = self._ring[cls]
        fifos = self._fifos[cls]
        deficit = self._deficit[cls]
        # Bounded scan: each full ring rotation tops every active
        # tenant up by >= its quantum, so at most ceil(max_cost /
        # min_quantum) rotations are needed; cap defensively anyway.
        for _ in range(64 * (len(ring) + 1)):
            if not ring:
                return None
            tenant = ring[0]
            fifo = fifos.get(tenant)
            if not fifo:
                ring.popleft()  # drained tenant leaves the ring
                deficit.pop(tenant, None)
                continue
            head = fifo[0]
            if deficit.get(tenant, 0.0) >= head.cost:
                deficit[tenant] -= head.cost
                self.last_deficit = deficit[tenant]
                self._depth -= 1
                req = fifo.popleft()
                if not fifo:  # retire promptly; reset carried deficit
                    ring.popleft()
                    deficit.pop(tenant, None)
                return req
            deficit[tenant] = deficit.get(tenant, 0.0) + \
                self._quantum_for(tenant)
            ring.rotate(-1)  # next tenant; this one waits for its turn
        # Pathological cost/weight ratio exhausted the scan cap: serve
        # the current head anyway — bounded dispatch latency beats
        # perfect fairness on a degenerate configuration.
        tenant = ring[0]
        fifo = fifos.get(tenant)
        if not fifo:
            return None
        deficit[tenant] = 0.0
        self.last_deficit = 0.0
        self._depth -= 1
        req = fifo.popleft()
        if not fifo:
            ring.popleft()
            deficit.pop(tenant, None)
        return req

    def pop(self) -> Request | None:
        """Next request to dispatch, honoring the class cycle then DRR.
        Work-conserving: a class with nothing queued donates its slot."""
        if self._depth == 0:
            return None
        for i in range(len(self._cycle)):
            cls = self._cycle[(self._cursor + i) % len(self._cycle)]
            req = self._pop_class(cls)
            if req is not None:
                self._cursor = (self._cursor + i + 1) % len(self._cycle)
                return req
        return None

    # -- observability ---------------------------------------------------

    def depth(self, cls: str | None = None, tenant: str | None = None) -> int:
        if cls is None:
            return self._depth
        fifos = self._fifos[cls]
        if tenant is not None:
            return len(fifos.get(tenant, ()))
        return sum(len(f) for f in fifos.values())

    def oldest(self, cls: str) -> Request | None:
        """The longest-waiting queued request of ``cls`` (the gateway's
        stuck-queue sentinel; it mutates the request's feedback
        watermark, hence the full object and not just its age)."""
        oldest = None
        for fifo in self._fifos[cls].values():
            for r in fifo:
                if oldest is None or r.submit_ns < oldest.submit_ns:
                    oldest = r
        return oldest

    def pending(self) -> list[Request]:
        """Every queued request (accounting/invariant checks)."""
        out: list[Request] = []
        for cls in SLO_CLASSES:
            for fifo in self._fifos[cls].values():
                out.extend(fifo)
        return out
