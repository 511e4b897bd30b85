"""pbs_tpu.obs.spans: request-span tracing + SLO observability.

Jax-free and virtual-time. The properties this subsystem exists for:
(1) the log2 histogram quantile is EXACTLY the nearest-rank sample's
bucket edge (pinned against utils.stats.nearest_rank, the repo's one
canonical percentile); (2) a request's span chain is gap-free through
admission, queueing, dispatch, execution, completion — and stays ONE
chain across federation custody transfers; (3) the assembler catches
every class of broken chain; (4) `pbst slo report` on a seeded demo is
byte-stable (the tier-1 golden smoke).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from pbs_tpu.gateway import (
    FederatedGateway,
    Gateway,
    SimServeBackend,
    TenantQuota,
)
from pbs_tpu.obs.spans import (
    HIST_BUCKETS,
    LatencyHistograms,
    SpanAssembler,
    SpanRecorder,
    bucket_edges,
    hist_bucket,
    hist_quantile,
)
from pbs_tpu.obs.trace import Ev
from pbs_tpu.utils.clock import MS, VirtualClock
from pbs_tpu.utils.stats import nearest_rank

# -- histograms ---------------------------------------------------------


def test_hist_bucket_edges_cover_and_monotone():
    edges = bucket_edges()
    assert len(edges) == HIST_BUCKETS
    assert all(edges[i] < edges[i + 1] for i in range(HIST_BUCKETS - 1))
    # Every value lands under (or at) its bucket's edge...
    for v in (0, 1, 8_191, 8_192, 1_000_000, 10**9, 10**12):
        b = hist_bucket(v)
        assert 0 <= b < HIST_BUCKETS
        if b < HIST_BUCKETS - 1:
            assert v <= edges[b]
    # ...and bucket assignment is monotone in the value.
    vals = [2**k for k in range(0, 45)]
    bs = [hist_bucket(v) for v in vals]
    assert bs == sorted(bs)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("q", [0.50, 0.95, 0.99])
def test_hist_quantile_pins_to_nearest_rank_bucket(seed, q):
    """THE estimator contract: hist_quantile returns exactly the
    bucket edge of the nearest-rank sample — the log2-resolution image
    of utils.stats.nearest_rank, never an interpolation."""
    rng = np.random.default_rng(seed)
    vals = [int(v) for v in rng.integers(1, 2 * 10**9, size=500)]
    counts = np.zeros(HIST_BUCKETS, dtype=np.int64)
    for v in vals:
        counts[hist_bucket(v)] += 1
    nr = nearest_rank(vals, q)
    assert hist_quantile(counts, q) == int(bucket_edges()[hist_bucket(nr)])
    # And the edge brackets the true sample within one log2 bucket.
    hq = hist_quantile(counts, q)
    assert nr <= hq < 2 * nr + 2


def test_hist_quantile_empty_is_zero():
    assert hist_quantile(np.zeros(HIST_BUCKETS, dtype=np.int64), 0.99) == 0


def test_latency_histograms_record_and_class_aggregate():
    h = LatencyHistograms(num_slots=32)
    for v in (1 * MS, 2 * MS, 4 * MS):
        h.record("a", "interactive", "queue", v)
    h.record("b", "interactive", "queue", 64 * MS)
    h.record("be:b0", "*", "service", 512 * MS)  # backend row
    # Per-tenant and class-aggregate views agree on totals; the
    # backend row never pollutes the class aggregate.
    assert int(h.counts("a", "interactive", "queue").sum()) == 3
    assert int(h.class_counts("interactive", "queue").sum()) == 4
    assert h.class_quantile("interactive", "queue", 0.99) >= 64 * MS
    assert h.quantile("be:b0", "*", "service", 0.5) >= 512 * MS


def test_latency_histograms_overflow_folds_into_class():
    h = LatencyHistograms(num_slots=2)
    for i in range(8):  # 8 tenants, 2 slots: most fold
        h.record(f"t{i}", "batch", "e2e", 1 * MS)
    # Nothing dropped: the class aggregate still counts every sample.
    assert int(h.class_counts("batch", "e2e").sum()) == 8


def test_latency_histograms_overflow_never_corrupts_allocated_rows():
    """The reserved overflow row: a brand-new (cls, stage) arriving
    after the ledger fills must land in the shared overflow slot, not
    in some other histogram's slot (which would poison its
    quantiles)."""
    h = LatencyHistograms(num_slots=3)  # 2 normal slots + overflow
    h.record("t0", "interactive", "e2e", 1 * MS)
    h.record("t1", "batch", "e2e", 1 * MS)
    # Full. A new (cls, stage) pair with no fold target:
    h.record("be:b0", "*", "service", 512 * MS)
    # The allocated histograms are untouched...
    assert h.class_quantile("interactive", "e2e", 0.99) < 4 * MS
    assert h.class_quantile("batch", "e2e", 0.99) < 4 * MS
    # ...and the overflow sample is still readable.
    assert h.quantile("be:b0", "*", "service", 0.5) >= 512 * MS


def test_span_recorder_intern_bound_forgets_oldest_keeps_newest():
    """The intern table follows the ring's rule: at the bound the
    OLDEST id goes (counted) and the new span is kept; ids are never
    recycled, and the assembler resolves the rest through rid_base."""
    rec = SpanRecorder(capacity=256, max_spans=2)
    _happy_chain(rec, "a")
    rec.admit(0, "b", "t", 0, 1, "gw")  # second rid: still fits
    rec.admit(0, "c", "t", 0, 1, "gw")  # third: "a" is forgotten
    rec.dispatch(1, "c", 0, 1, 0, "gw")
    rec.complete(2, "c", 0, 1, 2, "gw")
    assert rec.dropped_spans == 1 and rec.forgotten_spans == 1
    assert rec.rid_base == 1 and rec.rid_table() == ["b", "c"]
    asm = _asm(rec)
    assert set(asm.chains) == {"b", "c"}
    assert asm.unknown_spans == 5  # a's records: still in the ring
    # c's chain is whole; b's gap (never completed) is its own, and
    # the forgotten id's records are reported, not mis-attributed.
    assert all(p.startswith("span b") or "outside the rid table" in p
               for p in asm.validate(["b", "c"]))


def test_span_recorder_forgets_ids_whose_records_left_the_ring():
    """Unbounded run, bounded memory: once every record of a chain is
    more than the ring's capacity behind the head, its id is forgotten
    (not counted as dropped), so the table tracks the ring."""
    rec = SpanRecorder(capacity=64, batch_capacity=8)
    for i in range(200):
        _happy_chain(rec, f"r{i}")  # 5 records a chain
    rec.flush()
    assert rec.dropped_spans == 0
    assert rec.forgotten_spans >= 200 - 64 // 5 - 2
    assert len(rec.rid_table()) <= 64 // 5 + 2
    assert rec.ring.lost == 200 * 5 - 64  # newest 64 records kept
    asm = _asm(rec)
    assert asm.unknown_spans == 0  # every record in the ring resolves
    assert "r199" in asm.chains
    assert not [p for p in asm.validate() if p.startswith("span r199")]


def test_latency_histograms_file_backed_attach(tmp_path):
    path = str(tmp_path / "gw.hist")
    h = LatencyHistograms(num_slots=16, path=path)
    h.record("t", "interactive", "e2e", 5 * MS)
    h.record("t", "interactive", "e2e", 9 * MS)
    mon = LatencyHistograms.attach(path)
    assert int(mon.counts("t", "interactive", "e2e").sum()) == 2
    assert mon.class_quantile("interactive", "e2e", 0.99) >= 9 * MS


# -- recorder / assembler ----------------------------------------------


def _asm(rec: SpanRecorder) -> SpanAssembler:
    return SpanAssembler(rec.drain(), rec.rid_table(),
                         rec.member_table(), rec.tenant_table(),
                         rid_base=rec.rid_base)


def _happy_chain(rec: SpanRecorder, rid: str, t0: int = 0) -> None:
    rec.admit(t0, rid, "chat", 0, 1, "gw")
    rec.enqueue(t0, rid, "chat", 0, "gw")
    rec.dispatch(t0 + 5, rid, 0, 5, 1000, "gw")
    rec.exec(t0 + 6, rid, 0, "gw")
    rec.complete(t0 + 20, rid, 0, 14, 20, "gw")


def test_assembler_happy_chain_validates():
    rec = SpanRecorder(capacity=256)
    _happy_chain(rec, "gw-0")
    asm = _asm(rec)
    assert asm.validate(["gw-0"]) == []
    assert asm.summary() == {"chains": 1, "complete": 1,
                             "handoff_events": 0, "recover_events": 0,
                             "shed_events": 0}
    lat = asm.latencies()["gw-0"]
    assert lat == {"e2e_ns": 20, "queue_ns": 5, "service_ns": 14,
                   "requeues": 0, "handoffs": 0}


def test_assembler_catches_every_gap_class():
    rec = SpanRecorder(capacity=256)
    # missing-dispatch: complete while still queued.
    rec.admit(0, "r1", "t", 0, 1, "gw")
    rec.enqueue(0, "r1", "t", 0, "gw")
    rec.complete(9, "r1", 0, 5, 9, "gw")
    # starts mid-chain: no admit.
    rec.dispatch(1, "r2", 0, 1, 0, "gw")
    rec.complete(2, "r2", 0, 1, 2, "gw")
    # never terminates.
    rec.admit(0, "r3", "t", 0, 1, "gw")
    rec.enqueue(0, "r3", "t", 0, "gw")
    rec.dispatch(1, "r3", 0, 1, 0, "gw")
    # events after the terminal.
    _happy_chain(rec, "r4")
    rec.requeue(30, "r4", 0, "gw")
    # duplicate admit.
    rec.admit(0, "r5", "t", 0, 1, "gw")
    rec.admit(1, "r5", "t", 0, 1, "gw")
    asm = _asm(rec)
    problems = asm.validate(["r1", "r2", "r3", "r4", "r5", "r6"])
    text = "\n".join(problems)
    assert "r1: gap — SPAN_COMPLETE while queued" in text
    assert "r2: chain starts with SPAN_DISPATCH" in text
    assert "r3: 0 SPAN_COMPLETE" in text
    assert "r4: SPAN_REQUEUE after terminal" in text
    assert "r5: duplicate SPAN_ADMIT" in text
    assert "r6: admitted but no records" in text
    # A rid with records that was never admitted is also a problem.
    assert "records exist for a rid never admitted" in "\n".join(
        asm.validate(["r1"]))


def test_assembler_handoff_requeue_redispatch_is_gapless():
    rec = SpanRecorder(capacity=256)
    rec.admit(0, "x", "t", 0, 1, "gw0")
    rec.enqueue(0, "x", "t", 0, "gw0")
    rec.dispatch(2, "x", 0, 2, 0, "gw0")
    rec.handoff(3, "x", "gw0", "gw1")  # inflight casualty moves
    rec.requeue(3, "x", 0, "gw1")
    rec.dispatch(5, "x", 1, 5, 0, "gw1")
    rec.exec(5, "x", 1, "gw1")
    rec.complete(9, "x", 1, 4, 9, "gw1")
    asm = _asm(rec)
    assert asm.validate(["x"]) == []
    lat = asm.latencies()["x"]
    assert lat["handoffs"] == 1 and lat["requeues"] == 1


def test_recorder_shed_events_counted_not_chained():
    rec = SpanRecorder(capacity=64)
    rec.shed(0, "t", 0, 1, "gw")
    asm = _asm(rec)
    assert asm.summary()["shed_events"] == 1
    assert asm.chains == {}


def test_chrome_trace_spans_have_queue_and_service_slices():
    rec = SpanRecorder(capacity=256)
    _happy_chain(rec, "gw-7")
    doc = _asm(rec).chrome_trace()
    cats = [e["cat"] for e in doc["traceEvents"]]
    assert "span.queue" in cats and "span.service" in cats
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0.001 for e in x)


# -- gateway wiring -----------------------------------------------------


def _pump(gw, clock, ticks, tick_ns=1 * MS):
    done = []
    for _ in range(ticks):
        done += gw.tick()
        clock.advance(tick_ns)
    return done


def test_gateway_emits_gapless_chains_with_exec():
    clock = VirtualClock()
    be = SimServeBackend("b0", n_slots=1, service_ns_per_cost=2 * MS)
    gw = Gateway([be], clock=clock, trace_capacity=2048,
                 quotas={"t": TenantQuota(rate=1e6, burst=1e6,
                                          slo="interactive",
                                          max_queued=64)})
    rids = [gw.submit("t", None).rid for _ in range(4)]
    _pump(gw, clock, 40)
    assert gw.completed == 4
    asm = _asm(gw.spans)
    assert asm.validate(rids) == []
    # Execution attribution fired through the backend hook.
    evs = {ev for chain in asm.chains.values() for _, ev, *a in chain}
    assert Ev.SPAN_EXEC in evs
    # Queue-stage histogram got one sample per request.
    assert int(gw.hist.class_counts("interactive", "queue").sum()) == 4
    assert int(gw.hist.class_counts("interactive", "e2e").sum()) == 4


def test_gateway_backend_loss_chain_continues_through_requeue():
    clock = VirtualClock()
    b0 = SimServeBackend("b0", n_slots=2, service_ns_per_cost=5 * MS)
    b1 = SimServeBackend("b1", n_slots=2, service_ns_per_cost=5 * MS)
    gw = Gateway([b0, b1], clock=clock, trace_capacity=4096,
                 quotas={"t": TenantQuota(rate=1e6, burst=1e6,
                                          max_queued=64)})
    rids = [gw.submit("t", None).rid for _ in range(8)]
    _pump(gw, clock, 2)
    b0.fail()
    _pump(gw, clock, 200)
    assert gw.stats()["requeued"] > 0
    asm = _asm(gw.spans)
    assert asm.validate(rids) == []
    evs = {ev for chain in asm.chains.values() for _, ev, *a in chain}
    assert Ev.SPAN_REQUEUE in evs


def test_gateway_shed_lands_in_span_stream():
    clock = VirtualClock()
    gw = Gateway([SimServeBackend("b0")], clock=clock, trace_capacity=512,
                 quotas={"t": TenantQuota(rate=10.0, burst=1.0)})
    assert gw.submit("t", None).admitted
    assert not gw.submit("t", None).admitted  # quota shed
    asm = _asm(gw.spans)
    assert asm.summary()["shed_events"] == 1


def test_gateway_stats_reads_histograms():
    clock = VirtualClock()
    be = SimServeBackend("b0", n_slots=1, service_ns_per_cost=2 * MS,
                         jitter=0.0)
    gw = Gateway([be], clock=clock,
                 quotas={"t": TenantQuota(rate=1e6, burst=1e6,
                                          slo="interactive")})
    for _ in range(4):
        gw.submit("t", None)
    _pump(gw, clock, 40)
    st = gw.stats()
    cls = st["classes"]["interactive"]
    # Quantiles are log2 bucket edges from the histogram layer.
    assert cls["latency_p99_ns"] == gw.hist.class_quantile(
        "interactive", "e2e", 0.99) > 0
    assert st["backends"]["b0"]["service_p99_ns"] == gw.hist.quantile(
        "be:b0", "*", "service", 0.99) > 0


def test_gateway_publishes_backend_service_p99_to_controller():
    from pbs_tpu.dist.controller import AgentHandle, Controller

    clock = VirtualClock()
    ctl = Controller(clock=clock)
    h = AgentHandle("b0", client=None, probe=None)
    h.observed_ns = clock.now_ns()
    ctl.agents["b0"] = h
    be = SimServeBackend("b0", n_slots=2, service_ns_per_cost=1 * MS)
    gw = Gateway([be], clock=clock, controller=ctl,
                 quotas={"t": TenantQuota(rate=1e6, burst=1e6)},
                 feedback_period_ns=5 * MS)
    for _ in range(4):
        gw.submit("t", None)
    _pump(gw, clock, 40)
    health = ctl.backend_health()
    assert health["b0"]["service_p99_ns"] > 0
    assert health["b0"]["service_p99_ns"] == gw.hist.quantile(
        "be:b0", "*", "service", 0.99)


# -- federation stitching ----------------------------------------------


def test_federation_kill_stitches_one_chain_across_members():
    clock = VirtualClock()
    members = [
        Gateway([SimServeBackend(f"g{i}b0", n_slots=1,
                                 service_ns_per_cost=20 * MS)],
                clock=clock, name=f"gw{i}")
        for i in range(2)
    ]
    rec = SpanRecorder(capacity=4096)
    fed = FederatedGateway(members, clock=clock, spans=rec)
    fed.register_tenant("t", TenantQuota(rate=1e6, burst=1e6,
                                         max_queued=64))
    rids = []
    for _ in range(6):
        r = fed.submit("t", None)
        assert r.admitted
        rids.append(r.rid)
    fed.tick()  # dispatch some inflight at the home member
    clock.advance(1 * MS)
    victim = rids[0].rsplit("-", 1)[0]  # the member that admitted
    fed.kill(victim)
    for _ in range(400):
        if not fed.busy():
            break
        fed.tick()
        clock.advance(1 * MS)
    assert fed.admitted == fed.completed == 6
    asm = _asm(rec)
    assert asm.validate(rids) == []
    # At least one chain crossed members via a handoff — and it is
    # still ONE chain with one terminal complete.
    assert asm.summary()["handoff_events"] > 0
    handed = [rid for rid, chain in asm.chains.items()
              if any(ev == Ev.SPAN_HANDOFF for _, ev, *a in chain)]
    assert handed
    for rid in handed:
        assert sum(1 for _, ev, *a in asm.chains[rid]
                   if ev == Ev.SPAN_COMPLETE) == 1


# -- CLI + golden smoke (the ≤5 s tier-1 gate) --------------------------


def _demo_and_report(tmp_path, name: str) -> str:
    import subprocess  # noqa: F401  (capsys keeps this in-process)

    from pbs_tpu.cli.pbst import main

    obs = str(tmp_path / name)
    rc = main(["gateway", "demo", "--federated", "--ticks", "160",
               "--obs", obs, "--json"])
    assert rc == 0
    return obs


def test_slo_report_cli_stable_json(tmp_path, capsys):
    """`pbst slo report` on the seeded federated demo: stable JSON
    with per-tenant p50/p95/p99 + burn-rate — two runs byte-identical
    (the acceptance smoke)."""
    from pbs_tpu.cli.pbst import main

    outs = []
    for name in ("a", "b"):
        obs = _demo_and_report(tmp_path, name)
        capsys.readouterr()  # drop the demo's own output
        assert main(["slo", "report", obs]) == 0
        outs.append(capsys.readouterr().out)
    assert outs[0] == outs[1]  # seeded ⇒ byte-stable
    doc = json.loads(outs[0])
    assert doc["version"] == 1
    assert doc["spans"]["chains"] == doc["spans"]["complete"] > 0
    for tenant, row in doc["tenants"].items():
        assert {"p50_ms", "p95_ms", "p99_ms", "burn_rate", "target_ms",
                "slo", "requests", "over_target"} <= set(row)
        assert row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"]
        assert row["requests"] > 0


def test_trace_spans_cli_text_json_chrome(tmp_path, capsys):
    from pbs_tpu.cli.pbst import main

    obs = _demo_and_report(tmp_path, "c")
    capsys.readouterr()
    assert main(["trace", "spans", obs]) == 0
    out = capsys.readouterr().out
    assert "SPAN_ADMIT" in out and "SPAN_COMPLETE" in out
    assert main(["trace", "spans", obs, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["problems"] == [] and doc["spans"]["chains"] > 0
    chrome = str(tmp_path / "spans_chrome.json")
    assert main(["trace", "spans", obs, "--chrome", chrome]) == 0
    with open(chrome) as f:
        trace = json.load(f)
    assert any(e["cat"] == "span.service" for e in trace["traceEvents"])


def test_trace_spans_cli_needs_path(capsys):
    from pbs_tpu.cli.pbst import main

    assert main(["trace", "spans"]) == 2
    assert "needs a path" in capsys.readouterr().err
