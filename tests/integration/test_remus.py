"""Remus over the wire: continuous replication + failover, real processes.

Reference behavior being matched: ``tools/remus/README:1-4`` — a backup
host is kept continuously up to date by repeatedly shipping checkpoint
epochs over TCP; when the primary dies, the backup resumes the domain
from the last *committed* epoch, preserving its runtime state. Here the
shipped record carries steps, telemetry counters, contention sums, and
scheduler params (more than the reference — its perfctr state silently
resets on migration, SURVEY.md §5), so all of it must survive SIGKILL.
"""

from __future__ import annotations

import time

import pytest

from tests.integration.test_xm import HostProc

from pbs_tpu.dist import Controller


@pytest.fixture()
def hosts():
    procs = [HostProc(f"rm{i}") for i in range(3)]
    ctl = Controller()
    for p in procs:
        ctl.add_agent(p.name, p.address)
    yield ctl, procs
    ctl.close()
    for p in procs:
        p.stop()


def _kill_and_detect(ctl, procs, home):
    victim = next(p for p in procs if p.name == home)
    victim.kill9()
    for _ in range(ctl.dead_after_missed + 1):
        alive = ctl.heartbeat()
    assert alive[home] is False


def test_enable_replication_ships_first_epoch_synchronously(hosts):
    ctl, _ = hosts
    ctl.create_job("prot", spec={"step_time_ns": 1_000_000,
                                 "sched": {"weight": 320}})
    peers = ctl.enable_replication("prot", period_s=0.05)
    home = ctl.jobs["prot"].members[0].agent
    backup = peers["prot"]
    assert backup != home
    # the committed epoch-0 replica is already on the backup
    r = ctl.agents[backup].client.call("get_replica", job="prot")
    assert r is not None and r["source"] == home
    assert r["saved"]["sched"]["weight"] == 320
    st = ctl.agents[home].client.call("replicate_status", job="prot")
    assert st and st[0]["epochs_committed"] >= 1


def test_replication_pumps_epochs_while_running(hosts):
    from pbs_tpu.telemetry.counters import Counter

    ctl, _ = hosts
    ctl.create_job("pump", spec={"step_time_ns": 1_000_000})
    peers = ctl.enable_replication("pump", period_s=0.05)
    home = ctl.jobs["pump"].members[0].agent

    def committed() -> int:
        st = ctl.agents[home].client.call("replicate_status", job="pump")
        return st[0]["epochs_committed"]

    # Rounds until the pump has committed two epochs, however long a
    # loaded host takes over them (the deadline is a hang's, not the
    # pump's: four sleeps of 0.08 s measured the host's load).
    deadline = time.monotonic() + 20.0
    while committed() < 2 and time.monotonic() < deadline:
        ctl.run_round(max_rounds=20)
        time.sleep(0.02)
    backup = ctl.agents[peers["pump"]]
    before = committed()
    r = backup.client.call("get_replica", job="pump")
    assert before >= 2  # the pump advanced past epoch 0
    # the replica holds the last committed epoch (the pump may commit
    # one more between the two reads)
    assert before - 1 <= r["epoch"] <= committed() - 1
    # epochs capture live progress: steps have been retired and shipped
    shipped_steps = sum(c["counters"][Counter.STEPS_RETIRED]
                        for c in r["saved"]["contexts"])
    assert shipped_steps > 0


def test_kill9_failover_restores_from_replica_counters_survive(hosts):
    """The headline Remus test (verdict #7 'done' bar): SIGKILL the
    primary, recover from the replica on the peer, counters survive."""
    from pbs_tpu.telemetry.counters import Counter

    ctl, procs = hosts
    ctl.create_job("crit", spec={"step_time_ns": 1_000_000,
                                 "sched": {"weight": 640, "cap": 70}})
    peers = ctl.enable_replication("crit", period_s=0.05)
    home = ctl.jobs["crit"].members[0].agent
    backup = peers["crit"]

    for _ in range(3):
        ctl.run_round(max_rounds=25)
        time.sleep(0.08)
    # force one final epoch to be committed before the kill so the
    # assertion threshold is deterministic
    time.sleep(0.2)
    r_before = ctl.agents[backup].client.call("get_replica", job="crit")
    replicated_steps = sum(
        c["counters"][Counter.STEPS_RETIRED]
        for c in r_before["saved"]["contexts"])
    assert replicated_steps > 0

    _kill_and_detect(ctl, procs, home)
    moved = ctl.recover()
    assert moved == ["crit"]
    new_home = ctl.jobs["crit"].members[0].agent
    assert new_home == backup  # failover lands where the state already is

    # Runtime state survived: steps, counters, sched params.
    tele = ctl.agents[new_home].client.call("telemetry", job="crit")
    steps_after = sum(c["counters"]["steps_retired"]
                      for c in tele["contexts"])
    assert steps_after >= replicated_steps
    params = ctl.agents[new_home].client.call(
        "sched_setparams", job="crit", subject="controller")
    assert params["weight"] == 640 and params["cap"] == 70
    # the consumed replica is dropped (no stale failover source)
    assert ctl.agents[new_home].client.call("get_replica", job="crit") is None

    # and the job RUNS on the new home, continuing from where it was
    ctl.run_round(max_rounds=20)
    assert sum(ctl.job_steps("crit").values()) > steps_after

    # protection was re-armed from the new home (a third host is live)
    st = ctl.agents[new_home].client.call("replicate_status", job="crit")
    assert st and st[0]["running"]


def test_unreplicated_job_restarts_fresh_on_recover(hosts):
    """Contrast case: without Remus, host death loses runtime state —
    recover() falls back to a from-spec restart (the reference's
    unprotected-domain behavior)."""
    ctl, procs = hosts
    ctl.create_job("naked", spec={"step_time_ns": 1_000_000})
    home = ctl.jobs["naked"].members[0].agent
    ctl.run_round(max_rounds=20)
    assert sum(ctl.job_steps("naked").values()) > 0
    _kill_and_detect(ctl, procs, home)
    assert ctl.recover() == ["naked"]
    tele_steps = sum(ctl.job_steps("naked").values())
    assert tele_steps == 0  # fresh start: nothing survived


def test_disable_replication_stops_pump_and_drops_replica(hosts):
    ctl, _ = hosts
    ctl.create_job("tmp", spec={"step_time_ns": 1_000_000})
    peers = ctl.enable_replication("tmp", period_s=0.05)
    home = ctl.jobs["tmp"].members[0].agent
    backup = peers["tmp"]
    ctl.disable_replication("tmp")
    assert ctl.agents[home].client.call("replicate_status", job="tmp") == []
    assert ctl.agents[backup].client.call("get_replica", job="tmp") is None


def test_restarted_session_resumes_past_existing_replica(hosts):
    """Re-enabling replication to a peer already holding epoch N must
    resume at N+1, not restart at 0 (which the backup would discard as
    stale while the session reported healthy commits — review
    finding)."""
    ctl, _ = hosts
    ctl.create_job("resump", spec={"step_time_ns": 1_000_000})
    peers = ctl.enable_replication("resump", period_s=10.0)
    home, backup = ctl.jobs["resump"].members[0].agent, peers["resump"]
    # simulate history: the backup already holds a high epoch
    r0 = ctl.agents[backup].client.call("get_replica", job="resump",
                                        subject="controller")
    ctl.agents[backup].client.call(
        "push_replica", job="resump", epoch=41, saved=r0["saved"],
        source=home, subject="controller")
    # restart the session against the SAME backup
    st = ctl.agents[home].client.call(
        "replicate_start", job="resump",
        peer_host=ctl.agents[backup].address[0],
        peer_port=ctl.agents[backup].address[1],
        period_s=10.0, subject="controller")
    assert st["epochs_committed"] == 43  # resumed past 41, shipped 42
    r = ctl.agents[backup].client.call("get_replica", job="resump",
                                       subject="controller")
    assert r["epoch"] == 42  # the fresh state LANDED (not discarded)


def test_migration_keeps_protection_and_drops_stale_replica(hosts):
    """migrate_job must not leave a stale replica as a failover source
    nor silently disarm replication (review finding)."""
    ctl, _ = hosts
    ctl.create_job("mover", spec={"step_time_ns": 1_000_000})
    peers = ctl.enable_replication("mover", period_s=0.05)
    old_backup = peers["mover"]
    ctl.run_round(max_rounds=20)
    ctl.migrate_job("mover")
    new_home = ctl.jobs["mover"].members[0].agent
    # protection re-armed from the new home...
    assert ctl.jobs["mover"].replica_peers.get("mover") is not None
    st = ctl.agents[new_home].client.call("replicate_status", job="mover")
    assert st and st[0]["running"]
    # ...and the new backup holds a replica; the old stale one is gone
    new_backup = ctl.jobs["mover"].replica_peers["mover"]
    assert ctl.agents[new_backup].client.call(
        "get_replica", job="mover", subject="controller") is not None
    if old_backup != new_backup:
        assert ctl.agents[old_backup].client.call(
            "get_replica", job="mover", subject="controller") is None


def test_replica_reads_are_xsm_guarded(hosts):
    """get_replica carries full job state: an enforcing policy must
    gate it like the save op (review finding)."""
    import pbs_tpu.runtime.xsm as xsm

    ctl, _ = hosts
    ctl.create_job("guarded", spec={"step_time_ns": 1_000_000})
    peers = ctl.enable_replication("guarded", period_s=10.0)
    backup = ctl.agents[peers["guarded"]]
    # The agent processes run a DummyPolicy; the gate is the op's
    # xsm_check call — verify the subject kwarg reaches it by checking
    # the op rejects when the backup enforces. Flip policy remotely is
    # not exposed, so assert locally against the same code path:
    from pbs_tpu.dist.agent import Agent

    a = Agent("local", n_executors=1).start()
    a.replicas["x"] = {"epoch": 0, "saved": {"label": "tenant-a"},
                       "source": "s", "received_at": 0.0}
    xsm.set_policy(xsm.LabelPolicy(default_allow=False))
    try:
        try:
            a.op_get_replica("x", subject="rando")
            raised = False
        except xsm.XsmDenied:
            raised = True
        assert raised
        assert a.op_list_replicas(subject="rando") == []
        xsm.set_policy(xsm.LabelPolicy(default_allow=False)
                       .allow("ops", "job.replicate", "*"))
        assert a.op_get_replica("x", subject="ops") is not None
        assert len(a.op_list_replicas(subject="ops")) == 1
    finally:
        xsm.set_policy(xsm.DummyPolicy())
        a.stop()
    # remote path still works for the privileged controller subject
    assert backup.client.call("get_replica", job="guarded",
                              subject="controller") is not None


def test_stale_epoch_rejected_by_backup(hosts):
    """A delayed duplicate push must not roll the replica back."""
    ctl, _ = hosts
    ctl.create_job("seq", spec={"step_time_ns": 1_000_000})
    peers = ctl.enable_replication("seq", period_s=10.0)  # only epoch 0
    backup = ctl.agents[peers["seq"]]
    r0 = backup.client.call("get_replica", job="seq")
    # forge a newer epoch, then replay an older one
    backup.client.call("push_replica", job="seq", epoch=5,
                       saved=r0["saved"], source="test",
                       subject="controller")
    resp = backup.client.call("push_replica", job="seq", epoch=1,
                              saved=r0["saved"], source="test",
                              subject="controller")
    assert resp["stale"] is True
    assert backup.client.call("get_replica", job="seq")["epoch"] == 5
