"""Regression tests for the round-1 advisor findings.

1. RPC subjects: privileged ("system") labels over the wire require a
   token-authenticated connection (agent.py finding, medium).
2. hybrid_mesh multi-axis reorder: the (dcn*ici elementwise) array from
   create_hybrid_device_mesh must be split+transposed, not reshaped
   (multihost.py finding, medium).
3. cpu_pct counts closed windows only (mon.py finding, low).
4. add_job unwinds scheduler-enrollment failures atomically
   (partition.py finding, low).
5. store read/ls/watch are XSM-checked like writes (store.py finding,
   low).
"""

import numpy as np
import pytest

from pbs_tpu.dist import Agent
from pbs_tpu.dist.rpc import RpcClient, RpcError
from pbs_tpu.runtime import Job, Partition
from pbs_tpu.runtime.xsm import (
    DummyPolicy,
    LabelPolicy,
    XsmDenied,
    set_policy,
)
from pbs_tpu.telemetry.source import SimBackend, SimProfile


@pytest.fixture(autouse=True)
def _reset_policy():
    yield
    set_policy(DummyPolicy())


# -- 1: wire subjects ------------------------------------------------------


def test_wire_system_subject_rejected_without_auth():
    """Under an *enforcing* policy, claiming subject="system" over the
    wire must not short-circuit to allow."""
    set_policy(LabelPolicy())  # default-deny for everyone but system
    agent = Agent("sec0").start()
    cli = RpcClient(agent.address)
    try:
        with pytest.raises(RpcError, match="authenticated"):
            cli.call("create_job", job="j", spec={"max_steps": 2},
                     subject="system")
        # and an ordinary label is still policy-checked (denied here)
        with pytest.raises(RpcError, match="XsmDenied"):
            cli.call("create_job", job="j", spec={"max_steps": 2},
                     subject="mallory")
    finally:
        cli.close()
        agent.stop()


def test_wire_system_subject_allowed_with_token():
    set_policy(LabelPolicy())
    agent = Agent("sec1", auth_token="s3cret").start()
    good = RpcClient(agent.address, auth_token="s3cret")
    bad = RpcClient(agent.address, auth_token="wrong")
    try:
        r = good.call("create_job", job="j", spec={"max_steps": 2},
                      subject="system")
        assert r["job"] == "j"
        with pytest.raises(RpcError, match="auth"):
            bad.call("list_jobs")
    finally:
        good.close()
        bad.close()
        agent.stop()


def test_auth_refused_when_no_token_configured():
    agent = Agent("sec2").start()  # no token: nobody can be privileged
    cli = RpcClient(agent.address, auth_token="anything")
    try:
        with pytest.raises(RpcError, match="auth"):
            cli.call("ping")
    finally:
        cli.close()
        agent.stop()


# -- 2: hybrid mesh reorder ------------------------------------------------


def test_reorder_hybrid_multi_axis():
    """ici={tp:4,sp:4} x dcn={dp:2,fsdp:2}: every inner (ici) block of
    the result must come from one DCN granule (contiguous device ids,
    since create_hybrid_device_mesh fills granules densely)."""
    from pbs_tpu.parallel.multihost import _reorder_hybrid

    dcn_p, ici_p = (2, 2), (4, 4)
    # Build the elementwise-product array exactly as
    # create_hybrid_device_mesh lays it out: per axis, DCN major.
    n = 64
    ids = np.arange(n)
    # granule g holds devices [g*16, (g+1)*16); granules arranged (2,2)
    arr = np.zeros((8, 8), dtype=int)
    for d1 in range(2):
        for d2 in range(2):
            g = d1 * 2 + d2
            block = ids[g * 16:(g + 1) * 16].reshape(4, 4)
            arr[d1 * 4:(d1 + 1) * 4, d2 * 4:(d2 + 1) * 4] = block
    out = _reorder_hybrid(arr, dcn_p, ici_p)
    assert out.shape == (2, 2, 4, 4)
    for d1 in range(2):
        for d2 in range(2):
            g = d1 * 2 + d2
            inner = out[d1, d2]
            assert inner.min() == g * 16 and inner.max() == g * 16 + 15, (
                f"granule ({d1},{d2}) mixes slices: {inner}"
            )
    # and the naive reshape really is wrong (the bug being fixed)
    naive = arr.reshape(2, 2, 4, 4)
    assert any(
        naive[d1, d2].max() - naive[d1, d2].min() >= 16
        for d1 in range(2) for d2 in range(2)
    )


# -- 3: cpu_pct closed windows only ---------------------------------------


def test_cpu_pct_ignores_open_window():
    from pbs_tpu.obs.mon import SchedHistory, Window

    h = SchedHistory(window_ns=1000)
    h._hist[0] = [Window(gotten_ns=500)]
    h._cur[0] = Window(gotten_ns=900)  # open window, partial span
    # closed window only: 50%; with the old behavior this read 140%
    assert h.cpu_pct(0, windows=1) == pytest.approx(50.0)
    # summary still includes the open window by default
    assert h.summary(0).gotten_ns == 1400


# -- 4: add_job unwind covers scheduler enrollment -------------------------


def test_add_job_unwinds_scheduler_failure():
    be = SimBackend()
    part = Partition("p", source=be)
    be.register("boom", SimProfile.steady(step_time_ns=1000))

    orig = part.scheduler.job_added

    def exploding(job):
        raise RuntimeError("scheduler rejects")

    part.scheduler.job_added = exploding
    with pytest.raises(RuntimeError, match="rejects"):
        part.add_job(Job("boom"))
    part.scheduler.job_added = orig
    assert all(j.name != "boom" for j in part.jobs)
    if part.memory is not None:
        assert "boom" not in getattr(part.memory, "accounts", {})
    # name retryable, slots not leaked
    j = part.add_job(Job("boom"))
    assert j.contexts[0].ledger_slot >= 0


def test_multicall_malformed_entry_keeps_per_entry_status():
    """A bad entry (non-dict args) must not abort the batch — the
    multicall contract gives each entry its own status."""
    agent = Agent("mc0").start()
    cli = RpcClient(agent.address)
    try:
        sock_calls = [("ping", {}), ("ping", None)]
        # craft the malformed entry manually (client API normalizes)
        from pbs_tpu.dist.rpc import recv_msg, send_msg
        import socket

        s = socket.create_connection(agent.address, timeout=5)
        send_msg(s, {"op": "multicall", "calls": [
            {"op": "ping"}, {"op": "ping", "args": [1]}]})
        resp = recv_msg(s)
        s.close()
        assert resp["ok"]
        first, second = resp["result"]
        assert first["ok"] and first["result"] == "pong"
        assert not second["ok"]
        del sock_calls
    finally:
        cli.close()
        agent.stop()


def test_cpu_pct_windows_beyond_history_counts_all_closed():
    from pbs_tpu.obs.mon import SchedHistory, Window

    h = SchedHistory(window_ns=1000)
    h._hist[0] = [Window(gotten_ns=1000)] * 3
    # windows=5 > 3 closed: all 3 must count (old slice dropped oldest)
    assert h.cpu_pct(0, windows=5) == pytest.approx(100.0 * 3000 / 5000)


# -- 5: store reads are policy-checked ------------------------------------


def test_store_read_ls_watch_checked():
    from pbs_tpu.store import Store

    s = Store()
    s.write("/jobs/a", 1)
    set_policy(LabelPolicy().allow("app", "store.write", "/jobs/*"))
    with pytest.raises(XsmDenied):
        s.read("/jobs/a", subject="app")  # write-only label can't read
    with pytest.raises(XsmDenied):
        s.ls("/jobs", subject="app")
    with pytest.raises(XsmDenied):
        s.watch("/jobs", lambda p, v: None, subject="app")
    with pytest.raises(XsmDenied):
        s.exists("/jobs/a", subject="app")  # existence is information
    with pytest.raises(XsmDenied):
        s.version("/jobs/a", subject="app")
    set_policy(LabelPolicy()
               .allow("app", "store.write", "/jobs/*")
               .allow("app", "store.read", "/jobs*"))
    assert s.read("/jobs/a", subject="app") == 1
    assert s.ls("/jobs", subject="app") == ["a"]
    # in-process callers (default system subject) unaffected
    assert s.read("/jobs/a") == 1


def test_hybrid_mesh_trains_end_to_end():
    """Capstone for the hybrid-mesh reorder fix: a DCN dp axis over
    ICI tp x sp granules carries a REAL sharded train step (ring
    attention riding sp, Megatron specs riding tp) with loss parity
    vs single-device dense — the scaling-book layout, exercised."""
    import jax
    import jax.numpy as jnp
    import pytest

    from pbs_tpu.models import init_params, make_train_step
    from pbs_tpu.models.transformer import TransformerConfig
    from pbs_tpu.parallel import batch_sharding, make_sharded_train
    from pbs_tpu.parallel.multihost import hybrid_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    TINY = dict(vocab=128, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=128, max_seq=64, dtype=jnp.float32)
    tokens = jax.random.randint(
        jax.random.PRNGKey(7), (4, 64), 0, 128, jnp.int32)

    dense_cfg = TransformerConfig(**TINY, attn_impl="xla")
    init_opt, dstep = make_train_step(dense_cfg, learning_rate=1e-2,
                                      full_seq=True)
    params = init_params(dense_cfg, jax.random.PRNGKey(0))
    dstate = (params, init_opt(params), 0)
    dstate, dm = jax.jit(dstep)(dstate, tokens)

    mesh = hybrid_mesh({"tp": 2, "sp": 2}, {"dp": 2})
    assert mesh.axis_names == ("dp", "tp", "sp")
    ring_cfg = TransformerConfig(**TINY, attn_impl="ring")
    state, step = make_sharded_train(ring_cfg, mesh, learning_rate=1e-2)
    toks = jax.device_put(tokens, batch_sharding(mesh))
    state, m = step(state, toks)
    assert float(m["loss"]) == pytest.approx(float(dm["loss"]), rel=2e-4)
