"""Boot-param registry + lock-contention profiling (LOCK_PROFILE analog)."""

import json
import threading
import time

import pytest

from pbs_tpu.obs import lockprof
from pbs_tpu.obs.dumpfile import read_obs_dump, write_obs_dump
from pbs_tpu.obs.perfc import perfc
from pbs_tpu.utils import params


@pytest.fixture(autouse=True)
def _clean_registry():
    params.reset_all()
    lockprof.reset()
    yield
    params.reset_all()
    lockprof.reset()


# -- params -----------------------------------------------------------------


def test_param_kinds_and_defaults():
    b = params.boolean_param("t_bool", True)
    i = params.integer_param("t_int", 42)
    s = params.string_param("t_str", "credit")
    assert (b.value, i.value, s.value) == (True, 42, "credit")


def test_parse_cmdline_forms():
    params.boolean_param("t_flag", False)
    params.integer_param("t_num", 0)
    unknown = params.parse_cmdline("t_flag t_num=0x10 bogus=1")
    assert params.get("t_flag").value is True
    assert params.get("t_num").value == 16
    assert unknown == ["bogus=1"]
    params.parse_cmdline("no-t_flag")
    assert params.get("t_flag").value is False


def test_parse_cmdline_rejects_bad_values_without_raising():
    params.integer_param("t_strict", 5)
    rejected = params.parse_cmdline("t_strict=abc t_strict")
    assert sorted(rejected) == ["t_strict", "t_strict=abc"]
    assert params.get("t_strict").value == 5  # untouched


def test_parse_cmdline_bare_forms_only_for_booleans():
    params.string_param("t_name", "credit")
    rejected = params.parse_cmdline("t_name no-t_name")
    assert sorted(rejected) == ["no-t_name", "t_name"]
    assert params.get("t_name").value == "credit"  # not "on"/"off"


def test_reregistration_preserves_set_value():
    p = params.integer_param("t_keep", 1)
    p.set("7")
    again = params.integer_param("t_keep", 1)
    assert again is p and again.value == 7


def test_env_override(monkeypatch):
    monkeypatch.setenv("PBST_T_ENVD", "123")
    p = params.integer_param("t_envd", 5)
    assert p.value == 123


def test_bad_env_value_warns_and_keeps_default(monkeypatch, capsys):
    monkeypatch.setenv("PBST_T_ENVBAD", "4k")
    p = params.integer_param("t_envbad", 7)
    assert p.value == 7
    assert "PBST_T_ENVBAD" in capsys.readouterr().err


def test_sched_param_picks_partition_scheduler():
    from pbs_tpu.runtime import Partition
    from pbs_tpu.telemetry import SimBackend

    params.parse_cmdline("sched=credit2")
    part = Partition("p", source=SimBackend())
    assert type(part.scheduler).__name__.lower().startswith("credit2")
    # explicit argument still wins
    part2 = Partition("p2", source=SimBackend(), scheduler="credit")
    assert type(part2.scheduler).__name__.lower().startswith("credit2") is False


def test_tslice_param_feeds_schedparams_default():
    from pbs_tpu.runtime.job import SchedParams

    params.parse_cmdline("sched_credit_tslice_us=250")
    assert SchedParams().tslice_us == 250
    assert SchedParams(tslice_us=90).tslice_us == 90


# -- lockprof ---------------------------------------------------------------


def test_lockprof_disabled_counts_nothing():
    lk = lockprof.ProfiledLock("t_quiet")
    with lk:
        pass
    assert lk.stats.acquires == 0


def test_lockprof_counts_acquires_and_contention():
    params.get("lock_profile").set("on")
    lk = lockprof.ProfiledLock("t_lock")
    with lk:
        pass
    assert lk.stats.acquires == 1 and lk.stats.contended == 0

    held = threading.Event()

    def _holder():
        with lk:
            held.set()
            time.sleep(0.02)

    t = threading.Thread(target=_holder)
    t.start()
    # not a sleep: on a loaded host the holder may not have run yet
    assert held.wait(timeout=10)
    with lk:  # must block on the holder
        pass
    t.join()
    assert lk.stats.acquires == 3
    assert lk.stats.contended >= 1
    assert lk.stats.wait_ns > 0
    assert lk.stats.max_wait_ns <= lk.stats.wait_ns
    assert lk.stats.hold_ns > 0


def test_lockprof_recursive_reentry_counts_one_hold():
    params.get("lock_profile").set("on")
    lk = lockprof.ProfiledLock("t_rec", recursive=True)
    with lk:
        t_outer = lk._t_acq
        with lk:  # re-entry must not re-stamp or double-count hold
            assert lk._t_acq == t_outer
        assert lk.stats.hold_ns == 0  # not yet released outermost
    assert lk.stats.acquires == 2
    assert lk.stats.hold_ns > 0
    assert lk._t_acq is None  # cleared: no stale interval on next toggle


def test_lockprof_toggle_midstream_no_stale_hold():
    lk = lockprof.ProfiledLock("t_toggle")
    params.get("lock_profile").set("on")
    with lk:
        pass
    hold0 = lk.stats.hold_ns
    params.get("lock_profile").set("off")
    lk.acquire()  # unprofiled acquire: no timestamp
    params.get("lock_profile").set("on")
    lk.release()  # must NOT charge time since the old _t_acq
    assert lk.stats.hold_ns == hold0


def test_lockprof_dump_sorted_and_reset():
    params.get("lock_profile").set("on")
    a = lockprof.ProfiledLock("t_a")
    with a:
        pass
    rows = lockprof.dump()
    names = [r["name"] for r in rows]
    assert "t_a" in names
    lockprof.reset()
    assert all(r["acquires"] == 0 for r in lockprof.dump())


def test_store_lock_is_profiled(tmp_path):
    from pbs_tpu.store import Store

    params.get("lock_profile").set("on")
    lockprof.reset()
    s = Store()
    s.write("/x", 1)
    assert s.read("/x") == 1
    row = {r["name"]: r for r in lockprof.dump()}["store"]
    assert row["acquires"] >= 2


# -- dumpfile + CLI ---------------------------------------------------------


def test_obs_dump_roundtrip_and_cli(tmp_path, capsys):
    from pbs_tpu.cli.pbst import main

    params.get("lock_profile").set("on")
    perfc.incr("t_cli_counter", 3)
    with lockprof.ProfiledLock("t_cli_lock"):
        pass
    path = str(tmp_path / "obs.json")
    snap = write_obs_dump(path)
    assert read_obs_dump(path) == json.loads(json.dumps(snap))

    assert main(["perfc", path]) == 0
    out = capsys.readouterr().out
    assert "t_cli_counter" in out and "3" in out

    assert main(["lockprof", path]) == 0
    out = capsys.readouterr().out
    assert "t_cli_lock" in out

    assert main(["params", "--file", path]) == 0
    out = capsys.readouterr().out
    assert "lock_profile=true" in out


def test_cli_params_cmdline(capsys):
    from pbs_tpu.cli.pbst import main

    assert main(["params", "--cmdline", "tbuf_size=99"]) == 0
    out = capsys.readouterr().out
    assert "tbuf_size=99" in out


def test_cli_params_standalone_process():
    """A fresh process must see the full registry (no import side
    effects from other tests)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # The child stays on the CPU: the CLI pins no platform itself, and
    # a chip belongs to one process at a time.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "from pbs_tpu.cli.pbst import main; main(['params'])"],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for name in ("sched=", "tbuf_size=", "lock_profile=",
                 "sched_credit_tslice_us="):
        assert name in out.stdout
