"""Policy-comparison harness tests, including the ISSUE 1 parity gates:
under the contended mix the feedback quantum hits the 100 µs floor and
beats plain credit on p99 wait; under the stable HBM-stall mix it grows
to the 1.1 ms cap. Long all-policy sweeps are @slow (tier-1 stays fast).
"""

import json

import pytest

from pbs_tpu.cli.pbst import main as pbst_main
from pbs_tpu.sched.feedback import TSLICE_MAX_US, TSLICE_MIN_US
from pbs_tpu.sim import DEFAULT_POLICIES, compare, format_report, run_policy
from pbs_tpu.utils.clock import MS


def test_compare_smoke_and_format():
    cmp = compare("mixed", policies=("credit", "feedback"), seed=0,
                  n_tenants=3, horizon_ns=50 * MS)
    assert set(cmp["policies"]) == {"credit", "feedback"}
    txt = format_report(cmp)
    assert "credit" in txt and "feedback" in txt
    for r in cmp["policies"].values():
        assert r["trace_digest"]
        assert 0 < r["jain_fairness"] <= 1.0


def test_contended_feedback_beats_credit_p99():
    """The reference's claim, reproduced offline: adaptive quanta shrink
    to the floor under contention and cut co-tenant p99 wait vs the
    same workload stuck on its static 900 µs slice."""
    fb = run_policy("contended", "feedback", seed=7, n_tenants=4,
                    horizon_ns=500 * MS)
    cr = run_policy("contended", "credit", seed=7, n_tenants=4,
                    horizon_ns=500 * MS)
    for t in fb["tenants"].values():
        assert t["tslice_us"] == TSLICE_MIN_US
    assert fb["wait_p99_us"] < cr["wait_p99_us"]
    assert fb["wait_p50_us"] < cr["wait_p50_us"]


def test_stable_hbm_workload_grows_to_cap():
    r = run_policy("stable", "feedback", seed=3, n_tenants=4,
                   horizon_ns=500 * MS)
    for t in r["tenants"].values():
        assert t["tslice_us"] == TSLICE_MAX_US
    # Growing the quantum must have cut context switches vs plain credit
    # on the same mix (that is what the longer slice buys).
    cr = run_policy("stable", "credit", seed=3, n_tenants=4,
                    horizon_ns=500 * MS)
    assert r["switches"] < cr["switches"]


def test_cli_sim_single_policy(capsys):
    assert pbst_main(["sim", "--workload", "contended", "--policy",
                      "feedback", "--seed", "7", "--seconds", "0.1"]) == 0
    out1 = capsys.readouterr().out
    assert "trace_digest=" in out1
    assert pbst_main(["sim", "--workload", "contended", "--policy",
                      "feedback", "--seed", "7", "--seconds", "0.1"]) == 0
    out2 = capsys.readouterr().out
    # Acceptance gate: two CLI runs with the same seed are byte-identical.
    assert out1 == out2
    # Unknown names are clean errors, not tracebacks.
    assert pbst_main(["sim", "--workload", "nope"]) == 2
    capsys.readouterr()
    assert pbst_main(["sim", "--policy", "nope"]) == 2
    capsys.readouterr()


def test_cli_sim_compare_json(tmp_path, capsys):
    prefix = str(tmp_path / "cmp")
    assert pbst_main(["sim", "--workload", "mixed", "--policy", "all",
                      "--seconds", "0.05", "--tenants", "2",
                      "--trace", prefix, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc["policies"]) == set(DEFAULT_POLICIES)
    # --policy all honors --trace as a per-policy prefix.
    for p in DEFAULT_POLICIES:
        assert (tmp_path / f"cmp.{p}.jsonl").exists(), p


@pytest.mark.slow
def test_full_sweep_all_policies_all_workloads():
    """The long regression sweep: every policy × every workload at the
    full 2 s horizon. Slow-marked; the fast gates above cover tier-1."""
    from pbs_tpu.sim import workload_names

    for wl in workload_names():
        cmp = compare(wl, seed=7, n_tenants=6)
        for name, r in cmp["policies"].items():
            assert r["quanta"] > 0, (wl, name)
            assert 0 < r["jain_fairness"] <= 1.0, (wl, name)
