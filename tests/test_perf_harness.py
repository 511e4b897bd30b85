"""``pbs_tpu.perf`` harness: bench registry, baseline gate, CLI smoke.

Tier-1 keeps a <=5 s ``pbst perf --check --quick`` smoke (the CI
regression gate on a reduced op count); the full bench matrix runs
behind ``slow``. The gate's 2x default threshold is the flake
armor — quick-mode numbers sit well inside 2x of the checked-in
full-matrix baseline on any healthy host."""

from __future__ import annotations

import json
import os

import pytest

from conftest import require_native
from pbs_tpu.cli.pbst import main
from pbs_tpu.perf import (
    NATIVE_BENCHES,
    bench_names,
    compare_to_baseline,
    load_baseline,
    run_bench,
    run_benches,
)

#: The cheap, allocation-sensitive benches used for unit-level checks
#: (no sockets, no sim run).
CHEAP = ["trace.emit", "trace.emit_many", "trace.consume",
         "ledger.snapshot_many"]


def test_bench_registry_names():
    assert {"trace.emit", "trace.emit_many", "trace.consume",
            "span.emit", "hist.record", "hist.record_many",
            "ledger.snapshot_many", "fairqueue.cycle",
            "journal.append", "gateway.pump", "sim.smoke",
            "sim.sustained", "sweep.cell", "hwtelem.sample",
            "rpc.roundtrip"} == set(bench_names())
    # The native matrix is the substrate subset: every native bench
    # exists in the python registry too (dual-mode, same measurement).
    assert set(bench_names(native=True)) == set(NATIVE_BENCHES)
    assert set(NATIVE_BENCHES) <= set(bench_names())


def test_run_bench_shape_and_sanity():
    r = run_bench("trace.emit_many", quick=True, rounds=1)
    d = r.as_dict()
    assert set(d) == {"ops", "rounds", "ns_per_op", "ops_per_s",
                      "alloc_blocks_per_op", "alloc_peak_kib"}
    assert d["ops"] > 0 and d["ns_per_op"] > 0
    # The vectorized batched path must stay well under 1 us/record.
    assert d["ns_per_op"] < 1000


def test_unknown_bench_is_keyerror():
    with pytest.raises(KeyError):
        run_bench("nonesuch")
    with pytest.raises(KeyError):
        run_benches(["trace.emit", "nonesuch"])


def test_compare_flags_only_large_regressions():
    results = {"benches": {"a": {"ns_per_op": 100.0},
                           "b": {"ns_per_op": 100.0},
                           "c": {"ns_per_op": 100.0}}}
    baseline = {"benches": {"a": {"ns_per_op": 60.0},   # 1.67x: ok
                            "b": {"ns_per_op": 10.0},   # 10x: regression
                            "x": {"ns_per_op": 1.0}}}   # absent: skipped
    regs = compare_to_baseline(results, baseline, threshold=2.0)
    assert [r["bench"] for r in regs] == ["b"]
    assert regs[0]["ratio"] == 10.0


def test_checked_in_baseline_is_loadable_and_complete():
    base = load_baseline()
    # All four comparison maps ship: python full/quick AND the
    # --native mode's substrate maps (like-with-like per mode).
    assert set(base["benches"]) == set(bench_names())
    assert set(base["quick_benches"]) == set(bench_names())
    assert set(base["native_benches"]) == set(NATIVE_BENCHES)
    assert set(base["native_quick_benches"]) == set(NATIVE_BENCHES)
    for mode in ("benches", "quick_benches", "native_benches",
                 "native_quick_benches"):
        for name, rec in base[mode].items():
            assert rec["ns_per_op"] > 0, (mode, name)


def test_quick_results_compare_against_quick_baseline():
    results = {"quick": True, "benches": {"a": {"ns_per_op": 100.0}}}
    baseline = {"benches": {"a": {"ns_per_op": 10.0}},      # full: 10x
                "quick_benches": {"a": {"ns_per_op": 90.0}}}  # quick: 1.1x
    assert compare_to_baseline(results, baseline, threshold=2.0) == []
    results["quick"] = False
    regs = compare_to_baseline(results, baseline, threshold=2.0)
    assert [r["bench"] for r in regs] == ["a"]


def test_native_results_only_compare_against_native_maps():
    # A native run must NEVER be judged against python-mode numbers:
    # its whole point is being several x faster, which would mask a
    # real native regression until it crossed the python line.
    results = {"native": True, "benches": {"a": {"ns_per_op": 100.0}}}
    baseline = {"benches": {"a": {"ns_per_op": 1000.0}},  # python: fine
                "native_benches": {"a": {"ns_per_op": 10.0}}}  # 10x reg
    regs = compare_to_baseline(results, baseline, threshold=2.0)
    assert [r["bench"] for r in regs] == ["a"]
    # No native maps at all: nothing is gated (a new mode must be able
    # to land before its baseline numbers do) — python map untouched.
    assert compare_to_baseline(
        results, {"benches": {"a": {"ns_per_op": 10.0}}}, 2.0) == []


def test_wall_clock_benches_get_wider_armor():
    # rpc.roundtrip rides the OS scheduler: a 3x swing is environment,
    # not code — the per-bench armor (4x) absorbs it; 5x still fails.
    baseline = {"benches": {"rpc.roundtrip": {"ns_per_op": 100.0}}}
    ok = {"benches": {"rpc.roundtrip": {"ns_per_op": 300.0}}}
    bad = {"benches": {"rpc.roundtrip": {"ns_per_op": 500.0}}}
    assert compare_to_baseline(ok, baseline, threshold=2.0) == []
    regs = compare_to_baseline(bad, baseline, threshold=2.0)
    assert [r["bench"] for r in regs] == ["rpc.roundtrip"]
    assert regs[0]["threshold"] == 4.0


def test_cli_perf_quick_check_smoke(capsys):
    """THE tier-1 gate: quick matrix vs the checked-in baseline."""
    assert main(["perf", "--check", "--quick", "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["version"] == 1 and d["quick"] is True
    assert set(d["benches"]) == set(bench_names())


def test_cli_perf_check_fails_on_regression(tmp_path, capsys):
    fake = tmp_path / "baseline.json"
    fake.write_text(json.dumps({
        "version": 1,
        "benches": {"trace.emit_many": {"ns_per_op": 0.001}}}))
    rc = main(["perf", "--bench", "trace.emit_many", "--quick",
               "--baseline", str(fake), "--check", "--json"])
    assert rc == 1
    cap = capsys.readouterr()
    # Diagnostics go to stderr; stdout stays exactly the JSON document.
    assert "PERF REGRESSION" in cap.err
    json.loads(cap.out)


def test_cli_perf_rejects_quick_baseline_update(tmp_path, capsys):
    out = tmp_path / "b.json"
    rc = main(["perf", "--quick", "--update-baseline",
               "--baseline", str(out)])
    assert rc == 2 and not out.exists()


def test_cli_perf_unknown_bench_usage_error(capsys):
    assert main(["perf", "--bench", "nonesuch", "--quick"]) == 2
    assert "unknown bench" in capsys.readouterr().err


def test_cli_perf_update_baseline_roundtrip(tmp_path):
    out = tmp_path / "b.json"
    # Full-mode single cheap bench keeps this test fast while still
    # exercising the write->check cycle end to end.
    assert main(["perf", "--bench", "trace.consume",
                 "--baseline", str(out), "--update-baseline"]) == 0
    assert main(["perf", "--bench", "trace.consume",
                 "--baseline", str(out), "--check"]) == 0
    doc = json.loads(out.read_text())
    assert set(doc["benches"]) == {"trace.consume"}
    assert set(doc["quick_benches"]) == {"trace.consume"}


def test_partial_baseline_update_merges_not_replaces(tmp_path):
    from pbs_tpu.perf import save_baseline

    out = str(tmp_path / "b.json")
    save_baseline({"benches": {"a": {"ns_per_op": 1.0}}}, out,
                  quick_results={"benches": {"a": {"ns_per_op": 2.0}}})
    # A single-bench refresh must not drop 'a' from the gate.
    save_baseline({"benches": {"b": {"ns_per_op": 3.0}}}, out,
                  quick_results={"benches": {"b": {"ns_per_op": 4.0}}})
    doc = json.loads(open(out).read())
    assert doc["benches"] == {"a": {"ns_per_op": 1.0},
                              "b": {"ns_per_op": 3.0}}
    assert doc["quick_benches"] == {"a": {"ns_per_op": 2.0},
                                    "b": {"ns_per_op": 4.0}}


@pytest.mark.slow
def test_full_matrix_check_against_baseline():
    """The full bench matrix (the numbers the baseline was written
    from) stays inside the gate."""
    results = run_benches()
    regs = compare_to_baseline(results, load_baseline())
    assert regs == [], regs


def test_baseline_checked_into_package():
    # package-data wiring: the baseline ships next to the module.
    import pbs_tpu.perf.report as report

    assert os.path.exists(report.baseline_path())


# -- dual mode (--native) ----------------------------------------------------


def test_report_carries_native_stamp(capsys):
    """Satellite: every report says which mode ran and whether/why the
    native runtime is (un)available, so BENCH_r* rounds compare across
    machines with and without a toolchain."""
    assert main(["perf", "--bench", "trace.emit_many", "--quick",
                 "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["native"] is False and d["native_mode"] == "python"
    assert isinstance(d["native_available"], bool)
    if d["native_available"]:
        assert d["native_tier"] in ("fastcall", "ctypes")
    else:
        assert d["native_error"]


@pytest.mark.parametrize("gated", [
    False, pytest.param(True, marks=pytest.mark.slow)],
    ids=["record", "gate"])
def test_cli_perf_native_quick_check_smoke(capsys, gated):
    """The native twin of THE tier-1 gate: quick substrate matrix in
    native mode vs the baseline's native maps. Tier-1 (``record``) runs
    the command, gate and re-measure included, and checks what it
    reports; that no bench is 3 x its wall-clock baseline is asked
    behind ``slow`` (``gate``), on a host whose other five test workers
    are not compiling beside it (ROADMAP D20: it read 3.17 x once in
    three whole runs of tier-1)."""
    require_native()
    rc = main(["perf", "--check", "--quick", "--native", "--json"])
    assert rc == 0 if gated else rc in (0, 1)
    d = json.loads(capsys.readouterr().out)
    assert d["native"] is True and d["native_mode"] == "native"
    assert set(d["benches"]) == set(NATIVE_BENCHES)


def test_native_bench_without_native_path_is_usage_error(capsys):
    require_native()
    assert main(["perf", "--native", "--bench", "rpc.roundtrip",
                 "--quick"]) == 2
    err = capsys.readouterr().err
    assert "rpc.roundtrip" in err and "unknown bench" in err


def test_cli_perf_native_unavailable_is_explicit(monkeypatch, capsys):
    """--native on a host with no toolchain must FAIL with the cached
    reason, never silently bench the python paths as 'native'."""
    from pbs_tpu.runtime import native as native_mod

    monkeypatch.setattr(native_mod, "available", lambda: False)
    monkeypatch.setattr(native_mod, "unavailable_reason",
                        lambda: "make exited 2: g++: not found")
    assert main(["perf", "--native", "--bench", "trace.emit",
                 "--quick"]) == 2
    err = capsys.readouterr().err
    assert "g++: not found" in err


def test_update_baseline_native_writes_native_maps(tmp_path):
    require_native()
    out = tmp_path / "b.json"
    assert main(["perf", "--bench", "trace.consume", "--baseline",
                 str(out), "--update-baseline"]) == 0
    assert main(["perf", "--native", "--bench", "trace.consume",
                 "--baseline", str(out), "--update-baseline"]) == 0
    doc = json.loads(out.read_text())
    # A native refresh merges alongside the python maps, never over.
    assert set(doc["benches"]) == {"trace.consume"}
    assert set(doc["native_benches"]) == {"trace.consume"}
    assert set(doc["native_quick_benches"]) == {"trace.consume"}
    assert main(["perf", "--native", "--bench", "trace.consume",
                 "--baseline", str(out), "--check"]) == 0
