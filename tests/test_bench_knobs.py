"""bench.py candidate-config knobs: fail fast, before any backend.

bench.py accepts PBST_BENCH_* env knobs that select a candidate
configuration. A typo in a knob must die in milliseconds with a clean
message — never after TPU init or a 700M-step compile (chip minutes
are budgeted). bench.py is one process; its tiny mode is a rehearsal
that says so in the row it prints.

Reference analog: boot-param validation at scheduler init
(xen-4.2.1/xen/common/sched_credit.c:2000-2031 clamps a bad
sched_credit_tslice_us before the scheduler runs).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_worker(env_extra: dict, timeout: float = 60.0):
    """Run bench.py in tiny (rehearsal) mode on the CPU the test
    session pins, returning (rc, stdout, stderr, seconds)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PBST_BENCH_")}
    env.update({"PBST_BENCH_TINY": "1", **env_extra})
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True,
        text=True, timeout=timeout, env=env, cwd=REPO)
    return proc.returncode, proc.stdout, proc.stderr, time.perf_counter() - t0


@pytest.mark.parametrize("env,msg", [
    ({"PBST_BENCH_BATCH": "8x"}, "PBST_BENCH_BATCH must be an int"),
    ({"PBST_BENCH_BATCH": "0"}, "PBST_BENCH_BATCH must be >= 1"),
    ({"PBST_BENCH_LOSS_CHUNKS": "3"}, "must divide seq=128"),
    ({"PBST_BENCH_ATTN": "flash"}, "PBST_BENCH_ATTN must be xla|pallas"),
    ({"PBST_BENCH_REMAT": "selective"},
     "PBST_BENCH_REMAT must be none|dots|full"),
])
def test_bad_knob_fails_fast_without_backend(env, msg):
    rc, out, err, dt = _run_worker(env, timeout=30.0)
    assert rc != 0
    assert msg in err, err[-500:]
    # Fail-fast invariant: no backend init, no compile. The knob check
    # runs before `import jax`, so even CPU-backend markers must be
    # absent and the process must die well under compile timescales.
    assert "backend init" not in err, err[-500:]
    assert dt < 20.0, f"bad knob took {dt:.1f}s to fail"


@pytest.mark.parametrize("bad", ["0", "-4", "8,0", "4,-2,8"])
def test_sweep_rejects_non_positive_batches(bad):
    """PBST_SWEEP_BATCHES with a value < 1 must fail fast with the
    error JSON — not surface as per-point error rows after burning
    chip time."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PBST_SWEEP_")}
    env.update({"PBST_SWEEP_TINY": "1", "PBST_SWEEP_BATCHES": bad})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_sweep.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 1
    assert "must be ints >= 1" in proc.stdout
    # fail-fast: no sweep point ran (no tokens_per_s rows)
    assert "tokens_per_s" not in proc.stdout


@pytest.mark.slow  # ~8 s knob-sweep soak (tier-1 wall rescue)
def test_good_knobs_reach_result_with_extras():
    rc, out, err, _ = _run_worker(
        {"PBST_BENCH_BATCH": "2", "PBST_BENCH_LOSS_CHUNKS": "4",
         "PBST_BENCH_REMAT": "none"}, timeout=300.0)
    assert rc == 0, err[-800:]
    import json

    line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
    result = json.loads(line)
    assert result["value"] > 0
    # A CPU run is a rehearsal: marked, named for its platform, and
    # never under the device metric's name or with a utilization.
    assert result["metric"] == "rehearsal_flagship_train_throughput"
    assert result["rehearsal"] is True and result["platform"] == "cpu"
    assert "mfu" not in result and "vs_baseline" not in result
    # The result JSON must name every non-default knob so an artifact
    # can never be mistaken for the default-config headline.
    assert result["batch"] == 2
    assert result["loss_chunks"] == 4
    assert result["remat"] == "none"


def test_bench_exits_nonzero_without_a_tpu():
    """No TPU and no rehearsal asked for: bench.py names the platform
    it found and exits non-zero before building a model — a
    measurement path does not fall back to the CPU."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PBST_BENCH_")}
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=60, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stderr and "not a TPU" in proc.stderr
    assert proc.stdout.strip() == ""
    assert "params initialized" not in proc.stderr
