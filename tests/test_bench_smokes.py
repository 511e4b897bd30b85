"""Tiny-mode rehearsals for every root bench script.

A script that regresses fails on the CHIP, and chip minutes are
budgeted. Each script has a tiny rehearsal mode for exactly this
reason; this module pins that every script still runs end to end on
the CPU and emits its row shape — marked as a rehearsal, never under a
device metric's name. (bench.py itself is covered by test_bench_knobs.)

The scripts run as children of a pytest parent that has touched JAX:
fine on the CPU, and the reason tpu_tests/ and chip_smoke.py are never
run from this tree — a chip belongs to one process.
"""

from __future__ import annotations

import json
import os
import subprocess

import pytest
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, env_extra: dict, timeout: float = 900.0):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PBST_BENCH_", "PBST_SWEEP_",
                                "PBST_LONGCTX_", "PBST_DECOMP_"))}
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)
    rows = []
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            rows.append(json.loads(ln))
    return proc, rows


@pytest.mark.slow  # ~20 s eight-row engine matrix sweep
def test_bench_serving_tiny_covers_the_matrix():
    proc, rows = _run("bench_serving.py", {"PBST_BENCH_TINY": "1"})
    assert proc.returncode == 0, proc.stderr[-800:]
    metrics = {r["metric"] for r in rows}
    assert "rehearsal_serving_prefill_ms" in metrics
    assert "rehearsal_serving_decode_throughput" in metrics
    assert all(r["rehearsal"] and r["platform"] == "cpu" for r in rows)
    # the full {dense, MoE} x {plain, spec} x {bf16, int8} engine
    # matrix minus interpreter-hostile cells (none: all engines are
    # XLA) — 8 rows, none allowed to be an error row on CPU
    engine_rows = [r for r in rows if "continuous" in r["metric"]]
    assert len(engine_rows) == 8, sorted(metrics)
    errs = [r for r in engine_rows if "error" in r]
    assert not errs, errs
    # Self-draft spec rows (bf16 dense, dropless MoE) are exact on the
    # CPU's deterministic f32 path: acceptance must be ~1.0 (a draft
    # with unrelated weights measures the acceptance FLOOR instead).
    for m in ("rehearsal_serving_spec_continuous_bf16_throughput",
              "rehearsal_serving_spec_continuous_moe_dropless_throughput"):
        row = next(r for r in engine_rows if r["metric"] == m)
        assert row["acceptance"] >= 0.9, row


@pytest.mark.slow  # ~9 s longctx smoke (tier-1 wall rescue)
def test_bench_longctx_tiny_emits_points():
    proc, rows = _run("bench_longctx.py", {"PBST_LONGCTX_TINY": "1"})
    assert proc.returncode == 0, proc.stderr[-800:]
    ok = [r for r in rows if "tokens_per_s" in r]
    assert ok, rows


@pytest.mark.slow  # ~25 s roofline-section sweep
def test_bench_decompose_tiny_emits_sections():
    proc, rows = _run("bench_decompose.py", {"PBST_DECOMP_TINY": "1"})
    assert proc.returncode == 0, proc.stderr[-800:]
    assert len(rows) >= 3, rows
