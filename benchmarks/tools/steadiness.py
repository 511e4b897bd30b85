"""The steadiness study: from the token stamps that ``run.py --dump``
wrote for two sets of runs of one cell, every candidate statistic on the
same runs, at several window lengths.

    python3 benchmarks/tools/steadiness.py <cell> <dir of set 1> <dir of set 2> [seconds ...]

A spread is the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, which
is how the driver reads one; a bound is about five times the wider of
the two sets' spreads.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import reduce  # noqa: E402


def spread(values) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def stats_of(run: dict, seconds: float) -> dict:
    t0, t1 = run["t0"], run["t0"] + seconds
    reqs = run["requests"]
    out = {}
    tpot = reduce.quantity(reqs, "tpot_ms", t0, t1)
    gaps = reduce.quantity(reqs, "token_gap_ms", t0, t1)
    if tpot:
        out["completed"] = len(tpot)
        out["tpot_p50_ms"] = reduce.percentile(tpot, 50)
        out["tpot_p95_ms"] = reduce.percentile(tpot, 95)
        out["gap_p50_ms"] = reduce.percentile(gaps, 50)
        out["gap_p95_ms"] = reduce.percentile(gaps, 95)
        out["gap_p99_ms"] = reduce.percentile(gaps, 99)
        out["output_tokens_per_s"] = sum(
            reduce.tokens_in(r["stamps"], t0, t1) for r in reqs) / seconds
    ttft = reduce.quantity(reqs, "ttft_ms", t0, t1)
    if ttft:
        out["ttft_p95_ms"] = reduce.percentile(ttft, 95)
    steps = [t for t in run.get("train_step_times", []) if t0 <= t < t1]
    if steps:
        out["train_tokens_per_s"] = (len(steps)
                                     * run["train_tokens_per_step"] / seconds)
    if tpot:
        out.update(interleave_of(run, steps, tpot, gaps, t0, t1))
    return out


def interleave_of(run: dict, steps, tpot, gaps, t0: float, t1: float) -> dict:
    """Where a co-located cell's spread could come from: how the
    scheduler interleaved engine ticks with train steps (over the whole
    window and over its 5 s pieces), what a tick and a step cost on the
    wall, how many requests the window admitted and completed, and the
    serving statistics that are averages beside those that are tails."""
    ticks = [t for t in run["ticks"] if t0 <= t[0] < t1]
    out = {"admitted": sum(1 for r in run["requests"]
                           if r["admit"] is not None
                           and t0 <= r["admit"] < t1),
           "ticks": len(ticks),
           "tick_wall_ms_p50": reduce.percentile(
               [(t[1] - t[0]) * 1e3 for t in ticks], 50),
           "tpot_p90_ms": reduce.percentile(tpot, 90),
           "tpot_p99_ms": reduce.percentile(tpot, 99),
           "tpot_mean_ms": statistics.fmean(tpot),
           "gap_mean_ms": statistics.fmean(gaps)}
    if steps:
        out["train_steps"] = len(steps)
        out["ticks_per_step"] = len(ticks) / len(steps)
        # between two ticks with exactly one train step dispatched
        one = [(b[0] - a[1]) * 1e3 for a, b in zip(ticks, ticks[1:])
               if sum(1 for s in steps if a[1] <= s < b[0]) == 1]
        if one:
            out["step_wall_ms_p50"] = reduce.percentile(one, 50)
        pieces = []
        for k in range(int((t1 - t0) // 5)):
            lo, hi = t0 + 5 * k, t0 + 5 * k + 5
            n_steps = sum(1 for s in steps if lo <= s < hi)
            if n_steps:
                pieces.append(sum(1 for t in ticks if lo <= t[0] < hi)
                              / n_steps)
        if pieces:
            out["ticks_per_step_5s_min"] = min(pieces)
            out["ticks_per_step_5s_max"] = max(pieces)
    return out


def histogram(run: dict, width_ms: float = 5.0) -> dict:
    gaps = reduce.quantity(run["requests"], "token_gap_ms", run["t0"],
                           run["t1"])
    hist: dict[float, int] = {}
    for g in gaps:
        b = width_ms * int(g // width_ms)
        hist[b] = hist.get(b, 0) + 1
    return {"n": len(gaps), "bins": dict(sorted(hist.items()))}


def main(argv) -> None:
    cell, dir1, dir2 = argv[:3]
    sets = [[json.load(open(p)) for p in sorted(
        glob.glob(os.path.join(d, f"{cell}.*.json")))
        if not p.endswith(".trace.json")] for d in (dir1, dir2)]
    full = sets[0][0]["t1"] - sets[0][0]["t0"]
    lengths = [float(x) for x in argv[3:]] or [round(full)]
    for seconds in lengths:
        print(f"\n### window {seconds:g} s ({len(sets[0])} + "
              f"{len(sets[1])} runs)\n")
        rows = [[stats_of(r, seconds) for r in s] for s in sets]
        print("| statistic | set 1 median | set 1 spread | set 2 median | "
              "set 2 spread | medians apart | bound = 5 x wider |")
        print("|---|---|---|---|---|---|---|")
        for key in rows[0][0]:
            a, b = ([r[key] for r in s] for s in rows)
            ma, mb = statistics.median(a), statistics.median(b)
            sa, sb = spread(a), spread(b)
            print(f"| {key} | {ma:.4g} | {100 * sa:.2f}% | {mb:.4g} | "
                  f"{100 * sb:.2f}% | {100 * abs(mb - ma) / ma:.2f}% | "
                  f"{500 * max(sa, sb):.1f}% |")
        for i, s in enumerate(rows, 1):
            for key in ("tpot_p50_ms", "tpot_p95_ms", "gap_p95_ms",
                        "gap_p99_ms", "ticks_per_step", "completed"):
                if key in s[0]:
                    print(f"set {i} {key}: "
                          + " ".join(f"{r[key]:.4g}" for r in s))
    h = histogram(sets[0][0])
    print(f"\ngap histogram of the first run ({h['n']} gaps, 5 ms bins): "
          + ", ".join(f"{int(b)}:{n}" for b, n in h["bins"].items()))


if __name__ == "__main__":
    main(sys.argv[1:])
