"""From stamps to the numbers a client feels. Pure functions of lists.

A request's record is ``{"due", "submit", "admit", "stamps", "done",
"prompt_len", "tokens"}``: times in seconds on one monotonic clock;
``stamps`` is a list of ``(t, n)``, "by time ``t`` the host had seen
``n`` output tokens of this request" (tokens become visible when an
engine tick returns, so several can share a stamp).
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, ``q`` in (0, 100]: the smallest value
    with at least q% of the sample at or below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]


def token_times(stamps) -> list[float]:
    """One time per output token, in order."""
    out, seen = [], 0
    for t, n in stamps:
        out.extend([t] * (n - seen))
        seen = n
    return out


def tpot_s(stamps) -> float | None:
    """A request's mean time per output token after the first:
    ``(t_last - t_first) / (tokens - 1)``."""
    times = token_times(stamps)
    if len(times) < 2:
        return None
    return (times[-1] - times[0]) / (len(times) - 1)


def token_gaps(stamps) -> list[float]:
    times = token_times(stamps)
    return [b - a for a, b in zip(times, times[1:])]


def tokens_in(stamps, t0: float, t1: float) -> int:
    return sum(1 for t in token_times(stamps) if t0 <= t < t1)


def completed_in(requests, t0: float, t1: float) -> list:
    return [r for r in requests
            if r.get("done") is not None and t0 <= r["done"] < t1]


def due_in(requests, t0: float, t1: float) -> list:
    return [r for r in requests if t0 <= r["due"] < t1]


def quantity(requests, name: str, t0: float, t1: float) -> list[float]:
    """The per-request (or pooled) sample a stamp metric takes its
    percentile of, in milliseconds, over the window ``[t0, t1)``."""
    if name == "tpot_ms":
        vals = (tpot_s(r["stamps"]) for r in completed_in(requests, t0, t1))
        return [v * 1e3 for v in vals if v is not None]
    if name == "token_gap_ms":
        return [g * 1e3 for r in completed_in(requests, t0, t1)
                for g in token_gaps(r["stamps"])]
    if name == "ttft_ms":
        return [(r["stamps"][0][0] - r["due"]) * 1e3
                for r in due_in(requests, t0, t1) if r["stamps"]]
    if name == "queue_wait_ms":
        return [(r["admit"] - r["due"]) * 1e3
                for r in due_in(requests, t0, t1)
                if r.get("admit") is not None]
    raise KeyError(f"unknown stamp quantity {name!r}")
