"""TPOT and the pooled gap on a hand-made lattice of stamps.

A decode tick is 10 ms; a train step between two ticks adds 40 ms, so a
gap is 10 (tick after tick), 50 (one step between) or 90 (two). Each
request below has 100 tokens; its gaps are listed by count.
"""
import pytest

from benchmarks.harness import reduce


def request(gaps_ms, t_start=0.0, due=None):
    t, stamps = t_start, [(t_start, 1)]
    for i, g in enumerate(gaps_ms, start=2):
        t += g / 1e3
        stamps.append((t, i))
    return {"due": t_start if due is None else due, "admit": t_start,
            "stamps": stamps, "done": t, "tokens": list(range(len(stamps)))}


def lattice(n10, n50, n90):
    return [10.0] * n10 + [50.0] * n50 + [90.0] * n90


def pooled(requests, q):
    return reduce.percentile(
        reduce.quantity(requests, "token_gap_ms", 0.0, 1e9), q)


def tpot95(requests):
    return reduce.percentile(
        reduce.quantity(requests, "tpot_ms", 0.0, 1e9), 95)


def test_percentile_is_nearest_rank():
    assert reduce.percentile([1, 2, 3, 4], 50) == 2
    assert reduce.percentile(range(1, 101), 95) == 95
    assert reduce.percentile([5], 99) == 5
    with pytest.raises(ValueError):
        reduce.percentile([], 50)


def test_tpot_of_one_request_by_hand():
    # 99 gaps: 33 x 10 + 62 x 50 + 4 x 90 = 3790 ms over 99 intervals
    r = request(lattice(33, 62, 4))
    assert reduce.tpot_s(r["stamps"]) * 1e3 == pytest.approx(3790 / 99)
    assert len(reduce.token_gaps(r["stamps"])) == 99


def test_tokens_that_share_a_stamp():
    # prefill + decode in one tick: tokens 1 and 2 arrive together
    stamps = [(1.0, 2), (1.01, 3), (1.06, 4)]
    assert reduce.token_times(stamps) == [1.0, 1.0, 1.01, 1.06]
    assert reduce.tpot_s(stamps) == pytest.approx(0.06 / 3)
    assert reduce.token_gaps(stamps) == pytest.approx([0.0, 0.01, 0.05])
    assert reduce.tpot_s([(1.0, 1)]) is None


def test_pooled_p95_jumps_a_lattice_step_and_tpot_does_not():
    """20 requests x 99 gaps = 1980 pooled gaps. With 4 two-step gaps a
    request, 4.04% of the pool is at 90 ms: p95 reads 50. Move one gap in
    a hundred from 50 to 90 (5 a request, 5.05%): p95 reads 90, a whole
    lattice step (+80%). Each request's TPOT moved by 40/99 ms (+1%)."""
    a = [request(lattice(33, 62, 4)) for _ in range(20)]
    b = [request(lattice(33, 61, 5)) for _ in range(20)]
    assert pooled(a, 95) == pytest.approx(50.0)
    assert pooled(b, 95) == pytest.approx(90.0)
    assert tpot95(a) == pytest.approx(3790 / 99)
    assert tpot95(b) == pytest.approx(3830 / 99)
    assert tpot95(b) / tpot95(a) < 1.011


def test_window_selects_by_completion_and_by_due_time():
    early = request(lattice(9, 0, 0), t_start=0.0)      # done at 0.09
    late = request(lattice(9, 0, 0), t_start=5.0)       # done at 5.09
    assert reduce.quantity([early, late], "tpot_ms", 1.0, 6.0) == \
        pytest.approx([10.0])
    waited = request(lattice(2, 0, 0), t_start=2.5, due=2.0)
    assert reduce.quantity([waited], "ttft_ms", 1.0, 3.0) == \
        pytest.approx([500.0])
    assert reduce.quantity([waited], "queue_wait_ms", 1.0, 3.0) == \
        pytest.approx([500.0])
    assert reduce.quantity([waited], "ttft_ms", 2.1, 3.0) == []
    assert reduce.tokens_in(waited["stamps"], 2.5, 2.515) == 2
