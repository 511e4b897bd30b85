"""The generator is deterministic in --seed, and every seed gets the
same work in another order."""
import numpy as np

from benchmarks.harness.spec import Spec
from benchmarks.harness.traffic import (Requests, arrivals, length_pool,
                                        train_rows)

SURGE = Spec().traffic("chat-surge")["serve"]
STEADY = Spec().traffic("chat-steady")["serve"]


def take(seed, n):
    r = Requests(SURGE, 1000, seed)
    return [next(r) for _ in range(n)]


def test_requests_repeat_for_a_seed_and_differ_between_seeds():
    a, b, c = take(7, 40), take(7, 40), take(8, 40)
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))
    assert any((x[0][:4] != y[0][:4]).any() or len(x[0]) != len(y[0])
               for x, y in zip(a, c))


def test_every_seed_draws_the_same_lengths_per_epoch():
    n = SURGE["pool"]
    for seed in (1, 2 ** 31 + 5):
        reqs = take(seed, n)
        assert sorted(len(p) for p, _ in reqs) == \
            sorted(length_pool(SURGE["prompt_len"], n).tolist())
        assert sorted(o for _, o in reqs) == \
            sorted(length_pool(SURGE["output_len"], n).tolist())


def test_rotate_offers_every_seed_the_same_pattern_from_another_phase():
    assert SURGE["order"] == "rotate"
    n = SURGE["pool"]
    lens = {}
    for seed in (5, 6, 2 ** 31 + 7):
        lens[seed] = [(len(p), o) for p, o in take(seed, 2 * n)]
        assert lens[seed][:n] == lens[seed][n:]          # periodic
    a, b = lens[5], lens[6]
    assert any(a[k:k + n] == b[:n] for k in range(n))    # a rotation
    gaps = [np.diff(arrivals(STEADY, s, 60.0)) for s in (5, 6)]
    assert any(np.allclose(gaps[0][k:k + n], gaps[1][:n])
               for k in range(n))


def test_lengths_respect_the_clip():
    p = length_pool(SURGE["prompt_len"], 256)
    o = length_pool(SURGE["output_len"], 256)
    assert p.min() >= 16 and p.max() == 512
    assert o.min() == 32 and o.max() == 256
    assert abs(np.median(p) - 128) <= 2 and abs(np.median(o) - 64) <= 1


def test_arrivals_are_seeded_sorted_and_at_the_rate():
    a, b = arrivals(STEADY, 3, 100.0), arrivals(STEADY, 3, 100.0)
    assert a == b and a == sorted(a) and a != arrivals(STEADY, 4, 100.0)
    assert abs(len(a) / 100.0 - STEADY["rate_rps"]) < 0.05 * STEADY["rate_rps"]


def test_bursts_carry_their_factor():
    a = arrivals(SURGE, 11, 200.0)
    b = SURGE["burst"]
    on = sum(1 for t in a if t % b["period_s"] < b["on_s"])
    rate_on = on / (200.0 * b["on_s"] / b["period_s"])
    rate_off = (len(a) - on) / (200.0 * (1 - b["on_s"] / b["period_s"]))
    assert abs(rate_on / rate_off - b["factor"]) < 0.25


def test_train_rows_all_differ():
    rows = train_rows({"pool_rows": 32, "batch": 1, "seq": 64}, 512, 5)
    assert rows.shape == (32, 1, 64)
    assert len({r.tobytes() for r in rows}) == 32
    assert (rows == train_rows({"pool_rows": 32, "batch": 1, "seq": 64},
                               512, 5)).all()


def test_closed_loop_ramps_its_clients(monkeypatch):
    """Four clients over two seconds: one at once, one more every half
    second; a completion is answered with a new request at any time;
    with a ramp of 0 s all start together."""
    from benchmarks.harness import tenancy

    class FakeBook:
        completions = 0

        def expect(self, *_a, **_kw):
            pass

    clock = [100.0]
    monkeypatch.setattr(tenancy, "now", lambda: clock[0])
    serve = dict(Spec().traffic("colo-chat")["serve"], clients=4, ramp_s=2.0)
    book = FakeBook()
    loop = tenancy.ClosedLoop(serve, book, Requests(serve, 512, 1))
    sent = []
    for t, completions in ((100.0, 0), (100.2, 0), (100.5, 0), (100.9, 1),
                           (101.6, 1), (107.0, 3)):
        clock[0], book.completions = t, completions
        sent.append(len(loop.feed(0)))
    assert sent == [1, 0, 1, 1, 2, 2]
    serve["ramp_s"] = 0
    assert len(tenancy.ClosedLoop(serve, FakeBook(), Requests(
        serve, 512, 1)).feed(0)) == 4
