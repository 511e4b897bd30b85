"""One general traffic generator, driven by a traffic file's parameters.

Every seed gets the *same* work in another order: lengths and
inter-arrival gaps are the quantiles of their distributions (a pool of
``pool`` values, fixed by the file), and ``--seed`` only permutes each
successive epoch of the pool and draws the token values. Two runs with
different seeds then differ by order, not by how much work they drew.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def length_pool(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of ``spec``'s distribution."""
    if spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _rotation(serve: dict, seed: int, stream: int):
    """``"order": "rotate"``: one permutation of the pool fixed by the
    file's ``order_seed``, which ``--seed`` only rotates (the same
    rotation for lengths and gaps). An open loop then offers every seed
    the same periodic pattern from another phase: a queue's tail depends
    on which long prompts and short gaps fall together, so a free
    permutation changes the work. Without the key (``None`` here) every
    epoch is freshly permuted from the seed."""
    if serve.get("order", "permute") != "rotate":
        return None
    n = int(serve["pool"])
    base = np.random.default_rng(
        [int(serve["order_seed"]), stream]).permutation(n)
    return np.roll(base, -int(np.random.default_rng([seed, 5]).integers(n)))


class Requests:
    """The endless, seeded sequence of ``(prompt, max_new)`` requests."""

    def __init__(self, serve: dict, vocab: int, seed: int):
        n = int(serve["pool"])
        self._plens = length_pool(serve["prompt_len"], n)
        self._olens = length_pool(serve["output_len"], n)
        self._orders = (_rotation(serve, seed, 11),
                        _rotation(serve, seed, 12))
        self._rng = np.random.default_rng([seed, 1])
        self._vocab = vocab
        self._epoch: list[tuple[int, int]] = []

    def __next__(self) -> tuple[np.ndarray, int]:
        if not self._epoch:
            if self._orders[0] is None:
                p = self._rng.permutation(self._plens)
                o = self._rng.permutation(self._olens)
            else:
                p = self._plens[self._orders[0]]
                o = self._olens[self._orders[1]]
            self._epoch = list(zip(p.tolist(), o.tolist()))[::-1]
        plen, olen = self._epoch.pop()
        prompt = self._rng.integers(1, self._vocab, size=plen,
                                    dtype=np.int32)
        return prompt, int(olen)

    def __iter__(self):
        return self


def _rate_and_edge(serve: dict, t: float) -> tuple[float, float]:
    """The arrival rate at ``t`` and how long it still holds."""
    rate, burst = float(serve["rate_rps"]), serve.get("burst")
    if not burst:
        return rate, math.inf
    phase = t % burst["period_s"]
    if phase < burst["on_s"]:
        return rate * burst["factor"], burst["on_s"] - phase
    return rate, burst["period_s"] - phase


def arrivals(serve: dict, seed: int, horizon_s: float) -> list[float]:
    """Open-loop due times in ``[0, horizon_s)``: a Poisson process of
    rate ``rate_rps`` (times ``burst.factor`` during the first
    ``burst.on_s`` of every ``burst.period_s``), realized by stretching
    unit-rate exponential gaps (the pool's quantiles, permuted) over the
    rate profile."""
    n = int(serve["pool"])
    unit = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    unit /= unit.mean()
    order, rng = _rotation(serve, seed, 13), np.random.default_rng([seed, 2])
    out, t, gaps = [], 0.0, []
    while True:
        if not gaps:
            gaps = (rng.permutation(unit) if order is None
                    else unit[order][::-1]).tolist()
        need = gaps.pop()  # unit-rate time until the next arrival
        while True:
            rate, edge = _rate_and_edge(serve, t)
            if need <= rate * edge:
                t += need / rate
                break
            need -= rate * edge
            t += edge
        if t >= horizon_s:
            return out
        out.append(t)


def train_rows(train: dict, vocab: int, seed: int) -> np.ndarray:
    """``(pool_rows, batch, seq)`` token rows, all different, from the
    seed: the trainer takes the next one each step."""
    rng = np.random.default_rng([seed, 3])
    return rng.integers(0, vocab, size=(int(train["pool_rows"]),
                                        int(train["batch"]),
                                        int(train["seq"])), dtype=np.int32)
