"""The benchmark's own stamps on the serving engine.

``StampingBatcher`` is the program's ``ContinuousBatcher`` with two
methods wrapped from outside: ``submit`` (to know which request an
engine id is) and ``step`` (to look at the slot table when a tick
returns). Output tokens become visible to the host when ``step()``
returns, so that is when they are stamped; a tick that prefills a
request and then decodes stamps its first two tokens together. The
engine's own TTFT ring (last 1024) and the gateway's log2 histograms
are not read: neither can carry a tail.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from pbs_tpu.models.serving import ContinuousBatcher

now = time.monotonic


class Book:
    """Requests by prompt (prompts are random, so unique) and ticks."""

    def __init__(self):
        self.requests: list[dict] = []
        self._by_prompt: dict[bytes, dict] = {}
        self._by_rid: dict[int, dict] = {}
        self.ticks: list[tuple] = []  # (t_in, t_out, busy_slots, live_pos)
        self.completions = 0

    def expect(self, prompt: np.ndarray, max_new: int, due: float) -> dict:
        rec = {"due": due, "submit": None, "admit": None, "stamps": [],
               "done": None, "prompt": prompt, "max_new": max_new,
               "tokens": None, "n": 0, "shed": False}
        self.requests.append(rec)
        self._by_prompt[prompt.tobytes()] = rec
        return rec

    def on_submit(self, rid: int, prompt_bytes: bytes) -> None:
        rec = self._by_prompt.pop(prompt_bytes, None)
        if rec is not None:
            rec["submit"] = now()
            self._by_rid[rid] = rec

    def on_step(self, eng, t_in: float, t_out: float, done) -> None:
        live = 0
        for slot in range(eng.n_slots):
            rid = eng.slot_req[slot]
            if rid is None:
                continue
            n = len(eng.slot_tokens[slot])
            live += int(eng.slot_prompt_len[slot]) + n
            rec = self._by_rid.get(rid)
            if rec is not None:
                self._see(rec, n, t_in, t_out)
        for comp in done:
            rec = self._by_rid.pop(comp.request_id, None)
            if rec is not None:
                self._see(rec, len(comp.tokens), t_in, t_out)
                rec["done"] = t_out
                rec["tokens"] = [int(t) for t in comp.tokens]
        self.completions += len(done)
        busy = int(eng.active.sum()) + len(done)
        self.ticks.append((t_in, t_out, busy, live))

    @staticmethod
    def _see(rec: dict, n: int, t_in: float, t_out: float) -> None:
        if rec["admit"] is None:
            rec["admit"] = t_in
        if n > rec["n"]:
            rec["stamps"].append((t_out, n))
            rec["n"] = n


def _annotated(fn, label: str):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **kw)

    return call


class StampingBatcher(ContinuousBatcher):
    book: Book | None = None

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        # Host spans around the two dispatches inside a tick, where the
        # engine keeps them as attributes (it does today).
        for attr, label in (("_prefill_fn", "bench.prefill"),
                            ("_decode_fn", "bench.decode")):
            fn = getattr(self, attr, None)
            if fn is not None:
                setattr(self, attr, _annotated(fn, label))

    def submit(self, prompt, max_new_tokens: int) -> int:
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = super().submit(prompt, max_new_tokens)
        if self.book is not None:
            self.book.on_submit(
                rid, np.asarray(prompt, np.int32).reshape(-1).tobytes())
        return rid

    def step(self):
        if self.book is None:
            return super().step()
        t_in = now()
        with jax.profiler.TraceAnnotation("bench.serve_step"):
            done = super().step()
        self.book.on_step(self, t_in, now(), done)
        return done
