"""``benchmarks/readers/prefill_pad_pct.py`` on hand-made rings: the
hand-made share, and ``None`` where the sixth field of ``ENG_PREFILL``
is still the prefix-hit flag of the program before the ladder."""
import types

import pytest

from benchmarks.harness.spec import Spec
from pbs_tpu.obs import trace as pt
from pbs_tpu.obs.trace import Ev, TraceBuffer


def pad_context(records, monkeypatch):
    """A window of [1000, 10**6) ns over one engine ring holding
    ``records`` (``(ts, event, *args)``)."""
    eng = TraceBuffer(64)
    for ts, ev, *args in records:
        eng.emit(ts, ev, *args)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", eng)])
    return types.SimpleNamespace(events=None, trace_span=None,
                                 t0=1e-6, t1=1e-3)


def admission(ts, rid, plen, sixth):
    """One request's pair as the engine writes it: the prefill inside
    its admission, the admission's record after it."""
    return [(ts + 10, Ev.ENG_PREFILL, 1, rid, 0, 50, 500, sixth),
            (ts, Ev.ENG_ADMIT, 1, rid, 0, plen, 7, 900)]


def test_prefill_pad_pct_is_the_hand_made_share(monkeypatch):
    read = Spec().reader("prefill_pad_pct")
    ctx = pad_context(
        admission(2000, 0, 100, 256) + admission(3000, 1, 300, 512)
        + admission(4000, 2, 40, 0)          # a prefix hit ran no forward
        + admission(5000, 3, 256, 256)
        # admitted before the window, its forward inside it: counted
        + [(1005, Ev.ENG_PREFILL, 0, 4, 1, 50, 500, 512),
           (995, Ev.ENG_ADMIT, 0, 4, 1, 344, 7, 900)]
        # a forward after the window closed: not counted
        + admission(2 * 10**6, 5, 1, 512), monkeypatch)
    rows, tokens = 256 + 512 + 256 + 512, 100 + 300 + 256 + 344
    assert read(ctx) == pytest.approx(100 * (1 - tokens / rows))
    assert read(ctx) == pytest.approx(34.8958333)


def test_prefill_pad_pct_is_none_on_the_hit_flag(monkeypatch):
    """The parent writes 0 / 1 (prefix hit) where the rows now are: all
    zeros is a window without a forward, a 1 is fewer rows than any
    prompt has, and neither is a share."""
    read = Spec().reader("prefill_pad_pct")
    no_cache = admission(2000, 0, 100, 0) + admission(3000, 1, 300, 0)
    assert read(pad_context(no_cache, monkeypatch)) is None
    hits = no_cache + admission(4000, 2, 100, 1)
    assert read(pad_context(hits, monkeypatch)) is None
    monkeypatch.delattr(pt, "live_rings")    # a program without rings
    assert read(types.SimpleNamespace(t0=0.0, t1=1.0, events=None)) is None
