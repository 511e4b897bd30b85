"""The share of the rows of the window's prompt forwards that were
padding: 100 x (1 - sum of ``ENG_ADMIT.prompt_len`` / sum of
``ENG_PREFILL.rows``) over the prefills that ran a forward (``rows`` is
0 where a prefix hit installed cached keys), joined by the engine's
request id within a ring.

``None`` where the program keeps no such records, where a ring lost
records inside the window, where the window ran no prompt forward, or
where a record's sixth field is smaller than its prompt: that program
still writes the prefix-hit flag (0 / 1) there, not the rows.
"""
from collections import Counter

from benchmarks.readers import _ring


def read(ctx):
    v = _ring.view(ctx)
    if v is None or not v.ok:
        return None
    rungs: Counter = Counter()
    tokens = 0
    for name, recs in v.rings.items():
        whole = v.whole[name]   # an admission starts before its prefill
        plen = {r[3]: r[5] for r in
                whole[whole[:, 1] == int(v.Ev.ENG_ADMIT)].tolist()}
        for r in recs[recs[:, 1] == int(v.Ev.ENG_PREFILL)].tolist():
            rows, n = r[7], plen.get(r[3])
            if rows == 0 or n is None:
                continue
            if rows < n:
                return None
            rungs[rows] += 1
            tokens += n
    total = sum(rows * k for rows, k in rungs.items())
    if not total:
        return None
    if "_pad_line" not in vars(ctx):
        vars(ctx)["_pad_line"] = True
        print("ring: prompt forwards by rows: " + ", ".join(
            f"{rows} x {k}" for rows, k in sorted(rungs.items()))
            + f"; {tokens} prompt tokens in {total} rows", flush=True)
    return 100.0 * (1 - tokens / total)
