"""Of the positions the window's decode ticks' queries saw, the share a
selecting layer attended, in percent: chosen over live positions, both
summed over the program's ``ENG_SELECT`` records of those ticks (what
the choice leaves of the cache a dense attention would read). ``None``
where the program keeps no such records."""
from benchmarks.readers import select_roofline_pct as _select


def read(ctx):
    recs = _select.decode_selects(ctx)
    if recs is None or not len(recs):
        return None
    live = recs[:, _select.FIELDS["live"]].sum()
    return 100.0 * recs[:, _select.FIELDS["chosen"]].sum() / live \
        if live else None
