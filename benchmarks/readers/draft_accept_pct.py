"""Of the drafts the window's decodes proposed, the share the stack's
own argmax confirmed, in percent: accepted over proposed, both summed
over the program's ``ENG_DRAFT`` records of the window (one a decode
the host read: lanes, drafts proposed, drafts accepted, tokens booked,
tokens dropped). With weights drawn from a seed a draft is accepted by
chance; a file of trained weights changes this number and no code.
``None`` where the program keeps no such records (a program without a
drafting block, or one from before the PR that added them)."""
import numpy as np

from benchmarks.readers import _ring

#: ``ENG_DRAFT``'s arguments in a ring record (ts, event, six of them).
FIELDS = {"lanes": 3, "proposed": 4, "accepted": 5, "booked": 6,
          "dropped": 7}


def drafts(ctx):
    """``(n, 8)`` ``ENG_DRAFT`` records of the window; ``None`` where
    the program keeps none or a ring lost records inside the window."""
    v = _ring.view(ctx)
    if v is None or not v.ok or not hasattr(v.Ev, "ENG_DRAFT"):
        return None
    return np.concatenate(v.rows(v.Ev.ENG_DRAFT) or [np.empty((0, 8))])


def read(ctx):
    recs = drafts(ctx)
    if recs is None or not len(recs):
        return None
    proposed = recs[:, FIELDS["proposed"]].sum()
    print(f"ring: drafting, decodes read {len(recs)}, drafts proposed "
          f"{int(proposed)}, accepted "
          f"{int(recs[:, FIELDS['accepted']].sum())}, tokens booked "
          f"{int(recs[:, FIELDS['booked']].sum())}, dropped "
          f"{int(recs[:, FIELDS['dropped']].sum())}", flush=True)
    return 100.0 * recs[:, FIELDS["accepted"]].sum() / proposed \
        if proposed else None
