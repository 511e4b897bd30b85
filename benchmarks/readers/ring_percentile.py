"""A percentile of one quantity of the program's own ring records over
the measured window (``_ring.quantity``), in ``unit_ns`` nanoseconds.
``None`` where the program keeps no such records, or where a ring lost
records inside the window: a tail over a torn sample is a wrong number.
"""
from benchmarks.harness import reduce
from benchmarks.readers import _ring


def read(ctx, quantity: str, q: float, unit_ns: float, job: str = None):
    v = _ring.view(ctx)
    if v is None or not v.ok:
        return None
    vals = _ring.quantity(v, quantity, job=job)
    return reduce.percentile(vals, q) / unit_ns if vals else None
