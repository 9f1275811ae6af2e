"""Engine admission: 95th percentile of the wait from a request's
scheduled arrival to the engine's ``admitted`` emit, over the requests
offered in the window that were admitted, in ms."""

from chipbench.serve import nearest_rank


def read(rec):
    waits = [s.admitted - s.due for s in rec.offered()
             if s.admitted is not None]
    p = nearest_rank(waits, 0.95)
    return None if p is None else p * 1e3
