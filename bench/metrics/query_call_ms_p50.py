"""Median time of the call into ``query_detailed``, from the call to its
return (the wait before the call left out)."""

import numpy as np


def read(run):
    t = [q.end - q.start for q in run.queries if q.result is not None]
    return float(np.median(t)) * 1e3 if t else None
