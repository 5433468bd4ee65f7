"""50th percentile, over the window's served requests, of the time in the
program's ``query.program`` spans of each (upload of the rows, the fused
query program, fetch of the result)."""

import numpy as np

from bench.counters import query_program_s


def read(run):
    t = query_program_s(run)
    return float(np.percentile(t, 50.0)) * 1e3 if t else None
