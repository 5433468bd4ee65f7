"""Host time inside the program's ``ingest.cf`` spans (each block's
clustering-feature bookkeeping: pids, per-leaf CF sums, membership lists,
the internal nodes' CFs) per 1,000 operations applied in the window."""

from bench.counters import span_ms_per_kop


def read(run):
    return span_ms_per_kop(run, "ingest.cf")
