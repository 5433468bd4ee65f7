"""Host time inside the program's ``ingest.maintain`` spans (Algorithm
1's split/dissolve fixpoint after each block) per 1,000 operations
applied in the window."""

from bench.counters import span_ms_per_kop


def read(run):
    return span_ms_per_kop(run, "ingest.maintain")
