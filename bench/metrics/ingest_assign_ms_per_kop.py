"""Host time inside the program's ``ingest.assign`` spans (each insert
block's point -> leaf assignment: leaf table, pad, upload, the ``assign``
kernel, fetch) per 1,000 operations applied in the window."""

from bench.counters import span_ms_per_kop


def read(run):
    return span_ms_per_kop(run, "ingest.assign")
