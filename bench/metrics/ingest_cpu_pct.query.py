"""Share (%) of the host time in poll's block-apply loop that the ingest
thread spent on the CPU (engine counters ``ingest_cpu_s_total`` over
``ingest_wall_s_total``): well under 100 % means it waited, for the
interpreter lock, a core or memory."""

from bench.counters import growth


def read(run):
    cpu, wall = growth(run, "ingest_cpu_s_total"), growth(run, "ingest_wall_s_total")
    return None if cpu is None or not wall else 100.0 * cpu / wall
