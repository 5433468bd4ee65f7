"""Borůvka rounds per ε-pass in the window: the growth of the engine's
``mst_rounds_total`` over the growth of its ``recluster_count``.  A
program whose passes do not count their rounds gives None."""

from bench.counters import growth


def read(run):
    rounds, passes = growth(run, "mst_rounds_total"), growth(run, "recluster_count")
    if rounds is None or not passes:
        return None
    return rounds / passes
