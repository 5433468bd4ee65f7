"""Internal nodes whose clustering features the Bubble-tree's block paths
recomputed from their children, per 1,000 operations applied in the window
(engine counter ``tree_cf_refreshes``)."""

from bench.counters import growth, per_kop


def read(run):
    return per_kop(run, growth(run, "tree_cf_refreshes"))
