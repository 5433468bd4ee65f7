"""Mean wall time of one ε-pass in the window: the growth of the engine's
``offline_seconds_total`` over the growth of its ``recluster_count``."""


def read(run):
    if not run.stats1:
        return None
    n = run.stats1["recluster_count"] - run.stats0["recluster_count"]
    if n <= 0:
        return None
    return 1e3 * (run.stats1["offline_seconds_total"] - run.stats0["offline_seconds_total"]) / n
