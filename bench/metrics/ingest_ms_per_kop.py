"""Host time of the online summary per 1,000 operations: the benchmark's
span around ``poll`` less the window's growth of the engine's
``offline_seconds_total`` (the ε-passes the polls ran)."""


def read(run):
    if not run.polls or run.ops_applied == 0:
        return None
    poll_s = sum(t1 - t0 for t0, t1 in run.polls)
    offline_s = run.stats1["offline_seconds_total"] - run.stats0["offline_seconds_total"]
    return 1e3 * (poll_s - offline_s) / (run.ops_applied / 1e3)
