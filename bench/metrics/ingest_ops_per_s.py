"""Inserts plus expiries applied in the window, over its seconds (the
ε-passes the stream triggers inline included)."""


def read(run):
    return run.ops_applied / run.window_s if run.steps else None
