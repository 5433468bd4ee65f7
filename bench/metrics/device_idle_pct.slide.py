"""Share (%) of the traced window in which no program ran on the chip."""


def read(run):
    tr = run.trace
    return None if tr is None else 100.0 * (1.0 - tr.busy_s / tr.window_s)
