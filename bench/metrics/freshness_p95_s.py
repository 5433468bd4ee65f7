"""95th percentile, over every operation submitted in the window, of the
time from its submission to the first published snapshot that covers it;
an operation still uncovered at the window's end counts until then."""

from bench.harness import weighted_percentile


def read(run):
    if not run.steps:
        return None
    lags, ops = run.op_lags()
    return weighted_percentile(lags, ops, 95.0)
