"""Process start to the start of the measured window: data, fill, the
forced pass, warm-up and every compile they need."""


def read(run):
    return run.setup_s
