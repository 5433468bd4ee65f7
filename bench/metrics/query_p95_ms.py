"""95th percentile latency of the window's query requests, each from its
due time on the open-loop schedule to its result."""

import numpy as np


def read(run):
    lat = run.query_latencies()
    return float(np.percentile(lat, 95.0)) * 1e3 if len(lat) else None
