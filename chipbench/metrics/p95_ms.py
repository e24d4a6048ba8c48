"""95th percentile of the latency, due time to decoded answer, over every
request of the window (a failed or missing request counts as late by the
window and the grace after it); needs 200 requests, so that ten lie beyond
it."""

import numpy as np


def read(run):
    lat = run.latency_s
    return float(np.percentile(lat, 95) * 1e3) if len(lat) >= 200 else None
