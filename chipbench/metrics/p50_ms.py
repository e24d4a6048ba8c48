"""Median latency, due time to decoded answer, over every request of the
window; a request that failed or never came counts as late by the window and
the 60 s grace after it."""

import numpy as np


def read(run):
    lat = run.latency_s
    return float(np.percentile(lat, 50) * 1e3) if len(lat) >= 2 else None
