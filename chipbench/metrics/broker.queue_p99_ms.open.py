"""99th percentile of the per-query ``queue`` spans (submit to dispatch)."""

import numpy as np

from chipbench.tracereduce import span_ns


def read(run):
    d = span_ns(run.spans, "queue", "async")
    return float(np.percentile(d, 99) * 1e-6) if len(d) >= 1000 else None
