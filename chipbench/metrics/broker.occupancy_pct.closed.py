"""Mean share of a dispatched batch's lanes that carry a request
(``lanes / padded`` of the ``broker.batch`` spans), in percent."""

import numpy as np


def read(run):
    occ = [e["args"]["lanes"] / e["args"]["padded"] for e in run.spans or ()
           if e.get("kind") == "X" and e["name"] == "broker.batch"]
    return float(np.mean(occ) * 100) if occ else None
