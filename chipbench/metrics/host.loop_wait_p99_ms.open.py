"""99th percentile over batches of the time a batch's fetch spent waiting
on the host's threads rather than on the device or the copy: its
``broker.handoff`` (from ``_deliver`` to a worker thread starting the fetch)
plus its ``broker.resume`` (from the thread's return to the coroutine
running again), joined by ``bid``.  Needs 100 batches, so that p99 lies
between two of them."""

import numpy as np

STAGES = ("broker.handoff", "broker.resume")


def read(run):
    wait, seen = {}, {}
    for e in run.spans or ():
        if e.get("kind") == "X" and e["name"] in STAGES:
            bid = e["args"]["bid"]
            wait[bid] = wait.get(bid, 0) + e["t1"] - e["t0"]
            seen[bid] = seen.get(bid, 0) + 1
    w = [v for bid, v in wait.items() if seen[bid] == len(STAGES)]
    return float(np.percentile(w, 99) * 1e-6) if len(w) >= 100 else None
