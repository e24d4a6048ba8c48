"""Bytes the fetch brings to the host per query, in KB (1,000 bytes): the
``bytes`` of the ``broker.copy`` spans over the ``lanes`` of the
``broker.batch`` spans, summed over the batches that have both (joined by
``bid``)."""


def read(run):
    copied, lanes = {}, {}
    for e in run.spans or ():
        if e.get("kind") != "X":
            continue
        if e["name"] == "broker.copy":
            copied[e["args"]["bid"]] = e["args"]["bytes"]
        elif e["name"] == "broker.batch":
            lanes[e["args"]["bid"]] = e["args"]["lanes"]
    both = copied.keys() & lanes.keys()
    n = sum(lanes[bid] for bid in both)
    return sum(copied[bid] for bid in both) / n / 1e3 if n else None
