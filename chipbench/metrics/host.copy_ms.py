"""Mean ``broker.copy`` span per batch: the device-to-host copy of every
fetched field of a batch's result, timed from the moment the device has
finished it (the wait before is ``broker.device_wait``)."""

from chipbench.tracereduce import span_ns


def read(run):
    d = span_ns(run.spans, "broker.copy")
    return float(d.mean() * 1e-6) if len(d) else None
