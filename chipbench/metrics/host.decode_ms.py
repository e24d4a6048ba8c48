"""Mean ``broker.decode_deliver`` span per batch: host decode of every lane
and delivery to the clients."""

from chipbench.tracereduce import span_ns


def read(run):
    d = span_ns(run.spans, "broker.decode_deliver")
    return float(d.mean() * 1e-6) if len(d) else None
