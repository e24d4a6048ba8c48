"""8 x the bytes of every array live on the serving device after warm-up,
with nothing in flight, over the store's unique triples."""


def read(run):
    return 8.0 * run.live_device_bytes / run.n_triples
