"""The benchmark's cells cut to a size the CPU runs in seconds, with the
Pallas kernels interpreted: corpus, batch and load scaled down, every other
setting as the cell's files give it."""

from __future__ import annotations

import dataclasses

from chipbench import spec


def tiny(name: str, **serving):
    cell = spec.load_cell(name)
    config = dict(cell.config, triples=4000, subjects=400, objects=500)
    config["serving"] = dict(cell.config["serving"], max_batch=16, **serving)
    loop = dict(cell.loop, warmup_requests=32)
    if loop["loop"] == "open":
        loop["rate_per_s"] = 40
    else:
        loop["outstanding_per_tenant"] = 2
    return dataclasses.replace(cell, config=config, loop=loop)
