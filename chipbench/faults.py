"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that patches the program for its duration and puts
it back after.  ``control`` is the control that sets the upper reading of the
comparison: the store served at a cap of 64 with the overflow bit ignored, so
a lane whose traversal does not fit answers truncated instead of growing its
cap (the exactness guarantee broken the way a cheaper cap would tempt).  The
others are the faults of a served store: an answer altered where it comes
off the device, half of every batch's lanes left out, and requests
admitted and then dropped.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

CONTROL_CAP = 64


@contextlib.contextmanager
def _patched(obj, name: str, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _host_result_wrapped(edit):
    from repro.core import engine as eng

    real = eng.host_result

    def host_result(r, *, unbounded=True):
        return edit(real(r, unbounded=unbounded))

    return _patched(eng, "host_result", host_result)


def control_cell(cell):
    """``cell`` served at ``CONTROL_CAP``."""
    serving = dict(cell.config["serving"], cap=CONTROL_CAP)
    return dataclasses.replace(cell, config=dict(cell.config, serving=serving))


def control():
    """Overflow bits cleared: no lane ever grows its cap."""
    return _host_result_wrapped(
        lambda h: h._replace(overflow=np.zeros_like(h.overflow)))


def answer_altered():
    """Every returned id off by one where the answer comes off the device."""
    return _host_result_wrapped(
        lambda h: h._replace(ids=np.where(h.valid, h.ids + 1, h.ids),
                             u_ids=np.where(h.u_valid, h.u_ids + 1, h.u_ids)))


def half_batch_dropped():
    """The second half of every batch's requests sent as dead lanes."""
    from repro.launch.broker import ServeBroker

    real = ServeBroker._encode

    def encode(self, reqs, pad_to):
        qb = real(self, reqs, pad_to)
        op = np.array(qb.op)
        op[len(reqs) // 2: len(reqs)] = -1
        return qb._replace(op=op)

    return _patched(ServeBroker, "_encode", encode)


def requests_shed():
    """Every other request dropped once admitted: its answer never comes and
    it fails with ``QueueFull``, as a broker that sheds load it took."""
    import asyncio

    from repro.launch.broker import QueueFull, ServeBroker

    real = ServeBroker.submit_nowait
    calls = [0]

    def submit_nowait(self, *args, **kwargs):
        calls[0] += 1
        if calls[0] % 2 == 0:
            fut = asyncio.get_running_loop().create_future()
            fut.set_exception(QueueFull("shed by the planted fault"))
            return fut
        return real(self, *args, **kwargs)

    return _patched(ServeBroker, "submit_nowait", submit_nowait)


# each fault and the number of ``correct`` that it trips
FAULTS = {"answer_altered": answer_altered,
          "half_batch_dropped": half_batch_dropped,
          "requests_shed": requests_shed}
TRIPS = {"answer_altered": "wrong", "half_batch_dropped": "wrong",
         "requests_shed": "failed"}
