"""The load generators against a fake broker: the open loop times from the
due time, and the closed loop keeps its requests outstanding."""

from __future__ import annotations

import asyncio

import numpy as np

from chipbench import loops


class FakeBroker:
    """Answers every request ``delay`` seconds after it is sent, except that
    nothing is answered until ``stall_until`` (seconds after the first
    request): a broker that stalls once."""

    def __init__(self, delay=0.001, stall=0.0):
        self.delay, self.stall = delay, stall
        self.t_first = None
        self.last = 0.0
        self.outstanding = {}
        self.peak = {}

    def submit_nowait(self, tenant, op, s, p, o):
        loop = asyncio.get_running_loop()
        now = loop.time()
        if self.t_first is None:
            self.t_first = now
        fut = loop.create_future()
        at = max(now + self.delay, self.t_first + self.stall, self.last + 1e-5)
        self.last = at  # answers come in the order sent
        self.outstanding[tenant] = self.outstanding.get(tenant, 0) + 1
        self.peak[tenant] = max(self.peak.get(tenant, 0), self.outstanding[tenant])

        def answer():
            self.outstanding[tenant] -= 1
            fut.set_result(np.array([s]))

        loop.call_at(at, answer)
        return fut


def _reqs(n, tenants=2):
    return np.array([[i % tenants, 1, i + 1, 1, 1] for i in range(n)], np.int64)


def test_open_loop_times_from_the_due_time():
    """A 0.3 s stall at the start shows in the latency of every request due
    during it, each by what remained of the stall at its due time."""
    n, seconds = 100, 1.0
    due = np.linspace(0, seconds, n, endpoint=False)
    broker = FakeBroker(delay=0.001, stall=0.3)
    log = asyncio.run(loops.open_loop(broker, ["a", "b"], _reqs(n), due, seconds,
                                      grace=5.0))
    a = log.arrays()
    lat = a["done"] - a["due"]
    assert not a["failed"].any() and not np.isnan(lat).any()
    stalled = due < 0.25
    assert np.all(lat[stalled] >= 0.3 - due[stalled] - 0.01)
    assert np.median(lat[due > 0.5]) < 0.15
    # the generator itself kept its schedule
    assert np.percentile(a["sent"] - a["due"], 99) < 0.15
    assert [log.order[t] for t in (0, 1)] == [list(range(0, n, 2)), list(range(1, n, 2))]


def test_open_loop_counts_a_shed_as_failed():
    """A request the broker refuses every time it is resent is never
    admitted: failed once the grace is over."""
    from repro.launch.broker import QueueFull

    class Shedding(FakeBroker):
        def submit_nowait(self, tenant, op, s, p, o):
            if s % 2:
                raise QueueFull("full")
            return super().submit_nowait(tenant, op, s, p, o)

    due = np.linspace(0, 0.2, 10, endpoint=False)
    log = asyncio.run(loops.open_loop(Shedding(), ["a", "b"], _reqs(10), due,
                                      0.2, grace=0.2))
    a = log.arrays()
    assert a["failed"].sum() == 5 and len(log.errors) == 5
    assert a["failed"][0::2].all() and not np.isnan(a["done"][1::2]).any()


def test_open_loop_resends_a_refused_request():
    """A broker whose queue holds 3 a tenant refuses while it stalls; the
    client holds what was refused, resends it, and keeps each tenant's
    order.  Every request is answered, late by the stall."""
    from repro.launch.broker import QueueFull

    class Bounded(FakeBroker):
        def submit_nowait(self, tenant, op, s, p, o):
            if self.outstanding.get(tenant, 0) >= 3:
                raise QueueFull("full")
            return super().submit_nowait(tenant, op, s, p, o)

    n, seconds = 40, 0.4
    due = np.linspace(0, seconds, n, endpoint=False)
    log = asyncio.run(loops.open_loop(Bounded(stall=0.2), ["a", "b"], _reqs(n),
                                      due, seconds, grace=5.0))
    a = log.arrays()
    lat = a["done"] - a["due"]
    assert not a["failed"].any() and not np.isnan(lat).any()
    assert log.held > 0 and log.refusals > 0
    assert np.all(lat[due < 0.15] >= 0.2 - due[due < 0.15] - 0.01)
    assert [log.order[t] for t in (0, 1)] == [list(range(0, n, 2)), list(range(1, n, 2))]


def test_closed_loop_keeps_k_outstanding_per_tenant():
    broker = FakeBroker(delay=0.002)
    count = {0: 0, 1: 0, 2: 0}

    def next_request(t):
        count[t] += 1
        return np.array([t, 1, count[t], 1, 1], np.int64)

    log = asyncio.run(loops.closed_loop(broker, ["a", "b", "c"], next_request,
                                        4, 0.3, grace=5.0))
    a = log.arrays()
    assert broker.peak == {"a": 4, "b": 4, "c": 4}
    assert not a["failed"].any() and not np.isnan(a["done"]).any()
    assert len(log.reqs) > 3 * 4 * 20  # ~0.3 s / 2 ms rounds of 4
    assert all(np.all(np.diff(o) > 0) for o in log.order.values())
