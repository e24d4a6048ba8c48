"""The load generators: an open loop on a schedule and a closed loop.

Both drive a ``ServeBroker`` with ``submit_nowait`` from one asyncio loop and
log, for every request, when it was due, when it was sent, when its answer
reached the client, and the answer itself.  Times are seconds from the
window's start on ``time.perf_counter``.

- Open loop: each request is sent at its due time whatever the backlog, and
  its latency runs from that due time, so a stall shows in every request
  that queues behind it.  How late the generator sent is logged apart.
- Closed loop: each tenant keeps ``k`` requests outstanding and sends its
  next one when one of its own is answered; latency runs from the send.

``on_start`` and ``on_close`` are called as the window opens and closes.
A request the broker refuses (``QueueFull``) is held back by the client, as
a client told to back off holds it, and resent every ``RESEND_S`` until the
broker admits it; the tenant's later requests wait behind it, so its order
is kept.  Its latency still runs from its due time.  After the window closes
nothing new is sent, and the requests held back and the answers still due
are awaited for up to ``grace`` seconds.  A request never admitted by then,
or one the broker fails or cancels, counts as failed; one admitted and never
answered is missing.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import functools
import time

import numpy as np

from chipbench.traffic import O, OP, P, S, TENANT

RESEND_S = 0.002  # how often a request the broker refused is sent again


@dataclasses.dataclass
class Log:
    t0: float = 0.0  # perf_counter at the window's start
    reqs: list = dataclasses.field(default_factory=list)
    due: list = dataclasses.field(default_factory=list)
    sent: list = dataclasses.field(default_factory=list)
    done: list = dataclasses.field(default_factory=list)
    failed: list = dataclasses.field(default_factory=list)
    answers: list = dataclasses.field(default_factory=list)
    errors: list = dataclasses.field(default_factory=list)
    order: dict = dataclasses.field(default_factory=dict)  # tenant -> indices answered
    held: int = 0  # requests the broker refused and the client held back
    refusals: int = 0  # QueueFull answers, resends included

    def add(self, req, due) -> int:
        self.reqs.append(req)
        self.due.append(due)
        self.sent.append(np.nan)
        self.done.append(np.nan)
        self.failed.append(False)
        self.answers.append(None)
        return len(self.reqs) - 1

    def arrays(self) -> dict:
        return {"due": np.asarray(self.due, np.float64),
                "sent": np.asarray(self.sent, np.float64),
                "done": np.asarray(self.done, np.float64),
                "failed": np.asarray(self.failed, np.bool_)}


class _Client:
    """Sends requests through the broker and records what comes back."""

    def __init__(self, broker, tenants: list[str], log: Log):
        from repro.launch.broker import QueueFull

        self.broker, self.tenants, self.log = broker, tenants, log
        self.queue_full = QueueFull
        self.pending: set = set()
        self.held: dict = {}  # tenant -> requests held back, in send order
        self.closed = False
        self.on_answer = None  # called with the tenant after each answer

    def send(self, i: int) -> None:
        log = self.log
        log.sent[i] = time.perf_counter() - log.t0
        tenant = int(log.reqs[i][TENANT])
        if self.held.get(tenant):
            self._hold(tenant, i)
        elif not self._submit(i):
            self._hold(tenant, i)
            asyncio.get_running_loop().call_later(RESEND_S, self._resend, tenant)

    def _hold(self, tenant: int, i: int) -> None:
        self.held.setdefault(tenant, collections.deque()).append(i)
        self.log.held += 1

    def _submit(self, i: int) -> bool:
        """Offer request ``i`` to the broker; False where it is refused."""
        r = self.log.reqs[i]
        try:
            fut = self.broker.submit_nowait(self.tenants[r[TENANT]], int(r[OP]),
                                            int(r[S]), int(r[P]), int(r[O]))
        except self.queue_full:
            self.log.refusals += 1
            return False
        self.pending.add(fut)
        fut.add_done_callback(functools.partial(self._done, i, int(r[TENANT])))
        return True

    def _resend(self, tenant: int) -> None:
        if self.closed:
            return
        q = self.held[tenant]
        while q and self._submit(q[0]):
            q.popleft()
        if q:
            asyncio.get_running_loop().call_later(RESEND_S, self._resend, tenant)

    def _done(self, i: int, tenant: int, fut) -> None:
        log = self.log
        log.done[i] = time.perf_counter() - log.t0
        self.pending.discard(fut)
        if fut.cancelled():
            log.failed[i] = True
        elif fut.exception() is not None:
            log.failed[i] = True
            log.errors.append(repr(fut.exception()))
        else:
            log.answers[i] = fut.result()
        log.order.setdefault(tenant, []).append(i)
        if self.on_answer is not None:
            self.on_answer(tenant)

    async def drain(self, grace: float) -> None:
        end = time.perf_counter() + grace
        while any(self.held.values()) and time.perf_counter() < end:
            await asyncio.sleep(RESEND_S)
        if self.pending:
            await asyncio.wait(set(self.pending),
                               timeout=max(0.0, end - time.perf_counter()))
        await asyncio.sleep(0)  # let the last done-callbacks run
        self.closed = True
        for q in self.held.values():  # never admitted
            for i in q:
                self.log.failed[i] = True
                self.log.errors.append("QueueFull: never admitted in the grace")
            q.clear()


async def open_loop(broker, tenants: list[str], reqs: np.ndarray,
                    due: np.ndarray, seconds: float, *, grace: float = 60.0,
                    on_start=None, on_close=None) -> Log:
    """Send ``reqs[i]`` at ``due[i]`` seconds into a window of ``seconds``."""
    log = Log()
    client = _Client(broker, tenants, log)
    for r, d in zip(reqs, due):
        log.add(r, float(d))
    if on_start is not None:
        on_start()
    log.t0 = t0 = time.perf_counter()
    i, n = 0, len(reqs)
    while i < n:
        now = time.perf_counter() - t0
        if due[i] > now:
            await asyncio.sleep(due[i] - now)
            continue
        while i < n and due[i] <= now:
            client.send(i)
            i += 1
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        await asyncio.sleep(rest)
    if on_close is not None:
        on_close()
    await client.drain(grace)
    return log


async def closed_loop(broker, tenants: list[str], next_request, k: int,
                      seconds: float, *, grace: float = 60.0,
                      on_start=None, on_close=None) -> Log:
    """Each tenant keeps ``k`` requests outstanding for ``seconds``;
    ``next_request(tenant)`` gives that tenant's next request row."""
    log = Log()
    client = _Client(broker, tenants, log)
    closed = False

    def send_next(tenant: int) -> None:
        if not closed:
            client.send(log.add(next_request(tenant), time.perf_counter() - log.t0))

    if on_start is not None:
        on_start()
    log.t0 = time.perf_counter()
    client.on_answer = send_next
    for _ in range(k):
        for t in range(len(tenants)):
            send_next(t)
    await asyncio.sleep(seconds)
    closed = True
    if on_close is not None:
        on_close()
    await client.drain(grace)
    return log


async def burst(broker, tenants: list[str], reqs: np.ndarray,
                *, grace: float = 600.0) -> Log:
    """Send every request at once and wait for all answers: the warm-up."""
    log = Log()
    client = _Client(broker, tenants, log)
    log.t0 = time.perf_counter()
    for r in reqs:
        client.send(log.add(r, 0.0))
    await client.drain(grace)
    return log
