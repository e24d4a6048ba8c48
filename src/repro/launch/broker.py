"""Streaming multi-tenant serve broker over compiled ``ServeQ`` plans.

The production front-end the ROADMAP north star asks for: many tenants
submit single queries as async streams; the broker coalesces them into
mixed-op ``ServeBatch``es under a deadline/size policy, double-buffers
host-side decode against device serve, and streams each tenant's results
back the moment its lanes decode — no batch-level result object is ever
materialized for callers.

    broker = ServeBroker(engine, ExecConfig(cap=512))
    async with broker:
        objs = await broker.submit("tenant-a", eng.OP_ROW, s=12, p=3)

Pipeline (one background task)::

    submit() ──▶ global FIFO ──▶ coalesce (deadline/size) ──▶ Plan.submit
                                                              (device, async)
         futures ◀── per-lane streamed decode ◀── host_result ◀─┘
                     (batch N decodes while batch N+1 runs on device)

Isolation properties
--------------------

* **The shared base plan never grows.**  Dispatch rides ``Plan.submit`` —
  the raw device path with no CapPolicy growth — so one tenant's
  overflowing queries cannot recompile (or widen) the program every other
  tenant is served by.
* **Cap growth is per tenant and budgeted.**  Lanes whose ``overflow`` bit
  is set are retried on doubled-cap plans compiled under that tenant's
  :class:`TenantPolicy` budget (``max_cap_doublings``); a tenant that
  exhausts its budget gets :class:`~repro.core.query.CapOverflow` on that
  query while everyone else proceeds at base cap.
* **Plan-cache admission is quota'd.**  Every retry cap level is a plan
  the engine must compile; ``Engine.compile(admit=...)`` charges the
  tenant's ``max_plans`` quota on cache MISSES only — plans another tenant
  already compiled are shared free of charge — and denial surfaces as
  :class:`~repro.core.query.AdmissionError` on the offending query.

Back-pressure (the shed policy)
-------------------------------

Per-tenant queues are bounded at ``TenantPolicy.queue_depth`` *accepted
but unresolved* requests.  The policy is **shed-newest, fail-fast**: a
submit over the bound raises :class:`QueueFull` immediately (counted in
``stats()``) and nothing already accepted is ever dropped — so a flooding
tenant sees its own rejections synchronously while other tenants' queues
and latency are untouched.

Ordering
--------

Per-tenant FIFO: results resolve in submission order.  Batches decode in
dispatch order, lanes decode in lane order, and a tenant with a retried
(overflowed) lane has its later lanes in that batch held until the retry
lands — so growth never reorders a stream.

Writes (dynamic stores)
-----------------------

When the engine serves a :class:`~repro.core.delta.DynamicStore`,
``submit_insert`` / ``submit_delete`` apply live mutations to its delta —
synchronously (an in-memory set op), budgeted per tenant by
``TenantPolicy.max_writes`` (:class:`WriteBudgetExhausted` past the
bound; the budget refills at compaction).  Reads stay on the raw static
lane: dispatch pins the delta view, sanitizes lanes whose constants
exceed the static extents, and decode merges the delta host-side —
(static − tombstones) ∪ inserts per lane — off the event loop.  With a
:class:`~repro.core.compaction.CompactionPolicy`, a write that trips the
threshold schedules a background compaction; the epoch swap is atomic,
in-flight batches finish against the old epoch, and the base plan is
rebuilt eagerly so the serve loop never pays a ``StaleEpoch`` round-trip.

Stats
-----

``stats()`` returns a structured dict: global and per-tenant query
latency percentiles (``p50_ms``/``p99_ms`` via :func:`tail_percentile`,
which refuses sample counts that cannot support a tail quantile), queue
depth + peak, coalesce factor, flush-reason counts, shed counts, and
cap-growth / admission-denial events.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import math
import time
import warnings

import numpy as np

from repro import obs
from repro.core import delta as dyn
from repro.core import engine as eng
from repro.core.compaction import CompactionPolicy, compact, needs_compaction
from repro.core.query import (
    AdmissionError, CapOverflow, CapPolicy, ExecConfig, SelectQ, ServeQ,
    StaleEpoch,
)
from repro.obs import LATENCY_MS_BUCKETS, MetricsRegistry

__all__ = [
    "CoalescePolicy", "TenantPolicy", "QueueFull", "ServeBroker",
    "WriteBudgetExhausted", "tail_percentile",
]


class QueueFull(RuntimeError):
    """Shed signal: the tenant's bounded queue is at ``queue_depth``.

    Raised synchronously by ``submit``/``submit_nowait`` (shed-newest,
    fail-fast — see the module docstring); the request was NOT enqueued.
    """


class WriteBudgetExhausted(RuntimeError):
    """The tenant spent its ``TenantPolicy.max_writes`` budget.

    Raised synchronously by ``submit_insert``/``submit_delete``; the write
    was NOT applied.  The budget is resident-delta-based: it refills when
    a compaction folds the delta into a new static epoch, so a sustained
    writer is paced by the compactor rather than cut off forever.
    """


@dataclasses.dataclass(frozen=True)
class CoalescePolicy:
    """When pending requests flush into a device batch.

    A batch dispatches when ``max_batch`` requests are pending OR the
    oldest pending request has waited ``max_delay_s`` — whichever comes
    first.  Batches are padded to ``max_batch`` with dead (op = -1) lanes
    so every dispatch hits ONE compiled program geometry (no retraces).
    ``max_inflight`` bounds device batches awaiting decode; 2 is the
    double-buffer: batch N decodes on host while N+1 runs on device.
    """

    max_batch: int = 256
    max_delay_s: float = 2e-3
    max_inflight: int = 2

    def __post_init__(self):
        if self.max_batch < 1 or self.max_inflight < 1:
            raise ValueError("max_batch and max_inflight must be >= 1")
        if self.max_delay_s < 0:
            raise ValueError("max_delay_s must be >= 0")


@dataclasses.dataclass(frozen=True)
class TenantPolicy:
    """Per-tenant admission + back-pressure budgets (one policy, applied
    to every tenant; tenants are created on first submit).

    ``queue_depth``
        Accepted-but-unresolved request bound; beyond it submissions shed
        (:class:`QueueFull`).
    ``max_cap_doublings``
        Cap-growth budget: how many times this tenant's overflowing
        queries may double the retry cap above the broker's base cap.
    ``max_plans``
        Plan-cache quota: how many plan-cache MISSES (new compiled
        programs — one per distinct retry cap level) the tenant may
        charge.  Shared cache hits are free.
    ``max_writes``
        Write budget: how many inserts + deletes the tenant may have
        resident in the delta at once; refilled when compaction folds
        the delta down (:class:`WriteBudgetExhausted` past the bound).
    """

    queue_depth: int = 1024
    max_cap_doublings: int = 4
    max_plans: int = 4
    max_writes: int = 4096

    def __post_init__(self):
        if self.queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        if self.max_cap_doublings < 0 or self.max_plans < 0:
            raise ValueError("budgets must be >= 0")
        if self.max_writes < 1:
            raise ValueError("max_writes must be >= 1")


def tail_percentile(samples, q: float) -> float | None:
    """``np.percentile`` guarded by sample count: ``None`` unless there are
    at least ``ceil(100 / (100 - q))`` samples — the minimum for the q-th
    percentile to be interpolated between order statistics rather than
    being a relabeled maximum (p99 needs 100 samples, p50 needs 2)."""
    n = len(samples)
    if not 0 <= q < 100:
        raise ValueError(f"q must be in [0, 100), got {q}")
    need = max(1, math.ceil(100.0 / (100.0 - q)))
    if n < need:
        return None
    return float(np.percentile(np.asarray(samples), q))


# _Req.op marker for SELECT queries (real serve-IR ops are >= 0; dead
# lanes are -1): selects never ride the coalesced ServeBatch
OP_SELECT = -2


@dataclasses.dataclass
class _Req:
    tenant: str
    op: int
    s: int
    p: int
    o: int
    t_submit: float
    future: asyncio.Future
    seq: int = 0  # global submission sequence — the per-query trace id
    t_deliver: float = 0.0  # stamped at resolve/fail time


@dataclasses.dataclass
class _BatchMeta:
    """Timeline of one dispatched batch (``time.perf_counter_ns``), stamped
    whether tracing is on or off: coalesce ``[tc0, tc1]`` → encode+dispatch
    ``[td0, td1]`` → inflight ``[td1, tf0]`` → the fetch ``[tf0, tf1]`` in
    stages that meet end to end: handoff to a worker thread ``[tf0, tw0]``,
    device wait ``[tw0, tw1]``, copy ``[tw1, tw2]``, delta merge
    ``[tw2, tw3]`` (empty for a static store), resume on the event loop
    ``[tw3, tf1]`` → decode/deliver.  Feeds the retroactive trace spans
    emitted once the batch fully delivers."""

    bid: int
    n_padded: int
    tc0: int = 0
    tc1: int = 0
    td0: int = 0
    td1: int = 0
    tf0: int = 0
    tw0: int = 0
    tw1: int = 0
    tw2: int = 0
    tw3: int = 0
    tf1: int = 0


@dataclasses.dataclass
class _TenantState:
    name: str
    pending: int = 0  # accepted, not yet resolved
    shed: int = 0
    completed: int = 0
    failed: int = 0
    cap_level: int = 0  # highest doubling level this tenant reached
    plans_charged: int = 0  # plan-cache misses charged against max_plans
    cap_growth_events: int = 0
    admission_denials: int = 0
    inserts: int = 0
    deletes: int = 0
    writes_resident: int = 0  # writes in the live delta (budget state)
    lat_s: list = dataclasses.field(default_factory=list)


class ServeBroker:
    """Async multi-tenant request broker over one ``Engine``.

    Use as an async context manager (or ``start()`` / ``aclose()``)::

        async with ServeBroker(engine, cfg) as broker:
            hit = await broker.submit("t0", eng.OP_CHECK, s, p, o)

    ``unbounded=False`` compiles the ``u_*`` block out of the base plan —
    a broker serving only CHECK/ROW/COL traffic never pays for it (and
    the decode fetch skips the ``[B, L, cap]`` transfer either way when a
    batch carries no unbounded lanes).
    """

    def __init__(
        self,
        engine: eng.Engine,
        config: ExecConfig | None = None,
        *,
        unbounded: bool = True,
        coalesce: CoalescePolicy = CoalescePolicy(),
        tenant_policy: TenantPolicy = TenantPolicy(),
        compaction: CompactionPolicy | None = None,
    ):
        self.engine = engine
        self.compaction = compaction
        cfg = (config or engine.default_config).resolved()
        # growth is broker-managed (per tenant); the base plan must never
        # self-heal behind the broker's back
        self.config = cfg.replace(cap_policy=CapPolicy(grow=False))
        self.coalesce = coalesce
        self.tenant_policy = tenant_policy
        self.unbounded = unbounded
        self._query = ServeQ(unbounded=unbounded)
        self.base_plan = engine.compile(self._query, self.config)
        # data-axis divisibility for sharded dispatch geometries
        self._pad_to = self._padded_batch(coalesce.max_batch)

        self._queue: collections.deque[_Req] = collections.deque()
        self._inflight: collections.deque = collections.deque()
        self._tenants: dict[str, _TenantState] = {}
        self._wake: asyncio.Event | None = None
        self._task: asyncio.Task | None = None
        self._draining = False
        self._running = False
        # ALWAYS-ON bookkeeping registry backing ``stats()`` — the typed
        # replacement for the old ad-hoc ``collections.Counter``.  The
        # obs-layer extras (timing histograms, spans) live in the global
        # ``repro.obs`` state and only run while observability is enabled.
        self.metrics = MetricsRegistry()
        self._c = {
            name: self.metrics.counter(f"broker.{name}")
            for name in (
                "batches", "lanes", "flush_size", "flush_deadline",
                "flush_drain", "shed", "cap_growth_events",
                "admission_denials", "selects", "inserts", "deletes",
                "compactions", "compaction_ms", "compaction_errors",
            )
        }
        self._compaction_task: asyncio.Task | None = None
        # SELECT queries run off-loop (each is a host-planned multi-launch
        # pipeline, not a lane); the semaphore bounds their thread fanout
        self._select_sem = asyncio.Semaphore(max(2, coalesce.max_inflight))
        self._select_tasks: set[asyncio.Task] = set()
        self._queue_peak = 0
        self._seq = 0  # per-query trace ids
        self._bid = 0  # batch ids
        # trace tracks of the batch stages (see ``_trace_batch``)
        self._slots = tuple(
            f"batch-slot-{i}" for i in range(2 * coalesce.max_inflight)
        )
        self._retry_cfgs: set[ExecConfig] = set()  # cap levels ever compiled

    # -- lifecycle ------------------------------------------------------

    async def __aenter__(self) -> "ServeBroker":
        await self.start()
        return self

    async def __aexit__(self, *exc):
        await self.aclose()

    async def start(self) -> None:
        if self._running:
            raise RuntimeError("broker already started")
        self._wake = asyncio.Event()
        self._draining = False
        self._running = True
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def aclose(self) -> None:
        """Drain: serve everything accepted, then stop the loop."""
        if not self._running:
            return
        self._draining = True
        self._wake.set()
        await self._task
        if self._select_tasks:  # selects accepted before the drain finish
            await asyncio.gather(*self._select_tasks, return_exceptions=True)
        if self._compaction_task is not None and not self._compaction_task.done():
            await self._compaction_task
        self._running = False

    # -- submission -----------------------------------------------------

    def submit_nowait(self, tenant: str, op: int, s: int = 0, p: int = 0,
                      o: int = 0) -> asyncio.Future:
        """Enqueue one query; the future resolves to its decoded answer
        (see ``engine.decode_lane`` for per-op shapes).  Raises
        :class:`QueueFull` when the tenant's queue is at ``queue_depth``
        (the shed policy) and ``RuntimeError`` when the broker is not
        accepting."""
        if not self._running or self._draining:
            raise RuntimeError("broker is not accepting requests")
        st = self._tenant(tenant)
        if st.pending >= self.tenant_policy.queue_depth:
            st.shed += 1
            self._c["shed"].inc()
            raise QueueFull(
                f"tenant {tenant!r} at queue_depth="
                f"{self.tenant_policy.queue_depth}; shed-newest"
            )
        st.pending += 1
        fut = asyncio.get_running_loop().create_future()
        self._queue.append(
            _Req(tenant, int(op), int(s), int(p), int(o),
                 time.perf_counter(), fut, seq=self._seq)
        )
        self._seq += 1
        self._queue_peak = max(self._queue_peak, len(self._queue))
        self._wake.set()
        return fut

    async def submit(self, tenant: str, op: int, s: int = 0, p: int = 0,
                     o: int = 0):
        return await self.submit_nowait(tenant, op, s, p, o)

    # -- the write path -------------------------------------------------

    def submit_insert_nowait(self, tenant: str, s: int, p: int, o: int) -> None:
        """Insert one id triple into the dynamic store's delta.

        Writes apply synchronously (a delta insert is an in-memory set op
        — there is nothing to coalesce or await) and become visible to
        every batch dispatched after this call.  Requires the engine to
        serve a :class:`~repro.core.delta.DynamicStore`; raises
        :class:`WriteBudgetExhausted` when the tenant's resident-write
        budget (``TenantPolicy.max_writes``) is spent — it refills at the
        next compaction.  May schedule a background compaction when a
        :class:`~repro.core.compaction.CompactionPolicy` was configured.
        """
        self._write(tenant, s, p, o, insert=True)

    async def submit_insert(self, tenant: str, s: int, p: int, o: int) -> None:
        self.submit_insert_nowait(tenant, s, p, o)

    def submit_delete_nowait(self, tenant: str, s: int, p: int, o: int) -> None:
        """Delete one id triple (tombstone it in the delta).

        Same contract as :meth:`submit_insert_nowait`: synchronous,
        budgeted by ``max_writes``, compaction-triggering.
        """
        self._write(tenant, s, p, o, insert=False)

    async def submit_delete(self, tenant: str, s: int, p: int, o: int) -> None:
        self.submit_delete_nowait(tenant, s, p, o)

    def _write(self, tenant: str, s: int, p: int, o: int, *, insert: bool):
        if not self._running or self._draining:
            raise RuntimeError("broker is not accepting requests")
        store = self.engine.store
        if not isinstance(store, dyn.DynamicStore):
            raise TypeError(
                "writes need a DynamicStore; wrap the static store in "
                "repro.core.delta.DynamicStore"
            )
        st = self._tenant(tenant)
        if st.writes_resident >= self.tenant_policy.max_writes:
            raise WriteBudgetExhausted(
                f"tenant {tenant!r} has {st.writes_resident} writes resident "
                f"(max_writes={self.tenant_policy.max_writes}); budget "
                "refills at the next compaction"
            )
        if insert:
            store.insert(s, p, o)
            st.inserts += 1
            self._c["inserts"].inc()
        else:
            store.delete(s, p, o)
            st.deletes += 1
            self._c["deletes"].inc()
        st.writes_resident += 1
        self._maybe_compact()

    def _maybe_compact(self):
        """Kick a background compaction when the policy says the delta is
        due and none is already running.  The rebuild runs off-loop; the
        epoch swap is atomic and reads keep serving the old epoch until
        the swapped store lands (dispatch then sees ``StaleEpoch`` once
        and refreshes the base plan)."""
        if self.compaction is None or not needs_compaction(
            self.engine.store, self.compaction
        ):
            return
        if self._compaction_task is not None and not self._compaction_task.done():
            return
        task = asyncio.get_running_loop().create_task(self._run_compaction())
        task.add_done_callback(self._observe_compaction)
        self._compaction_task = task

    def _observe_compaction(self, task: asyncio.Task) -> None:
        """Surface a background-compaction failure when the task completes
        (not first at ``drain``): count it and warn.  The broker keeps
        serving the old epoch — the delta simply grows until the next
        write re-triggers the policy."""
        if task.cancelled():
            return
        exc = task.exception()
        if exc is not None:
            self._c["compaction_errors"].inc()
            warnings.warn(
                f"background compaction failed: {exc!r}", RuntimeWarning,
                stacklevel=2,
            )

    async def _run_compaction(self):
        # writes resident at this point are exactly the entries the pinned
        # snapshot will absorb (writes racing in during the rebuild stay
        # resident in the rebased delta and must keep paying budget) —
        # capture per tenant so the refill below decrements rather than
        # zeroing away still-resident raced writes.  A write landing
        # between this capture and the snapshot pin is absorbed but not
        # decremented: it stays counted, erring on the strict side.
        absorbed = {
            name: st.writes_resident for name, st in self._tenants.items()
        }
        with obs.span("broker.compaction", cat="broker"):
            rep = await asyncio.to_thread(
                compact, self.engine.store,
                backend=self.config.backend,
            )
        # the swap bumped the store epoch: every cached plan (base + retry
        # levels) is stale — rebuild the base plan eagerly so the serve
        # loop never pays the StaleEpoch round-trip.  Off the event loop:
        # Engine.compile is a full JAX trace+JIT and must not stall
        # intake/dispatch; a dispatch racing the refresh self-heals via
        # its own StaleEpoch recompile.
        await asyncio.to_thread(self._refresh_base_plan)
        for name, st in self._tenants.items():
            st.writes_resident = max(
                0, st.writes_resident - absorbed.get(name, 0)
            )
        self._c["compactions"].inc()
        self._c["compaction_ms"].inc(rep.duration_s * 1e3)
        m = obs.STATE.metrics
        if m is not None:
            m.gauge("broker.epoch").set(rep.epoch)
        return rep

    def _refresh_base_plan(self):
        self.base_plan = self.engine.compile(self._query, self.config)
        self._retry_cfgs.clear()  # stale cap levels; recompiled on demand

    def submit_select_nowait(self, tenant: str, q: SelectQ) -> asyncio.Future:
        """Enqueue one SPARQL-shaped :class:`~repro.core.query.SelectQ`;
        the future resolves to its columnar named bindings.

        Selects share the tenant's bounded queue (``queue_depth``) and its
        latency/completion stats with the lane path, but never ride the
        coalesced ``ServeBatch``: each executes off the event loop through
        ``Engine.compile`` with cap growth budgeted by the tenant's
        ``max_cap_doublings`` and plan-cache admission charged through the
        same ``max_plans`` quota (the compiled ``("select",)`` executor is
        shared across tenants — misses are charged to whoever compiles a
        cap level first, hits are free, exactly like retry plans).
        """
        if not self._running or self._draining:
            raise RuntimeError("broker is not accepting requests")
        st = self._tenant(tenant)
        if st.pending >= self.tenant_policy.queue_depth:
            st.shed += 1
            self._c["shed"].inc()
            raise QueueFull(
                f"tenant {tenant!r} at queue_depth="
                f"{self.tenant_policy.queue_depth}; shed-newest"
            )
        st.pending += 1
        fut = asyncio.get_running_loop().create_future()
        r = _Req(tenant, OP_SELECT, 0, 0, 0, time.perf_counter(), fut,
                 seq=self._seq)
        self._seq += 1
        self._c["selects"].inc()
        task = asyncio.get_running_loop().create_task(self._run_select(r, q))
        self._select_tasks.add(task)
        task.add_done_callback(self._select_tasks.discard)
        return fut

    async def submit_select(self, tenant: str, q: SelectQ):
        return await self.submit_select_nowait(tenant, q)

    async def _run_select(self, r: _Req, q: SelectQ):
        async with self._select_sem:
            try:
                value = await asyncio.to_thread(self._select_call, r, q)
            except (CapOverflow, AdmissionError) as e:
                st = self._tenants[r.tenant]
                if isinstance(e, AdmissionError):
                    st.admission_denials += 1
                    self._c["admission_denials"].inc()
                self._fail(r, e)
            except Exception as e:  # lowering/validation errors -> caller
                self._fail(r, e)
            else:
                self._resolve(r, value)

    def _select_call(self, r: _Req, q: SelectQ):
        """Blocking (off-loop) SELECT execution under the tenant's growth
        budget; cap-doubling recompiles pass the tenant's admission
        closure like any retry plan."""
        st = self._tenants[r.tenant]
        # mesh=None: SELECT planner blocks run single-device (the engine
        # rejects sharded BGP/SELECT loudly); the broker's base serve plan
        # stays sharded regardless
        cfg = self.config.replace(
            mesh=None,
            cap_policy=CapPolicy(
                grow=True,
                max_doublings=self.tenant_policy.max_cap_doublings,
            ),
        )
        with obs.span("broker.select", cat="broker", tenant=r.tenant,
                      seq=r.seq):
            plan = self.engine.compile(q, cfg, admit=self._admit(st))
            return plan()

    async def stream(self, tenant: str, queries):
        """Submit a tenant's query stream, yielding results in submission
        order.  ``queries`` is an iterable of ``(op, s, p, o)`` lane
        tuples and/or :class:`~repro.core.query.SelectQ` queries (mixed
        freely — the serve driver's full-shape traffic).  The whole
        stream is admitted through the same bounded queue — a
        :class:`QueueFull` shed propagates to the caller mid-stream."""
        window: collections.deque[asyncio.Future] = collections.deque()
        for item in queries:
            while window and window[0].done():
                yield await window.popleft()
            # stay inside the tenant's queue bound: wait for the oldest
            # outstanding result instead of shedding our own stream
            while (
                window
                and self._tenant(tenant).pending >= self.tenant_policy.queue_depth
            ):
                yield await window.popleft()
            if isinstance(item, SelectQ):
                window.append(self.submit_select_nowait(tenant, item))
            else:
                window.append(self.submit_nowait(tenant, *item))
        while window:
            yield await window.popleft()

    # -- the serve loop -------------------------------------------------

    async def _run(self):
        while True:
            if len(self._inflight) >= self.coalesce.max_inflight:
                await self._deliver(*self._inflight.popleft())
                continue
            reqs, tc0, tc1 = await self._collect(block=not self._inflight)
            if reqs:
                self._dispatch(reqs, tc0, tc1)
            elif self._inflight:
                await self._deliver(*self._inflight.popleft())
            elif self._draining and not self._queue:
                return

    async def _collect(self, *, block: bool):
        """Coalesce: returns ``(reqs, tc0, tc1)`` — the collected batch
        plus the ``perf_counter_ns`` window the coalesce wait spanned."""
        pol = self.coalesce
        while not self._queue:
            if not block or self._draining:
                return [], 0, 0
            self._wake.clear()
            await self._wake.wait()
        tc0 = time.perf_counter_ns()
        # deadline of the OLDEST pending request governs the flush
        deadline = self._queue[0].t_submit + pol.max_delay_s
        while len(self._queue) < pol.max_batch and not self._draining:
            now = time.perf_counter()
            if now >= deadline:
                break
            self._wake.clear()
            try:
                await asyncio.wait_for(self._wake.wait(), deadline - now)
            except asyncio.TimeoutError:
                break
        if len(self._queue) >= pol.max_batch:
            self._c["flush_size"].inc()
        elif self._draining:
            self._c["flush_drain"].inc()
        else:
            self._c["flush_deadline"].inc()
        n = min(len(self._queue), pol.max_batch)
        reqs = [self._queue.popleft() for _ in range(n)]
        return reqs, tc0, time.perf_counter_ns()

    def _dispatch(self, reqs: list[_Req], tc0: int = 0, tc1: int = 0):
        bid = self._bid
        t = obs.STATE.tracer
        td0 = time.perf_counter_ns()
        live = _stage(t, "broker.dispatch", td0, self._slot(bid), bid)
        qb = self._encode(reqs, self._pad_to)
        # pin the dynamic view AT dispatch: the static lane answers this
        # batch against lanes sanitized to the static extents, and decode
        # merges the SAME delta snapshot — writes landing mid-flight wait
        # for the next batch (per-batch snapshot isolation)
        try:
            raw, view = self._submit_dyn(self.base_plan, qb)
        except StaleEpoch:  # a compaction swapped under the base plan
            self._refresh_base_plan()
            raw, view = self._submit_dyn(self.base_plan, qb)
        td1 = time.perf_counter_ns()
        if live is not None:
            t.end(live, t1=td1)
        meta = _BatchMeta(
            bid=bid, n_padded=int(qb.op.shape[0]),
            tc0=tc0 or td0, tc1=tc1 or td0, td0=td0, td1=td1,
        )
        self._bid += 1
        self._inflight.append((raw, reqs, meta, qb, view))
        self._c["batches"].inc()
        self._c["lanes"].inc(len(reqs))
        m = obs.STATE.metrics
        if m is not None:
            m.histogram("broker.batch_occupancy").observe(
                len(reqs) / meta.n_padded
            )
            m.gauge("broker.queue_depth").set(len(self._queue))
            h = m.histogram("broker.queue_wait_ms", LATENCY_MS_BUCKETS)
            for r in reqs:
                h.observe((td0 * 1e-9 - r.t_submit) * 1e3)

    def _encode(self, reqs: list[_Req], pad_to: int) -> eng.ServeBatch:
        n = max(pad_to, self._padded_batch(len(reqs)))
        op = np.full(n, -1, np.int32)  # dead lanes: masked to zero output
        s = np.zeros(n, np.int32)
        p = np.zeros(n, np.int32)
        o = np.zeros(n, np.int32)
        for i, r in enumerate(reqs):
            op[i], s[i], p[i], o[i] = r.op, r.s, r.p, r.o
        return eng.ServeBatch(op=op, s=s, p=p, o=o)

    def _submit_dyn(self, plan, qb: eng.ServeBatch):
        """Static-lane dispatch for a possibly-dynamic store: sanitize
        lanes whose constants exceed the static extents (delta-only ids
        must not reach the device), submit raw, and return the pinned
        ``(raw, view)`` pair — the caller merges decode-time with the SAME
        view.  ``view`` is None for static stores / empty deltas."""
        view = self.engine.dynamic_view()
        qb_run = qb if view is None else view.sanitize_batch(qb)
        return plan.submit(qb_run), view

    def _slot(self, bid: int) -> str:
        return self._slots[bid % len(self._slots)]

    def _padded_batch(self, b: int) -> int:
        """pow2 bucket (>= 8), then data-axis divisibility when sharded."""
        n = 8
        while n < b:
            n <<= 1
        cfg = self.config
        if cfg.mesh is not None:
            d = int(np.prod([cfg.mesh.shape[a] for a in cfg.data_axes]))
            n = ((max(n, d) + d - 1) // d) * d
        return n

    # -- streamed decode + per-tenant growth ----------------------------

    async def _deliver(self, raw, reqs: list[_Req], meta: _BatchMeta,
                       qb: eng.ServeBatch, view):
        has_u = any(r.op in eng._UNBOUNDED_OPS for r in reqs)
        slot = self._slot(meta.bid)
        meta.tf0 = time.perf_counter_ns()

        # the blocking device->host fetch (and the host-side delta merge,
        # when the store is dynamic) runs off-loop so submitters keep
        # filling the next batch while this one decodes.  Each stage that
        # begins and ends on this thread is also a live span there when
        # tracing is on (so ``ObsConfig(device_annotations=True)`` puts it
        # in the profiler's own trace)
        def fetch():
            t = obs.STATE.tracer
            meta.tw0 = time.perf_counter_ns()
            live = _stage(t, "broker.device_wait", meta.tw0, slot, meta.bid)
            eng.wait_result(raw)
            meta.tw1 = time.perf_counter_ns()
            if live is not None:
                t.end(live, t1=meta.tw1)
                live = _stage(t, "broker.copy", meta.tw1, slot, meta.bid)
            # the result is ready: what is left of the fetch is the copy
            host = eng.host_result(raw, unbounded=has_u and self.unbounded)
            meta.tw2 = meta.tw3 = time.perf_counter_ns()
            if live is not None:
                # the u_* placeholders of a bounded fetch hold no bytes
                fetched = [a.nbytes for a in host if a.nbytes]
                t.end(live, t1=meta.tw2, bytes=sum(fetched),
                      arrays=len(fetched))
            if view is not None:
                live = _stage(t, "broker.merge", meta.tw2, slot, meta.bid)
                # merge against the ORIGINAL (unsanitized) lane constants:
                # lanes masked off the device get delta-only answers
                host = view.merge_lanes(qb.op, qb.s, qb.p, qb.o, host)
                meta.tw3 = time.perf_counter_ns()
                if live is not None:
                    t.end(live, t1=meta.tw3)
            return host

        host = await asyncio.to_thread(fetch)
        meta.tf1 = time.perf_counter_ns()
        retry_tenants = {
            reqs[i].tenant
            for i in np.nonzero(host.overflow[: len(reqs)])[0]
        }
        for i, r in enumerate(reqs):
            # streamed delivery: every lane of an unaffected tenant
            # resolves here, before any retry work happens
            if r.tenant not in retry_tenants:
                self._resolve(r, eng.decode_lane(r.op, host, i))
        for tenant in sorted(retry_tenants):
            # per-tenant FIFO: the whole segment of a tenant with a
            # retried lane is held and re-released in submission order
            segment = [(i, r) for i, r in enumerate(reqs) if r.tenant == tenant]
            await self._retry_tenant(tenant, segment, host)
        if obs.STATE.tracer is not None:
            self._trace_batch(reqs, meta)

    def _trace_batch(self, reqs: list[_Req], meta: _BatchMeta):
        """Emit the batch's retroactive spans now that every timestamp of
        its lifetime is known: a fixed number per batch, none per lane.

        Batch stages land as complete spans on a bounded pool of
        ``batch-slot-*`` tracks (slot = ``bid`` mod ``2 * max_inflight``
        — the inflight bound guarantees a slot's previous occupant fully
        delivered before reuse, so same-track spans never overlap), beside
        the live ``broker.dispatch``, ``broker.device_wait``,
        ``broker.copy`` and ``broker.merge`` spans recorded as they ran.
        Each query's lifetime lands as one Chrome *async* ``query`` span
        keyed by its ``seq`` (its ``bid`` links it to the batch's stages),
        with its ``queue`` phase (submit to dispatch) nested under it.
        """
        t = obs.STATE.tracer
        t_end = time.perf_counter_ns()
        slot = self._slot(meta.bid)
        occupancy = len(reqs) / meta.n_padded
        t.add("broker.batch", meta.tc0, t_end, tid=slot, cat="broker",
              bid=meta.bid, lanes=len(reqs), padded=meta.n_padded,
              occupancy=round(occupancy, 4))
        for name, a, b in (
            ("broker.coalesce", meta.tc0, meta.tc1),
            ("broker.inflight", meta.td1, meta.tf0),
            ("broker.handoff", meta.tf0, meta.tw0),
            ("broker.resume", meta.tw3, meta.tf1),
            ("broker.decode_deliver", meta.tf1, t_end),
        ):
            t.add(name, a, b, tid=slot, cat="broker", bid=meta.bid)
        for i, r in enumerate(reqs):
            t0 = int(r.t_submit * 1e9)  # perf_counter s -> ns
            td = int(r.t_deliver * 1e9) if r.t_deliver else t_end
            t.add_async("query", r.seq, t0, td,
                        tenant=r.tenant, op=r.op, lane=i, bid=meta.bid)
            t.add_async("queue", r.seq, t0, min(meta.td0, td))

    def _resolve(self, r: _Req, value):
        st = self._tenants[r.tenant]
        st.pending -= 1
        st.completed += 1
        r.t_deliver = time.perf_counter()
        lat = r.t_deliver - r.t_submit
        st.lat_s.append(lat)
        m = obs.STATE.metrics
        if m is not None:
            m.histogram(
                "broker.query_latency_ms", LATENCY_MS_BUCKETS
            ).observe(lat * 1e3)
        if not r.future.cancelled():
            r.future.set_result(value)

    def _fail(self, r: _Req, exc: BaseException):
        st = self._tenants[r.tenant]
        st.pending -= 1
        st.failed += 1
        r.t_deliver = time.perf_counter()
        if not r.future.cancelled():
            r.future.set_exception(exc)

    async def _retry_tenant(self, tenant, segment, host):
        """Re-run a tenant's overflowed lanes on doubled-cap plans, then
        release its held segment in submission order."""
        grow = [(i, r) for (i, r) in segment if bool(host.overflow[i])]
        try:
            done = await asyncio.to_thread(
                self._grow_and_run, tenant, [r for (_, r) in grow]
            )
            regrown, err = dict(zip((i for i, _ in grow), done)), None
        except (CapOverflow, AdmissionError) as e:
            regrown, err = {}, e
        for i, r in segment:
            if i in regrown:
                self._resolve(r, regrown[i])
            elif err is not None and bool(host.overflow[i]):
                self._fail(r, err)
            else:
                self._resolve(r, eng.decode_lane(r.op, host, i))

    def _grow_and_run(self, tenant: str, rs: list[_Req]):
        """Blocking (off-loop) escalation: double the cap from the tenant's
        remembered level until the lanes fit or the budget runs out."""
        st = self._tenants[tenant]
        pol = self.tenant_policy
        level = max(st.cap_level, 1)
        while True:
            if level > pol.max_cap_doublings:
                raise CapOverflow(
                    f"tenant {tenant!r} exhausted its cap budget "
                    f"(max_cap_doublings={pol.max_cap_doublings})"
                )
            cap = self.config.cap << level
            cfg = self.config.replace(cap=cap, cap_y=self.config.cap_y << level)
            try:
                plan = self.engine.compile(
                    self._query, cfg, admit=self._admit(st)
                )
            except AdmissionError:
                st.admission_denials += 1
                self._c["admission_denials"].inc()
                raise
            st.cap_growth_events += 1
            self._c["cap_growth_events"].inc()
            st.cap_level = max(st.cap_level, level)
            self._retry_cfgs.add(cfg)
            with obs.span("broker.retry", cat="broker", tenant=tenant,
                          level=level, cap=cap, lanes=len(rs)):
                qb = self._encode(rs, 0)
                try:
                    raw, view = self._submit_dyn(plan, qb)
                except StaleEpoch:  # compaction swapped mid-retry
                    plan = self.engine.compile(
                        self._query, cfg, admit=self._admit(st)
                    )
                    raw, view = self._submit_dyn(plan, qb)
                host = eng.host_result(
                    raw, unbounded=any(r.op in eng._UNBOUNDED_OPS for r in rs),
                )
                if view is not None:
                    host = view.merge_lanes(qb.op, qb.s, qb.p, qb.o, host)
            if not host.overflow[: len(rs)].any():
                return [
                    eng.decode_lane(r.op, host, i) for i, r in enumerate(rs)
                ]
            level += 1

    def _admit(self, st: _TenantState):
        """The per-tenant plan-cache admission closure: charge MISSES
        against ``max_plans`` (the engine never calls this on a hit)."""

        def admit(_key):
            if st.plans_charged >= self.tenant_policy.max_plans:
                return False
            st.plans_charged += 1
            return True

        return admit

    def _tenant(self, name: str) -> _TenantState:
        st = self._tenants.get(name)
        if st is None:
            st = self._tenants[name] = _TenantState(name)
        return st

    # -- stats ----------------------------------------------------------

    def reset_stats(self) -> None:
        """Zero EVERY counter ``stats()`` reports, global and per-tenant
        (flush reasons, shed / cap-growth / admission-denial counts, queue
        peak, latency samples, insert / delete / compaction counts) — the
        benchmark warmup boundary.  Admission and write-budget STATE
        (``cap_level``, ``plans_charged``, ``writes_resident``) is
        retained: those are live budgets governing future admissions, not
        measurements — and ``delta_triples`` / ``tombstones`` in
        ``stats()`` are live gauges of the store, unaffected by reset."""
        self.metrics.reset()
        self._queue_peak = 0
        for st in self._tenants.values():
            st.lat_s.clear()
            st.completed = st.failed = st.shed = 0
            st.cap_growth_events = st.admission_denials = 0
            st.inserts = st.deletes = 0

    def stats(self) -> dict:
        """Structured serving stats (JSON-ready).  ``delta_triples`` and
        ``tombstones`` are LIVE store gauges (0 for static stores);
        everything else is counted since the last ``reset_stats``."""
        all_lat = [t for st in self._tenants.values() for t in st.lat_s]
        batches = self._c["batches"].value
        store = self.engine.store
        d = store.delta if isinstance(store, dyn.DynamicStore) else None
        return {
            "batches": batches,
            "lanes": self._c["lanes"].value,
            "coalesce_factor": (
                self._c["lanes"].value / batches if batches else 0.0
            ),
            "flush_size": self._c["flush_size"].value,
            "flush_deadline": self._c["flush_deadline"].value,
            "flush_drain": self._c["flush_drain"].value,
            "queue_depth": len(self._queue),
            "queue_peak": self._queue_peak,
            "selects": self._c["selects"].value,
            "shed": self._c["shed"].value,
            "cap_growth_events": self._c["cap_growth_events"].value,
            "admission_denials": self._c["admission_denials"].value,
            "inserts": self._c["inserts"].value,
            "deletes": self._c["deletes"].value,
            "compactions": self._c["compactions"].value,
            "compaction_ms": self._c["compaction_ms"].value,
            "compaction_errors": self._c["compaction_errors"].value,
            "delta_triples": d.n_inserts if d is not None else 0,
            "tombstones": d.n_tombstones if d is not None else 0,
            "queries": len(all_lat),
            "p50_ms": _ms(tail_percentile(all_lat, 50)),
            "p99_ms": _ms(tail_percentile(all_lat, 99)),
            "tenants": {
                name: {
                    "queries": st.completed,
                    "failed": st.failed,
                    "shed": st.shed,
                    "pending": st.pending,
                    "cap_level": st.cap_level,
                    "plans_charged": st.plans_charged,
                    "cap_growth_events": st.cap_growth_events,
                    "inserts": st.inserts,
                    "deletes": st.deletes,
                    "writes_resident": st.writes_resident,
                    "p50_ms": _ms(tail_percentile(st.lat_s, 50)),
                    "p99_ms": _ms(tail_percentile(st.lat_s, 99)),
                }
                for name, st in sorted(self._tenants.items())
            },
        }

    def cost_profiles(self) -> dict:
        """Static XLA cost profiles of every program this broker has
        served through: the shared base plan at its dispatch geometry,
        plus each doubled-cap retry level any tenant ever compiled
        (cache hits — profiling never charges admission quotas)."""
        out = {"base": self.base_plan.cost_profile(
            self._encode([], self._pad_to)
        )}
        for cfg in sorted(self._retry_cfgs, key=lambda c: c.cap):
            plan = self.engine.compile(self._query, cfg)
            out[f"retry_cap_{cfg.cap}"] = plan.cost_profile(
                self._encode([], 0)
            )
        return out


def _stage(t, name: str, t0: int, slot: str, bid: int):
    """Open batch ``bid``'s live stage span ``name`` at ``t0`` on its slot's
    track; ``None`` with tracing off (``t`` is ``obs.STATE.tracer``)."""
    if t is None:
        return None
    return t.begin(name, t0=t0, tid=slot, cat="broker", bid=bid)


def _ms(v: float | None) -> float | None:
    return None if v is None else v * 1e3
