"""Low-overhead host-side span tracer with a Chrome ``trace_event`` export.

The serve stack's timeline instrument: a fixed-capacity ring buffer of
spans stamped with the monotonic clock (``time.perf_counter_ns`` — the
same clock base the broker's latency samples use), recorded either live
(``begin``/``end`` or the ``span`` context manager) or retroactively
(``add``/``add_async`` with explicit timestamps — how the broker emits
the stages of a batch that span an ``await``, and its per-query spans,
at delivery time, when every timestamp of the batch is known).  A live
span may take its ends from stamps the caller already took
(``begin(t0=)``, ``end(t1=)``), so live and retroactive stages of one
batch meet end to end.

Design rules:

* **Disabled is free.**  The tracer only exists while observability is
  enabled (``repro.obs.enable``); every instrumentation site guards on
  ``obs.STATE.tracer is None`` — one attribute read and one branch, no
  tracer method calls, no allocation (``tests/test_obs.py`` tripwires
  this the same way ``test_no_env_read_inside_plan_call`` bans env reads
  in compiled plan calls).
* **Recording never blocks the serve path, and allocates nothing that
  lives on.**  A record is a row of preallocated columns (kind, interned
  name, category, track and argument names, the two timestamps, the
  argument values) written under a (practically uncontended) lock; no
  per-record dict or tuple outlives the call, so a traced window adds no
  objects for the garbage collector to walk.  When the ring wraps, the
  OLDEST spans are dropped and counted (``dropped``) — tracing a long run
  degrades to a suffix window, never to back-pressure.  ``events()``
  builds the record dicts once, at export.
* **Hierarchy is time containment.**  Spans carry a track id (``tid`` —
  the thread id by default, or an explicit string track like
  ``"batch-slot-0"``); within a track, nesting is by interval
  containment, exactly the Chrome/Perfetto model, so no parent pointers
  are threaded through async hops.  Overlapping per-query lifetimes ride
  Chrome *async* events (``ph: "b"/"e"`` with an ``id``) instead, which
  Perfetto renders as per-id nested tracks.

The optional ``jax.profiler`` bridge (``annotate=True``) wraps every live
span in a ``jax.profiler.TraceAnnotation`` so a device profile captured
around the same run carries the same span names.
"""

from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["Tracer", "NOOP_SPAN"]

_X, _ASYNC, _INSTANT = 0, 1, 2  # record kinds, the ``_KINDS`` of the export
_KINDS = ("X", "async", "I")
_NARGS = 4  # arguments a record keeps in columns; more keep their dict whole


class _NoopSpan:
    """The shared disabled-path context manager: no state, no effect."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _LiveSpan:
    """Handle for an open ``begin``/``end`` span."""

    __slots__ = ("name", "cat", "t0", "tid", "args", "ann")

    def __init__(self, name, cat, t0, tid, args, ann):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.tid = tid
        self.args = args
        self.ann = ann


class _SpanCM:
    __slots__ = ("tracer", "live", "name", "attrs")

    def __init__(self, tracer, name, attrs):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.live = None

    def __enter__(self):
        self.live = self.tracer.begin(self.name, **self.attrs)
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is not None:
            self.live.args = dict(self.live.args, error=exc_type.__name__)
        self.tracer.end(self.live)
        return False


class Tracer:
    """Ring-buffered hierarchical span recorder.

    All timestamps are ``time.perf_counter_ns`` integers (``Tracer.now``);
    retroactive ``add*`` callers holding ``time.perf_counter`` float
    seconds convert with ``int(t * 1e9)`` — same clock, same epoch.
    """

    def __init__(self, capacity: int = 1 << 16, *, annotate: bool = False):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.annotate = annotate
        # one row per record; names, categories, tracks and argument-name
        # tuples are interned into ``_atoms``, argument values and async ids
        # sit in object columns (numpy object arrays are not gc-tracked)
        self._kind = np.zeros(capacity, np.int8)
        self._name = np.zeros(capacity, np.int32)
        self._cat = np.zeros(capacity, np.int32)
        self._tid = np.zeros(capacity, np.int32)
        self._keys = np.zeros(capacity, np.int32)  # -1: the args dict whole
        self._t0 = np.zeros(capacity, np.int64)
        self._t1 = np.zeros(capacity, np.int64)
        self._aid = np.empty(capacity, object)
        self._vals = np.empty((capacity, _NARGS), object)
        self._atoms: list = []
        self._atom_id: dict = {}
        self._n = 0  # total records ever (ring cursor = _n % capacity)
        self._lock = threading.Lock()
        self.t_epoch = time.perf_counter_ns()
        self._profiler = None
        if annotate:
            import jax.profiler  # deferred: only the bridge needs it

            self._profiler = jax.profiler

    # -- recording ------------------------------------------------------

    @staticmethod
    def now() -> int:
        return time.perf_counter_ns()

    def _atom(self, v) -> int:
        i = self._atom_id.get(v)
        if i is None:
            i = self._atom_id[v] = len(self._atoms)
            self._atoms.append(v)
        return i

    def _record(self, kind: int, name: str, cat: str, tid, aid, t0: int,
                t1: int, args: dict) -> None:
        with self._lock:
            i = self._n % self.capacity
            self._n += 1
            self._kind[i] = kind
            self._name[i] = self._atom(name)
            self._cat[i] = self._atom(cat)
            self._tid[i] = self._atom(tid)
            self._t0[i] = t0
            self._t1[i] = t1
            self._aid[i] = aid
            if len(args) <= _NARGS:
                self._keys[i] = self._atom(tuple(args))
                for j, v in enumerate(args.values()):
                    self._vals[i, j] = v
            else:
                self._keys[i] = -1
                self._vals[i, 0] = args

    def begin(self, name: str, *, cat: str = "", tid=None, t0: int | None = None,
              **attrs) -> _LiveSpan:
        """Open a live span on the current thread's track (or on ``tid``),
        starting now or at ``t0`` (a ``perf_counter_ns`` stamp the caller
        already took)."""
        ann = None
        if self._profiler is not None:
            ann = self._profiler.TraceAnnotation(name)
            ann.__enter__()
        return _LiveSpan(
            name, cat, time.perf_counter_ns() if t0 is None else t0, tid,
            attrs, ann,
        )

    def end(self, live: _LiveSpan, *, t1: int | None = None, **extra) -> None:
        """Close ``live`` now or at ``t1``; ``extra`` joins its args."""
        if t1 is None:
            t1 = time.perf_counter_ns()
        if live.ann is not None:
            live.ann.__exit__(None, None, None)
        self._record(
            _X, live.name, live.cat,
            live.tid if live.tid is not None else threading.get_ident(), None,
            live.t0, t1, dict(live.args, **extra) if extra else live.args,
        )

    def span(self, name: str, **attrs) -> _SpanCM:
        """``with tracer.span("engine.compile", shape=...):`` — live span."""
        return _SpanCM(self, name, attrs)

    def add(self, name: str, t0: int, t1: int, *, tid=None, cat: str = "",
            **attrs) -> None:
        """Retroactive complete span with explicit ns timestamps."""
        self._record(
            _X, name, cat, tid if tid is not None else threading.get_ident(),
            None, int(t0), int(t1), attrs,
        )

    def add_async(self, name: str, aid, t0: int, t1: int, *,
                  cat: str = "query", **attrs) -> None:
        """Retroactive async (overlappable) span — one ``b``/``e`` pair
        under ``id=aid`` in the Chrome export.  Same-id slices nest by
        time, so per-query phase breakdowns share the query's id."""
        self._record(_ASYNC, name, cat or "async", 0, aid, int(t0), int(t1),
                     attrs)

    def instant(self, name: str, *, tid=None, **attrs) -> None:
        t = time.perf_counter_ns()
        self._record(
            _INSTANT, name, "", tid if tid is not None else threading.get_ident(),
            None, t, t, attrs,
        )

    # -- inspection -----------------------------------------------------

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wrap (oldest-first)."""
        return max(0, self._n - self.capacity)

    def events(self) -> list[dict]:
        """Retained records, oldest first, as dicts: ``kind`` ("X",
        "async" or "I"), ``name``, ``cat``, ``t0``, ``t1``, ``tid``,
        ``args`` and, on async records, ``id``."""
        with self._lock:
            n, cap = self._n, self.capacity
            rows = np.arange(n - cap, n) % cap if n > cap else np.arange(n)
            cols = [c[rows].tolist() for c in (
                self._kind, self._name, self._cat, self._tid, self._keys,
                self._t0, self._t1, self._aid)]
            vals = self._vals[rows].tolist()
            atoms = list(self._atoms)
        out = []
        for kind, name, cat, tid, keys, t0, t1, aid, v in zip(*cols, vals):
            args = v[0] if keys < 0 else dict(zip(atoms[keys], v))
            e = {"kind": _KINDS[kind], "name": atoms[name], "cat": atoms[cat]}
            if kind == _ASYNC:
                e["id"] = aid
            e.update(t0=t0, t1=t1, tid=atoms[tid], args=args)
            out.append(e)
        return out

    def clear(self) -> None:
        """Drop everything recorded so far (the warmup boundary)."""
        with self._lock:
            self._aid.fill(None)
            self._vals.fill(None)
            self._n = 0
            self.t_epoch = time.perf_counter_ns()

    # -- Chrome trace_event export --------------------------------------

    def to_chrome(self, *, metadata: dict | None = None) -> dict:
        """The Perfetto-loadable ``{"traceEvents": [...]}`` object.

        Complete spans become ``ph: "X"`` events nested by time per
        track; async records become ``ph: "b"``/``"e"`` pairs; string
        track ids are mapped to integer tids with ``thread_name``
        metadata so Perfetto shows readable track names.
        """
        events = self.events()
        t_base = min((e["t0"] for e in events), default=self.t_epoch)
        tids: dict = {}

        def tid_of(raw):
            if raw not in tids:
                tids[raw] = len(tids) + 1
            return tids[raw]

        out = []
        for e in events:
            ts = (e["t0"] - t_base) / 1e3  # us
            args = {k: _jsonable(v) for k, v in e["args"].items()}
            if e["kind"] == "X":
                out.append({
                    "ph": "X", "name": e["name"], "cat": e["cat"] or "span",
                    "ts": ts, "dur": max(0.0, (e["t1"] - e["t0"]) / 1e3),
                    "pid": 1, "tid": tid_of(e["tid"]), "args": args,
                })
            elif e["kind"] == "async":
                common = {
                    "name": e["name"], "cat": e["cat"], "id": str(e["id"]),
                    "pid": 1, "tid": 0,
                }
                out.append({"ph": "b", "ts": ts, "args": args, **common})
                out.append({
                    "ph": "e", "ts": (e["t1"] - t_base) / 1e3, **common,
                })
            else:  # instant
                out.append({
                    "ph": "i", "name": e["name"], "cat": e["cat"] or "span",
                    "ts": ts, "s": "t", "pid": 1, "tid": tid_of(e["tid"]),
                    "args": args,
                })
        for raw, tid in tids.items():
            out.append({
                "ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                "args": {"name": raw if isinstance(raw, str) else f"thread-{raw}"},
            })
        trace = {"traceEvents": out, "displayTimeUnit": "ms"}
        if self.dropped:
            trace["droppedSpans"] = self.dropped
        if metadata:
            trace["otherData"] = {k: _jsonable(v) for k, v in metadata.items()}
        return trace


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)
