"""The compiled-plan query API: ``Query`` → ``Engine.compile(ExecConfig)`` → ``Plan``.

This module is the ONE place execution knobs enter the system.  A query is
described declaratively (variables as ``"?name"`` strings or ``None`` for
anonymous, constants as 1-based dictionary ids), paired with a frozen,
hashable :class:`ExecConfig`, and lowered by ``Engine.compile`` into a
:class:`Plan` — a compile-once / run-many handle over the serve IR
(``core.engine.make_serve_step`` / ``make_sharded_serve_step``).

Query kinds
-----------

``TriplePatternQ(s, p, o)``
    Any of the paper's eight triple patterns.  Bound positions are ints,
    free positions are variables.  The *shape* (which positions are bound)
    selects the compiled program; the ids themselves are runtime inputs,
    so ``compile`` is amortized across every query of the same shape.

``JoinQ(category, vpos1, vpos2, p1, c1, p2, c2)``
    The paper's join categories A–F (``core.joins``).

``BgpQ(patterns)``
    A basic graph pattern — conjunction of triple patterns with shared
    variables — planned and executed by ``core.optimizer`` through the
    same serve-step machinery.

``ServeQ(unbounded)``
    The raw serve-IR passthrough: ``Plan(batch)`` takes a ``ServeBatch``
    spanning every keyed + unbounded op and returns the ``ServeResult``
    — the multi-tenant production surface.

Execution config
----------------

:class:`ExecConfig` is a frozen dataclass — hashable, so it keys plan and
program caches directly.  ``ExecConfig.from_env()`` is the ONLY sanctioned
consumer of the legacy ``REPRO_SCAN_BACKEND`` / ``REPRO_PALLAS_INTERPRET``
environment flags: it reads them once into an explicit config; nothing on
a compiled ``Plan.__call__`` path consults ``os.environ``
(tests/test_backend_flag.py enforces this).

Cap policy
----------

Fixed result capacities are what make the whole pipeline jit-able; the
PR-4 contract is that truncation is never silent.  :class:`CapPolicy`
upgrades "never silent" to "self-healing": on overflow the plan recompiles
at doubled cap (up to ``max_doublings``) and re-runs, so callers get the
complete answer without hand-tuning ``cap``.  ``grow=False`` restores the
raise-on-overflow behavior (:class:`CapOverflow`).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

Term = Any  # int (bound 1-based id) | str "?name" | None (anonymous variable)

SCAN_BACKENDS = ("pallas", "jnp")
PRED_INDEX_LAYOUTS = ("dac", "fixed")


class CapOverflow(RuntimeError):
    """A fixed-capacity result buffer truncated and the policy forbids (or
    exhausted) growth.  Subclasses ``RuntimeError`` so pre-redesign callers
    catching the old truncation errors keep working."""


class AdmissionError(RuntimeError):
    """Plan-cache admission denied: compiling this plan would exceed the
    caller's budget.  Raised by ``Engine.compile(..., admit=fn)`` when the
    ``admit`` callback vetoes a cache MISS — cache hits are never charged,
    so shared already-compiled programs stay free.  The multi-tenant serve
    broker translates per-tenant plan quotas into this."""


def _backend() -> str:
    import jax

    return jax.default_backend()


def default_interpret() -> bool:
    """The ONE definition of the auto interpret default: compiled kernels
    on a TPU backend, the Pallas interpreter on the CPU.  Deterministic —
    consulted by ``ExecConfig.resolved()`` and ``kernels.ops.resolve_exec``
    alike, never by reading the environment.  Any other backend is an
    error: the kernels are written for the TPU, and interpreting them
    there would hide that the device is not in use."""
    backend = _backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need a TPU backend (or the CPU interpreter); "
        f"JAX runs on {backend!r} — use backend='jnp' there"
    )


def check_interpret(interpret: bool, where: str) -> bool:
    """Refuse Pallas interpret mode on a TPU backend: there the kernels run
    compiled, and an interpreter would quietly keep them off the chip."""
    if interpret and _backend() == "tpu":
        raise ValueError(
            f"{where}: Pallas interpret mode requested on a TPU backend; "
            f"the kernels run compiled there (unset REPRO_PALLAS_INTERPRET "
            f"or set it to 0, and pass interpret=None or False)"
        )
    return interpret


def is_var(t: Term) -> bool:
    """Variables are ``None`` (anonymous) or ``"?name"`` strings."""
    return t is None or isinstance(t, str)


@dataclasses.dataclass(frozen=True)
class CapPolicy:
    """What a plan does when a result buffer overflows its cap.

    ``grow=True``: recompile at doubled cap and re-run, at most
    ``max_doublings`` times (the doubled programs land in the same program
    cache, so a grown plan stays warm).  ``grow=False``: raise
    :class:`CapOverflow` immediately.
    """

    grow: bool = True
    max_doublings: int = 6


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Frozen, hashable execution config — the only way knobs reach a plan.

    ``backend``
        Traversal substrate: "pallas" (batched TPU kernels) or "jnp"
        (vmapped reference traversal).
    ``interpret``
        Pallas interpret mode.  ``None`` = auto (compiled on a TPU, the
        interpreter on the CPU) — resolved once at compile time, never
        from the environment.  ``True`` on a TPU backend is an error.
    ``cap`` / ``cap_y``
        Result capacities: ``cap`` for scan/side-list/X lanes, ``cap_y``
        for the re-bind (Y) lanes of join categories D–F.
    ``cap_policy``
        Overflow handling; see :class:`CapPolicy`.
    ``use_pred_index``
        Serve unbounded-``?P`` lanes through the SP/OP predicate index
        (k²-triples+) when the store carries one; ``False`` forces the
        all-preds sweep fallback.
    ``u_width_quantile``
        Sizes the unbounded candidate lane at this quantile of the
        per-entity predicate-degree distribution (per axis: the width is
        ``max(quantile(SP degrees), quantile(OP degrees))``) instead of
        ``max_degree``.  Outlier entities whose candidate list exceeds the
        lane (the index's ``truncated`` bit) are routed to the all-preds
        sweep fallback, so answers stay exact.  ``1.0`` = exact sizing
        from ``max_degree`` (no outliers).
    ``pred_index_layout``
        On-device layout of the SP/OP predicate index: "dac" (default —
        multi-level DAC(b=8) chunks + flag bitmaps, decoded inside the
        gather kernel) or "fixed" (byte-packed fallback).  Part of the
        program cache key, so plans over different layouts coexist;
        results are bit-identical across layouts (the differential suite
        enforces this).
    ``donate_batch``
        Donate the per-batch query-key buffers (the ``ServeBatch`` /
        lane arrays) to the compiled serve-step program
        (``jax.jit(donate_argnums=...)``), letting XLA alias their device
        memory for outputs on the hot dispatch path.  The engine copies
        caller-held device arrays defensively before a donating call, so
        semantics don't change; host (numpy) inputs are unaffected.
        Ignored (off) for sharded programs.
    ``mesh`` / ``data_axes`` / ``model_axis``
        When ``mesh`` is set, plans compile the shard_map'd serve step:
        forest sharded by predicate over ``model_axis``, query batches
        over ``data_axes``.
    """

    backend: str = "pallas"
    interpret: bool | None = None
    cap: int = 4096
    cap_y: int = 256
    cap_policy: CapPolicy = CapPolicy()
    use_pred_index: bool = True
    u_width_quantile: float = 1.0
    pred_index_layout: str = "dac"
    donate_batch: bool = True
    mesh: Any = None  # jax.sharding.Mesh | None (Mesh is hashable)
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"

    def __post_init__(self):
        if self.backend not in SCAN_BACKENDS:
            raise ValueError(
                f"unknown scan backend {self.backend!r} (want one of {SCAN_BACKENDS})"
            )
        if not (0.0 < self.u_width_quantile <= 1.0):
            raise ValueError(
                f"u_width_quantile must be in (0, 1], got {self.u_width_quantile}"
            )
        if self.cap < 1 or self.cap_y < 1:
            raise ValueError("cap and cap_y must be >= 1")
        if self.pred_index_layout not in PRED_INDEX_LAYOUTS:
            raise ValueError(
                f"unknown pred_index_layout {self.pred_index_layout!r} "
                f"(want one of {PRED_INDEX_LAYOUTS})"
            )

    @classmethod
    def from_env(cls, **overrides) -> "ExecConfig":
        """The one-time environment read.

        Folds the legacy ``REPRO_SCAN_BACKEND`` / ``REPRO_PALLAS_INTERPRET``
        flags into an explicit config ONCE, at call time; the returned
        config carries concrete values, so nothing downstream re-reads the
        environment.  ``overrides`` are applied on top.
        """
        if "backend" not in overrides:
            overrides["backend"] = os.environ.get("REPRO_SCAN_BACKEND", "pallas")
        if "interpret" not in overrides:
            # tri-state: unset -> auto (default_interpret), "0" -> force
            # compiled, anything else -> force interpret — an error on a
            # TPU backend, never a quiet fallback
            raw = os.environ.get("REPRO_PALLAS_INTERPRET")
            overrides["interpret"] = check_interpret(
                default_interpret() if raw is None else raw != "0",
                "ExecConfig.from_env (REPRO_PALLAS_INTERPRET)",
            )
        if "pred_index_layout" not in overrides:
            overrides["pred_index_layout"] = os.environ.get(
                "REPRO_PRED_INDEX_LAYOUT", "dac"
            )
        return cls(**overrides)

    def resolved(self) -> "ExecConfig":
        """Fill ``interpret=None`` with :func:`default_interpret`.

        Deterministic — depends on the jax backend, never the environment.
        ``interpret=True`` on a TPU backend is an error.
        """
        if self.interpret is not None:
            check_interpret(self.interpret, "ExecConfig.resolved")
            return self
        return dataclasses.replace(self, interpret=default_interpret())

    def replace(self, **kw) -> "ExecConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Frozen observability config — the knobs behind ``repro.obs.enable``.

    ``trace``
        Record host-side spans into a ring-buffered tracer (exported as
        Chrome ``trace_event`` JSON, loadable in Perfetto).
    ``metrics``
        Record timing histograms / gauges into the global
        ``repro.obs`` metrics registry.  (The broker's own bookkeeping
        registry backing ``ServeBroker.stats()`` is always on; this knob
        governs only the obs-layer extras.)
    ``trace_capacity``
        Ring size in spans; when full, the OLDEST spans are dropped and
        counted — a long run degrades to a suffix window, never to
        back-pressure.
    ``device_annotations``
        Bridge live spans into ``jax.profiler.TraceAnnotation`` so a
        device profile captured around the same run carries the same
        span names.
    """

    trace: bool = True
    metrics: bool = True
    trace_capacity: int = 1 << 16
    device_annotations: bool = False

    def __post_init__(self):
        if self.trace_capacity < 1:
            raise ValueError("trace_capacity must be >= 1")

    def replace(self, **kw) -> "ObsConfig":
        return dataclasses.replace(self, **kw)


def run_with_policy(policy: CapPolicy, cap: int, cap_y: int, fn):
    """Run ``fn(cap, cap_y)`` under the cap policy.

    On :class:`CapOverflow` both caps double (the rebind ``cap_y`` lanes
    overflow under the same conditions as the X lanes) and ``fn`` re-runs,
    at most ``policy.max_doublings`` times.  Returns
    ``(result, cap, cap_y)`` so callers can persist the grown caps.
    """
    doublings = 0
    while True:
        try:
            return fn(cap, cap_y), cap, cap_y
        except CapOverflow:
            if not policy.grow or doublings >= policy.max_doublings:
                raise
            doublings += 1
            cap *= 2
            cap_y *= 2


# ---------------------------------------------------------------------------
# query descriptions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TriplePatternQ:
    """One triple pattern: ints bind a position, ``"?x"``/``None`` free it."""

    s: Term = None
    p: Term = None
    o: Term = None

    @property
    def bound(self) -> tuple[bool, bool, bool]:
        return (not is_var(self.s), not is_var(self.p), not is_var(self.o))

    @property
    def variables(self) -> tuple[str, ...]:
        # a named variable may legitimately repeat inside a BgpQ pattern
        # (join-on-self semantics, handled by the optimizer); a standalone
        # TriplePatternQ plan rejects that at compile time
        return tuple(
            t for t in (self.s, self.p, self.o) if isinstance(t, str)
        )


JOIN_CATEGORIES = "ABCDEF"
# which of (p1, c1, p2, c2) each category requires (vpos1/vpos2 always)
_JOIN_FIELDS = {
    "A": ("p1", "c1", "p2", "c2"),
    "B": ("p1", "c1", "c2"),
    "C": ("c1", "c2"),
    "D": ("p1", "c1", "p2"),
    "E": ("p1", "c1"),
    "F": ("c1",),
}


@dataclasses.dataclass(frozen=True)
class JoinQ:
    """A paper join category A–F (two patterns sharing variable ?X).

    ``vpos1``/``vpos2`` name the position ("s"/"o") of ?X in each pattern;
    ``p*``/``c*`` are the bound predicate / non-join constant of each side
    (which ones are required depends on the category — see
    ``core.joins``).
    """

    category: str
    vpos1: str
    vpos2: str
    p1: int | None = None
    c1: int | None = None
    p2: int | None = None
    c2: int | None = None

    def __post_init__(self):
        if self.category not in JOIN_CATEGORIES:
            raise ValueError(f"unknown join category {self.category!r}")
        if self.vpos1 not in ("s", "o") or self.vpos2 not in ("s", "o"):
            raise ValueError("vpos1/vpos2 must be 's' or 'o'")
        for fld in _JOIN_FIELDS[self.category]:
            if getattr(self, fld) is None:
                raise ValueError(
                    f"join category {self.category} requires {fld}="
                )


@dataclasses.dataclass(frozen=True)
class BgpQ:
    """Basic graph pattern: a conjunction of ≥1 triple patterns."""

    patterns: tuple[TriplePatternQ, ...]

    def __post_init__(self):
        pats = tuple(
            p if isinstance(p, TriplePatternQ) else TriplePatternQ(*p)
            for p in self.patterns
        )
        object.__setattr__(self, "patterns", pats)


def _coerce_block(ps):
    return tuple(
        p if isinstance(p, TriplePatternQ) else TriplePatternQ(*p) for p in ps
    )


@dataclasses.dataclass(frozen=True)
class SelectQ:
    """SPARQL-shaped SELECT over one group graph pattern.

    ``where`` is the base conjunction; each entry of ``union`` is an
    alternative branch (the branches' union is joined with ``where``);
    each entry of ``optional`` is an OPTIONAL block left-joined in
    declaration order; ``filter`` holds ``core.algebra`` expressions
    (``Cmp``/``Bound``/``And``/``Or``/``Not``, SPARQL 3-valued logic);
    ``select`` projects (``None`` = every named variable), ``order_by``
    entries are ``"?v"`` ascending / ``"-?v"`` descending, and
    ``limit``/``offset`` slice the ordered result.  Results are DISTINCT
    (set semantics, like ``BgpQ``); the ORDER BY ties break over the
    remaining columns in sorted-name order, so a LIMIT cut is
    deterministic.

    Lowered by ``core.algebra.from_select`` to an operator tree and
    executed by ``core.planner`` — cost-ordered conjunctive blocks with
    sideways information passing over the engine's pooled serve
    programs.
    """

    where: tuple[TriplePatternQ, ...] = ()
    optional: tuple[tuple[TriplePatternQ, ...], ...] = ()
    union: tuple[tuple[TriplePatternQ, ...], ...] = ()
    filter: tuple[Any, ...] = ()
    select: tuple[str, ...] | None = None
    order_by: tuple[str, ...] = ()
    limit: int | None = None
    offset: int = 0

    def __post_init__(self):
        object.__setattr__(self, "where", _coerce_block(self.where))
        object.__setattr__(
            self, "optional", tuple(_coerce_block(b) for b in self.optional)
        )
        object.__setattr__(
            self, "union", tuple(_coerce_block(b) for b in self.union)
        )
        object.__setattr__(self, "filter", tuple(self.filter))
        if self.select is not None:
            object.__setattr__(self, "select", tuple(self.select))
        object.__setattr__(self, "order_by", tuple(self.order_by))
        if not self.where and not self.union:
            raise ValueError("SelectQ needs a WHERE or UNION block")
        for spec in self.order_by:
            v = spec[1:] if spec.startswith("-") else spec
            if not v.startswith("?"):
                raise ValueError(
                    f"order_by entries are '?v' or '-?v', got {spec!r}"
                )
        if self.limit is not None and self.limit < 0:
            raise ValueError("limit must be >= 0")
        if self.offset < 0:
            raise ValueError("offset must be >= 0")


@dataclasses.dataclass(frozen=True)
class ServeQ:
    """Raw serve-IR passthrough: ``Plan(batch)`` takes a ``ServeBatch``.

    ``unbounded=False`` compiles the unbounded-``?P`` lanes out entirely —
    a batch of only CHECK/ROW/COL ops never pays for the ``u_*`` block.
    """

    unbounded: bool = True


Query = Any  # TriplePatternQ | JoinQ | BgpQ | SelectQ | ServeQ


def shape_key(query: Query):
    """The plan-cache key component: everything that selects a compiled
    program, nothing that is a runtime input (the constant ids)."""
    if isinstance(query, TriplePatternQ):
        return ("pattern", query.bound)
    if isinstance(query, JoinQ):
        return ("join", query.category, query.vpos1, query.vpos2)
    if isinstance(query, BgpQ):
        # BGP planning is data-dependent (cardinality estimates), so the
        # host plan re-runs per call; the compiled programs underneath are
        # shared via the engine's serve-lane pool for ANY BgpQ.
        return ("bgp",)
    if isinstance(query, SelectQ):
        # like BgpQ: planning is data-dependent and re-runs per call; the
        # serve-lane pool underneath is shared across ALL select plans
        return ("select",)
    if isinstance(query, ServeQ):
        return ("serve", query.unbounded)
    raise TypeError(f"not a Query: {query!r}")


# ---------------------------------------------------------------------------
# plan handle
# ---------------------------------------------------------------------------


class StaleEpoch(RuntimeError):
    """A compiled plan outlived a compaction swap of its dynamic store.

    Executors pin the store epoch they were compiled against; running one
    after ``DynamicStore.swap`` would silently serve dropped triples from
    the old forest, so the engine raises this instead.  ``Plan.__call__``
    recompiles transparently; ``Plan.submit`` (the raw device path) lets it
    propagate so the broker can refresh its base plan."""


class Plan:
    """Compile-once / run-many handle returned by ``Engine.compile``.

    ``plan()`` executes the query with its own constants; ``plan(batch)``
    re-executes the same compiled shape over a batch of constants (a dict
    of position → id array for ``TriplePatternQ``, a ``ServeBatch`` for
    ``ServeQ``).  Overflow is handled by the config's :class:`CapPolicy`.

    Plans with the same ``(shape_key(query), config)`` share one executor
    — and therefore one set of compiled programs and one effective
    (possibly grown) cap.
    """

    __slots__ = ("query", "config", "_executor")

    def __init__(self, query: Query, config: ExecConfig, executor):
        self.query = query
        self.config = config
        self._executor = executor

    def __call__(self, batch=None):
        try:
            return self._executor.run(self.query, batch)
        except StaleEpoch:
            # the store was compacted under us — recompile against the new
            # epoch (ids are stable across swaps, so the query still means
            # the same thing) and retry once
            eng = self._executor.engine
            self._executor = eng.compile(self.query, self.config)._executor
            return self._executor.run(self.query, batch)

    def submit(self, batch=None):
        """Asynchronous dispatch: launch the compiled program and return its
        DEVICE results immediately — no host sync, no overflow guard, no
        CapPolicy growth.  The streamed-serving hook: a caller (the
        ``launch.broker`` front-end) can overlap host-side decode of batch N
        with device execution of batch N+1, inspecting ``overflow`` itself.
        Only executors with a raw device surface support it (``ServeQ``)."""
        return self._executor.submit(self.query, batch)

    @property
    def effective_cap(self) -> int:
        """Current cap — ``config.cap`` until growth doubled it."""
        return self._executor.cap

    def compiled_text(self, batch=None) -> str:
        """Compiled-module text of the underlying program (where the
        executor exposes one, e.g. ``ServeQ``) — for asserting
        communication properties like 'no all-gather on the wire'."""
        return self._executor.compiled_text(self.query, batch)

    def cost_profile(self, batch=None) -> dict:
        """Static compile-time cost profile of the underlying program
        (where the executor exposes one, e.g. ``ServeQ``): XLA
        ``cost_analysis`` FLOPs/bytes, memory stats, and the lanes × cap
        geometry — see ``repro.obs.cost``.  Cached per program geometry."""
        return self._executor.cost_profile(self.query, batch)

    def __repr__(self):
        return (
            f"Plan({self.query!r}, backend={self.config.backend!r}, "
            f"cap={self.effective_cap})"
        )
