"""Public jit'd entry points for the kernel layer.

Execution knobs reach this layer one of two ways:

  * **the compiled-plan path** (``core.query.ExecConfig`` threaded through
    ``Engine.compile`` → patterns/joins/optimizer → ``core.k2forest``):
    the config object carries explicit ``backend`` + ``interpret`` values
    and ``resolve_exec`` honors them with ZERO environment reads — nothing
    inside a compiled ``Plan.__call__`` consults ``os.environ``
    (tests/test_backend_flag.py);
  * **the legacy path** (``backend=None`` or a bare string from the
    deprecation shims / ad-hoc calls): ``scan_backend()`` /
    ``pallas_interpret()`` resolve the environment flags below per call.

Environment flags (legacy defaults — fold them into an explicit config
once via ``ExecConfig.from_env()``):

``REPRO_PALLAS_INTERPRET``
    (re-read on every entry-point call — same semantics as the scan-backend
    flag below; a function already jit-compiled keeps the mode baked in at
    trace time) Unset: the backend's default — compiled kernels on a TPU,
    the interpreter on the CPU (the correctness path of CPU tests).  "0"
    forces compiled kernels; anything else forces the interpreter, which
    is an error on a TPU backend.

``REPRO_SCAN_BACKEND``
    (re-read on every resolve — flipping the var mid-session takes effect
    on the next *trace*: eager calls and fresh jit traces see the new
    value, but a function already jit-compiled keeps the backend baked in
    at trace time) Selects the traversal substrate behind
    ``core.k2forest`` batch scans — ``scan_batch_mixed`` (the
    (S,P,?O)/(?S,P,O) serve hot path + all-preds sweeps),
    ``range_scan_batch`` ((?S,P,?O) pair enumeration),
    ``scan_rebind_batch`` (join categories D–F), and
    ``core.predindex.gather_batch`` (the SP/OP candidate gather feeding
    the index-pruned unbounded-?P lanes):

      * ``"pallas"`` (default) — the batched kernels (``kernels/k2_scan.py``
        / ``kernels/k2_range.py``): scalar-core traversals over the
        HBM-resident arena (``kernels/tiles.py``).
      * ``"jnp"`` — the vmapped pure-jnp level-synchronous traversal
        (the pre-kernel path; also the differential reference).

    Callers can override per-call via the ``backend=`` keyword.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.k2tree import K2Meta, K2Tree
from repro.kernels import block_spmm as _bs
from repro.kernels import k2_check as _kc
from repro.kernels import k2_range as _kr
from repro.kernels import k2_scan as _ks
from repro.kernels import popcount as _pc
from repro.kernels import pred_gather as _pg
from repro.kernels import sorted_intersect as _si

DEFAULT_SCAN_BACKEND = "pallas"


def pallas_interpret(override: bool | None = None) -> bool:
    """Resolve interpret mode for every Pallas launch.

    Re-reads ``REPRO_PALLAS_INTERPRET`` from the environment on every call —
    the same no-latching contract as ``scan_backend()``.  Unset means the
    backend's default (compiled on a TPU, interpreted on the CPU);
    interpret mode on a TPU backend is an error, never a quiet fallback.
    """
    from repro.core.query import check_interpret, default_interpret

    if override is None:
        raw = os.environ.get("REPRO_PALLAS_INTERPRET")
        override = default_interpret() if raw is None else raw != "0"
    return check_interpret(override, "Pallas kernel launch")


def scan_backend(override: str | None = None) -> str:
    """Resolve the scan backend ("pallas" | "jnp").

    Re-reads ``REPRO_SCAN_BACKEND`` from the environment on every call, so
    flipping the flag after import (as a test or notebook naturally does)
    is honored — the value is NOT latched at import time.
    """
    b = override or os.environ.get("REPRO_SCAN_BACKEND", DEFAULT_SCAN_BACKEND)
    if b not in ("pallas", "jnp"):
        raise ValueError(f"unknown scan backend {b!r} (want 'pallas' or 'jnp')")
    return b


def resolve_exec(backend=None) -> tuple[str, bool]:
    """Resolve ``(backend, interpret)`` for one traversal dispatch.

    ``backend`` may be an ``ExecConfig``-shaped object (anything with
    ``.backend`` / ``.interpret`` attributes — duck-typed so core modules
    need no import of ``core.query``), a bare backend string, or ``None``.
    A config resolves WITHOUT touching the environment: its values are
    explicit (``interpret=None`` means the deterministic off-TPU default).
    A string or ``None`` falls back to the legacy per-call env resolution.
    """
    cfg_backend = getattr(backend, "backend", None)
    if cfg_backend is not None:
        if cfg_backend not in ("pallas", "jnp"):
            raise ValueError(
                f"unknown scan backend {cfg_backend!r} (want 'pallas' or 'jnp')"
            )
        from repro.core.query import check_interpret, default_interpret

        interp = backend.interpret
        if interp is None:
            interp = default_interpret()
        return cfg_backend, check_interpret(bool(interp), "resolve_exec")
    return scan_backend(backend), pallas_interpret()


def popcount(words: jax.Array, *, block_m: int = 8) -> jax.Array:
    return _pc.popcount_2d(words, block_m=block_m, interpret=pallas_interpret())


def k2_check_tree(
    meta: K2Meta, tree: K2Tree, rows: jax.Array, cols: jax.Array, *, block_q: int = 1024
) -> jax.Array:
    """Kernel-backed version of core.k2tree.check (single tree)."""
    q = rows.shape[0]
    pad = (-q) % block_q
    if pad:
        rows = jnp.pad(rows, (0, pad))
        cols = jnp.pad(cols, (0, pad))
    out = _kc.k2_check(
        meta, rows, cols, tree.t.words, tree.t.rank_blocks, tree.l.words,
        tree.ones_before, tree.level_start, block_q=block_q, interpret=pallas_interpret(),
    )
    return out[:q]


def k2_scan_forest(
    meta: K2Meta,
    forest,
    preds: jax.Array,
    keys: jax.Array,
    axes: jax.Array,
    *,
    cap: int,
    interpret: bool | None = None,
    name: str = "k2_scan",
):
    """Kernel-backed batched mixed row/col scan over a K2Forest.

    Drop-in compute for ``core.k2forest.scan_batch_mixed`` (which routes
    here when the scan backend is "pallas").  Lanes whose pred is not a
    row of the arena are dead and answer empty.  Returns (ids, valid, count, overflow).
    ``interpret=None`` defers to the legacy env flag; the compiled-plan
    path always passes an explicit bool.  ``name`` names the launch.
    """
    return _ks.k2_scan(
        meta, jnp.asarray(preds, jnp.int32), jnp.asarray(keys, jnp.int32),
        jnp.asarray(axes, jnp.int32),
        forest.t_words, forest.t_rank, forest.l_words,
        forest.ones_before, forest.level_start,
        cap=cap, interpret=pallas_interpret(interpret), name=name,
    )


def k2_range_forest(
    meta: K2Meta,
    forest,
    preds: jax.Array,
    *,
    cap: int,
    interpret: bool | None = None,
):
    """Kernel-backed batched (?S,P,?O) pair enumeration over a K2Forest.

    Drop-in compute for ``core.k2forest.range_scan_batch`` (which routes
    here when the scan backend is "pallas").  Returns (rows, cols, valid,
    count, overflow).
    """
    return _kr.k2_range(
        meta, jnp.asarray(preds, jnp.int32),
        forest.t_words, forest.t_rank, forest.l_words,
        forest.ones_before, forest.level_start,
        cap=cap, interpret=pallas_interpret(interpret),
    )


def k2_scan_rebind_forest(
    meta: K2Meta,
    forest,
    preds1: jax.Array,
    keys1: jax.Array,
    axes1: jax.Array,
    preds2: jax.Array,
    axes2: jax.Array,
    *,
    cap_x: int,
    cap_y: int,
    interpret: bool | None = None,
):
    """Kernel-backed fused X-scan + re-bind (join categories D–F).

    Drop-in compute for ``core.k2forest.scan_rebind_batch`` (which routes
    here when the scan backend is "pallas").  Returns the kernel's 8-tuple
    (x_ids, x_valid, x_count, x_overflow, y_ids, y_valid, y_count,
    y_overflow).
    """
    arrs = [jnp.asarray(a, jnp.int32) for a in (preds1, keys1, axes1, preds2, axes2)]
    return _ks.k2_scan_rebind(
        meta, *arrs,
        forest.t_words, forest.t_rank, forest.l_words,
        forest.ones_before, forest.level_start,
        cap_x=cap_x, cap_y=cap_y, interpret=pallas_interpret(interpret),
    )


def pred_gather_index(
    pmeta,
    index,
    rows: jax.Array,
    *,
    cap: int,
    interpret: bool | None = None,
):
    """Kernel-backed candidate-predicate gather over a PredIndex.

    Drop-in compute for ``core.predindex.gather_batch`` (which routes here
    when the scan backend is "pallas").  The decode layout follows
    ``pmeta.layout``: "dac" launches the on-device DAC(b=8) decode kernel,
    "fixed" the byte-packed direct-access kernel.  Rows are clipped to the
    index range.  Returns (ids, valid, count, overflow).
    """
    rows = jnp.clip(
        jnp.asarray(rows, jnp.int32), 0,
        max(pmeta.n_subjects + pmeta.n_objects - 1, 0),
    )
    interp = pallas_interpret(interpret)
    if getattr(pmeta, "layout", "fixed") == "dac":
        return _pg.pred_gather_dac(
            rows, index.offsets, index.words, index.degs, index.flags,
            index.frank, levels=pmeta.levels,
            level_byte_start=pmeta.level_byte_start,
            flag_word_start=pmeta.flag_word_start,
            deg_width=pmeta.deg_width, rows_per_block=pmeta.rows_per_block,
            cap=cap, interpret=interp,
        )
    return _pg.pred_gather(
        rows, index.offsets, index.words,
        bytes_per_pred=pmeta.bytes_per_pred, cap=cap, interpret=interp,
    )


def sorted_intersect_mask(a_ids: jax.Array, b_ids: jax.Array) -> jax.Array:
    return _si.sorted_intersect_mask(a_ids, b_ids, interpret=pallas_interpret())


def block_spmm(mask: jax.Array, a: jax.Array, x: jax.Array, **kw) -> jax.Array:
    return _bs.block_spmm(mask, a, x, interpret=pallas_interpret(), **kw)
