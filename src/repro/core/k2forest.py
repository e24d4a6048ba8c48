"""K2Forest — the vertical-partitioning arena: one k²-tree per predicate.

The paper keeps |P| independent k²-trees.  For a device-resident engine we
pack them into padded 2-D word arenas ``(P, W)`` so that

  * the predicate axis is shardable (``model`` axis of the production mesh —
    vertical partitioning *is* the sharding scheme, lifted to the pod level);
  * a batch of queries with per-query predicate ids lowers to gathers
    ``words[pred, pos >> 5]`` — no per-query row materialization.

All trees share one ``K2Meta`` (same matrix side = dictionary extent, padded
to the hybrid-k power — exactly the paper's square-matrix construction).

Every 2-D arena is built in whole (8, 128) tiles (``bitvec.TILE_ROWS`` /
``TILE_COLS``): the predicate rows pad to a multiple of 8 with empty trees,
the word columns with zero words (``t_rank`` rank-extended).  ``nnz`` keeps
the logical predicate count.
"""

from __future__ import annotations

import functools
import types
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitvec, k2tree
from repro.core.k2tree import K2Meta, PairResult, QueryResult, _compact


class K2Forest(NamedTuple):
    t_words: jax.Array  # uint32[R, Wt]   R = P rounded up to 8 rows,
    t_rank: jax.Array  # int32[R, Wt]     Wt, Wl and the table widths
    l_words: jax.Array  # uint32[R, Wl]   rounded up to 128 columns
    ones_before: jax.Array  # int32[R, >= H-1]
    level_start: jax.Array  # int32[R, >= H]
    nnz: jax.Array  # int32[P]

    @property
    def n_preds(self) -> int:
        return self.nnz.shape[0]


class ForestStats(NamedTuple):
    """Honest compression accounting (padding is a layout, not a size)."""

    total_bits: int  # sum over predicates of (|T| + |L|)
    padded_bits: int  # device-arena footprint
    per_pred_bits: np.ndarray
    per_pred_nnz: np.ndarray


def build_forest(
    coords: Sequence[tuple[np.ndarray, np.ndarray]], meta: K2Meta
) -> tuple[K2Forest, ForestStats]:
    """Build one tree per predicate from (rows, cols) coordinate lists."""
    hosts = [k2tree.build_host(r, c, meta) for (r, c) in coords]
    P = len(hosts)
    H = meta.n_levels
    tile = functools.partial(bitvec.round_up, m=bitvec.TILE_COLS)
    R = bitvec.round_up(max(P, 1), bitvec.TILE_ROWS)
    wt = tile(max([1] + [(h.t_bits.shape[0] + 31) // 32 for h in hosts]))
    wl = tile(max([1] + [(h.l_bits.shape[0] + 31) // 32 for h in hosts]))

    t_words = np.zeros((R, wt), np.uint32)
    t_rank = np.zeros((R, wt), np.int32)
    l_words = np.zeros((R, wl), np.uint32)
    ones_before = np.zeros((R, tile(max(H - 1, 1))), np.int32)
    level_start = np.zeros((R, tile(H)), np.int32)
    nnz = np.zeros((P,), np.int32)
    bits = np.zeros((P,), np.int64)
    for i, h in enumerate(hosts):
        tw = bitvec.pack_bits_np(h.t_bits)
        t_words[i, : tw.shape[0]] = tw
        t_rank[i, : tw.shape[0]] = bitvec.rank_blocks_np(tw)
        # padding words rank-extend so rank1 beyond the tree stays monotone
        if tw.shape[0] < wt:
            total = int(h.t_bits.sum())
            t_rank[i, tw.shape[0]:] = total
        lw = bitvec.pack_bits_np(h.l_bits)
        l_words[i, : lw.shape[0]] = lw
        ones_before[i, : h.ones_before.shape[0]] = h.ones_before
        level_start[i, :H] = h.level_start
        nnz[i] = h.nnz
        bits[i] = h.t_bits.shape[0] + h.l_bits.shape[0]

    forest = K2Forest(
        t_words=jnp.asarray(t_words),
        t_rank=jnp.asarray(t_rank),
        l_words=jnp.asarray(l_words),
        ones_before=jnp.asarray(ones_before),
        level_start=jnp.asarray(level_start),
        nnz=jnp.asarray(nnz),
    )
    stats = ForestStats(
        total_bits=int(bits.sum()),
        padded_bits=int((t_words.size + t_rank.size + l_words.size) * 32),
        per_pred_bits=bits,
        per_pred_nnz=nnz.copy(),
    )
    return forest, stats


# ---------------------------------------------------------------------------
# batched queries — 2-D indexed (pred travels with every lane)
# ---------------------------------------------------------------------------


def live_lanes(meta: K2Meta, f: K2Forest, preds, *keys) -> jax.Array:
    """Predicate ids -> -1, the dead-lane id, where the predicate is outside
    ``[0, n_preds)`` or any of ``keys`` is outside the matrix side.

    Client ids reach the traversals unchecked; a dead lane answers empty
    and the kernels read nothing for it, so no id can steer a read outside
    the arena or answer from another predicate's tree or another entity.
    """
    preds = jnp.asarray(preds, jnp.int32)
    live = (preds >= 0) & (preds < f.n_preds)
    for k in keys:
        k = jnp.asarray(k, jnp.int32)
        live = live & (k >= 0) & (k < meta.side)
    return jnp.where(live, preds, -1)


def check(
    meta: K2Meta, f: K2Forest, pred: jax.Array, rows: jax.Array, cols: jax.Array
) -> jax.Array:
    """Batched (S, P, O) over per-lane predicates -> bool[Q]; an id outside
    the store is never a hit."""
    H = meta.n_levels
    pred = live_lanes(meta, f, pred, rows, cols)
    rd = k2tree._row_digits(meta, rows.astype(jnp.int32))
    cd = k2tree._row_digits(meta, cols.astype(jnp.int32))
    alive = jnp.broadcast_to(pred >= 0, rows.shape)
    pos = (rd[0] * meta.ks[0] + cd[0]).astype(jnp.int32)
    for lvl in range(H):
        last = lvl == H - 1
        words = f.l_words if last else f.t_words
        bit = bitvec.get_bit_2d(words, pred, pos)
        alive = alive & (bit == 1)
        if not last:
            j = bitvec.rank1_2d(f.t_words, f.t_rank, pred, pos) - f.ones_before[pred, lvl]
            nxt = rd[lvl + 1] * meta.ks[lvl + 1] + cd[lvl + 1]
            pos = f.level_start[pred, lvl + 1] + j * meta.radices[lvl + 1] + nxt
            pos = jnp.where(alive, pos, 0).astype(jnp.int32)
    return alive


def check_all_preds(meta: K2Meta, f: K2Forest, row: jax.Array, col: jax.Array) -> jax.Array:
    """(S, ?P, O): bool[P] — the paper's 'check the cell in every tree'."""
    P = f.n_preds
    preds = jnp.arange(P, dtype=jnp.int32)
    return check(meta, f, preds, jnp.broadcast_to(row, (P,)), jnp.broadcast_to(col, (P,)))


def row_scan(meta: K2Meta, f: K2Forest, pred, row, cap: int,
             backend: str | None = None) -> QueryResult:
    """(S, P, ?O) — direct neighbors, ascending object id."""
    r = scan_batch_mixed(
        meta, f, jnp.reshape(jnp.asarray(pred, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(row, jnp.int32), (1,)),
        jnp.zeros((1,), jnp.int32), cap, backend,
    )
    return jax.tree.map(lambda x: x[0], r)


def col_scan(meta: K2Meta, f: K2Forest, pred, col, cap: int,
             backend: str | None = None) -> QueryResult:
    """(?S, P, O) — reverse neighbors, ascending subject id."""
    r = scan_batch_mixed(
        meta, f, jnp.reshape(jnp.asarray(pred, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(col, jnp.int32), (1,)),
        jnp.ones((1,), jnp.int32), cap, backend,
    )
    return jax.tree.map(lambda x: x[0], r)


def row_scan_batch(meta: K2Meta, f: K2Forest, preds, rows, cap: int,
                   backend: str | None = None) -> QueryResult:
    preds = jnp.asarray(preds, jnp.int32)
    return scan_batch_mixed(
        meta, f, preds, jnp.asarray(rows, jnp.int32),
        jnp.zeros(preds.shape, jnp.int32), cap, backend,
    )


def col_scan_batch(meta: K2Meta, f: K2Forest, preds, cols, cap: int,
                   backend: str | None = None) -> QueryResult:
    preds = jnp.asarray(preds, jnp.int32)
    return scan_batch_mixed(
        meta, f, preds, jnp.asarray(cols, jnp.int32),
        jnp.ones(preds.shape, jnp.int32), cap, backend,
    )


def row_scan_all_preds(meta: K2Meta, f: K2Forest, row, cap: int,
                       backend: str | None = None) -> QueryResult:
    """(S, ?P, ?O): per-predicate object lists, result axis 0 = predicate.

    The all-preds sweep is the batched mixed scan with a broadcast key —
    one kernel launch covers every predicate's tree.
    """
    preds = jnp.arange(f.n_preds, dtype=jnp.int32)
    rows = jnp.broadcast_to(jnp.asarray(row, jnp.int32), (f.n_preds,))
    return row_scan_batch(meta, f, preds, rows, cap, backend)


def col_scan_all_preds(meta: K2Meta, f: K2Forest, col, cap: int,
                       backend: str | None = None) -> QueryResult:
    """(?S, ?P, O): per-predicate subject lists."""
    preds = jnp.arange(f.n_preds, dtype=jnp.int32)
    cols = jnp.broadcast_to(jnp.asarray(col, jnp.int32), (f.n_preds,))
    return col_scan_batch(meta, f, preds, cols, cap, backend)


def _axis_scan_traced(
    meta: K2Meta, f: K2Forest, pred: jax.Array, fixed: jax.Array, axis: jax.Array, cap: int
) -> QueryResult:
    """Like ``_axis_scan`` but the row/col axis is a *traced* per-query flag.

    This lets one compiled program serve a mixed batch of direct-neighbor
    (S,P,?O) and reverse-neighbor (?S,P,O) scans — the serving hot path.
    """
    H = meta.n_levels
    pred = pred.astype(jnp.int32)
    is_row = (jnp.asarray(axis, jnp.int32) == 0)
    fdig = k2tree._row_digits(meta, fixed.astype(jnp.int32))

    k0 = meta.ks[0]
    sub0 = meta.subsides[0]
    init_n = min(k0, cap)
    j0 = jnp.arange(init_n, dtype=jnp.int32)
    p0 = jnp.where(is_row, fdig[0] * k0 + j0, j0 * k0 + fdig[0])
    pos = jnp.zeros((cap,), jnp.int32).at[:init_n].set(p0)
    base = jnp.zeros((cap,), jnp.int32).at[:init_n].set(j0 * sub0)
    live = pred >= 0  # a dead lane (pred < 0) answers empty
    valid = jnp.zeros((cap,), jnp.bool_).at[:init_n].set(live)
    overflow = jnp.asarray(k0 > cap) & live

    words0 = f.l_words if H == 1 else f.t_words
    valid = valid & (bitvec.get_bit_2d(words0, pred, pos) == 1)

    for lvl in range(H - 1):
        last_child = lvl + 1 == H - 1
        k = meta.ks[lvl + 1]
        r = meta.radices[lvl + 1]
        sub = meta.subsides[lvl + 1]
        j = bitvec.rank1_2d(f.t_words, f.t_rank, pred, pos) - f.ones_before[pred, lvl]
        child_base0 = f.level_start[pred, lvl + 1] + j * r
        ch = jnp.arange(k, dtype=jnp.int32)
        cpos = child_base0[:, None] + jnp.where(
            is_row, fdig[lvl + 1] * k + ch[None, :], ch[None, :] * k + fdig[lvl + 1]
        )
        cbase = base[:, None] + ch[None, :] * sub
        wordsc = f.l_words if last_child else f.t_words
        cbit = bitvec.get_bit_2d(wordsc, pred, jnp.where(valid[:, None], cpos, 0))
        cvalid = valid[:, None] & (cbit == 1)
        valid, _, ovf, (pos, base) = _compact(
            cvalid.reshape(-1), cap, cpos.reshape(-1), cbase.reshape(-1)
        )
        overflow = overflow | ovf
        pos = jnp.where(valid, pos, 0)

    valid, count, ovf, (ids,) = _compact(valid, cap, base)
    return QueryResult(ids=ids, valid=valid, count=count, overflow=overflow | ovf)


def scan_batch_mixed(
    meta: K2Meta, f: K2Forest, preds, keys, axes, cap: int,
    backend: str | None = None, *, name: str = "k2_scan",
) -> QueryResult:
    """Batched mixed row/col scans: axes[i]==0 -> row (S,P,?O), 1 -> col.

    A lane whose predicate is outside ``[0, n_preds)`` or whose key is
    outside the matrix is dead (:func:`live_lanes`): empty, no overflow,
    and the kernel reads nothing for it (the serve step parks unused lanes
    at -1).

    ``backend`` selects the compute substrate: an ``ExecConfig``
    (``core.query``) carries explicit backend + interpret values (the
    compiled-plan path — zero env reads); a bare "pallas"/"jnp" string or
    ``None`` falls back to the legacy ``REPRO_SCAN_BACKEND`` env
    resolution.  "pallas" routes to the batched ``kernels.k2_scan`` TPU
    kernel, "jnp" to the vmapped level-synchronous traversal below.  Both
    produce bit-identical QueryResults (tests/test_k2_scan.py).  ``name``
    names the kernel launch in the compiled program and its profiles (the
    serve step's ``k2_scan_bound`` and ``k2_scan_u``).
    """
    from repro.kernels import ops  # deferred: core must import without pallas

    preds = live_lanes(meta, f, preds, keys)
    be, interp = ops.resolve_exec(backend)
    if be == "pallas":
        ids, valid, count, overflow = ops.k2_scan_forest(
            meta, f, preds, keys, axes, cap=cap, interpret=interp, name=name
        )
        return QueryResult(ids=ids, valid=valid, count=count, overflow=overflow)
    return jax.vmap(lambda p, x, a: _axis_scan_traced(meta, f, p, x, a, cap))(
        preds, jnp.asarray(keys), jnp.asarray(axes)
    )


def _range_scan_traced(meta: K2Meta, f: K2Forest, pred: jax.Array, cap: int) -> PairResult:
    """Single-predicate (?S, P, ?O) traversal (vmap for batches) — the jnp
    reference behind ``range_scan_batch``.

    Level 0 bit-tests every root child and only then compacts the frontier:
    overflow latches only when more than ``cap`` root children are actually
    occupied.  (The previous code truncated the ``r0`` root radix to ``cap``
    *before* the bit test, falsely reporting overflow — and silently
    dropping candidates — for any sparse matrix under a large root radix.)
    """
    H = meta.n_levels
    pred = jnp.asarray(pred, dtype=jnp.int32)
    k0, r0, sub0 = meta.ks[0], meta.radices[0], meta.subsides[0]

    d0 = jnp.arange(r0, dtype=jnp.int32)
    words0 = f.l_words if H == 1 else f.t_words
    bit0 = bitvec.get_bit_2d(words0, pred, d0)
    valid, _, ovf, (pos, rbase, cbase) = _compact(
        (bit0 == 1) & (pred >= 0), cap, d0, (d0 // k0) * sub0, (d0 % k0) * sub0
    )
    overflow = ovf
    pos = jnp.where(valid, pos, 0)

    for lvl in range(H - 1):
        last_child = lvl + 1 == H - 1
        k = meta.ks[lvl + 1]
        r = meta.radices[lvl + 1]
        sub = meta.subsides[lvl + 1]
        j = bitvec.rank1_2d(f.t_words, f.t_rank, pred, pos) - f.ones_before[pred, lvl]
        child_base0 = f.level_start[pred, lvl + 1] + j * r
        d = jnp.arange(r, dtype=jnp.int32)
        cpos = child_base0[:, None] + d[None, :]
        crb = rbase[:, None] + (d[None, :] // k) * sub
        ccb = cbase[:, None] + (d[None, :] % k) * sub
        wordsc = f.l_words if last_child else f.t_words
        cbit = bitvec.get_bit_2d(wordsc, pred, jnp.where(valid[:, None], cpos, 0))
        cvalid = valid[:, None] & (cbit == 1)
        valid, _, ovf, (pos, rbase, cbase) = _compact(
            cvalid.reshape(-1), cap, cpos.reshape(-1), crb.reshape(-1), ccb.reshape(-1)
        )
        overflow = overflow | ovf
        pos = jnp.where(valid, pos, 0)

    valid, count, ovf, (rows, cols) = _compact(valid, cap, rbase, cbase)
    return PairResult(rows, cols, valid, count, overflow | ovf)


def range_scan_batch(
    meta: K2Meta, f: K2Forest, preds, cap: int, backend: str | None = None
) -> PairResult:
    """Batched (?S, P, ?O) pair enumeration, one lane per predicate; a
    predicate outside ``[0, n_preds)`` is a dead lane (no pairs).

    ``backend`` resolves exactly like ``scan_batch_mixed`` (ExecConfig /
    string / None): "pallas" routes to the batched ``kernels.k2_range``
    TPU kernel, "jnp" to the vmapped traversal above.  Bit-identical
    outputs (tests/test_k2_range.py).
    """
    from repro.kernels import ops  # deferred: core must import without pallas

    preds = live_lanes(meta, f, preds)
    be, interp = ops.resolve_exec(backend)
    if be == "pallas":
        rows, cols, valid, count, overflow = ops.k2_range_forest(
            meta, f, preds, cap=cap, interpret=interp
        )
        return PairResult(rows, cols, valid, count, overflow)
    return jax.vmap(lambda p: _range_scan_traced(meta, f, p, cap))(preds)


def range_scan(meta: K2Meta, f: K2Forest, pred, cap: int,
               backend: str | None = None) -> PairResult:
    """(?S, P, ?O): all pairs of one predicate's matrix (Morton order)."""
    r = range_scan_batch(
        meta, f, jnp.reshape(jnp.asarray(pred, jnp.int32), (1,)), cap, backend
    )
    return jax.tree.map(lambda x: x[0], r)


def range_scan_all_preds(meta: K2Meta, f: K2Forest, cap: int,
                         backend: str | None = None) -> PairResult:
    """(?S, ?P, ?O): dataset dump, axis 0 = predicate."""
    preds = jnp.arange(f.n_preds, dtype=jnp.int32)
    return range_scan_batch(meta, f, preds, cap, backend)


def scan_rebind_batch(
    meta: K2Meta, f: K2Forest, preds1, keys1, axes1, preds2, axes2,
    cap_x: int, cap_y: int, backend: str | None = None,
):
    """Fused X-resolution + re-bind (join categories D–F).

    Per query lane: scan (preds1, keys1, axes1) into a ``cap_x`` side-list
    of ?X ids, then re-bind each X into pattern 2 as (preds2, X, axes2)
    scans of ``cap_y``.  Invalid X lanes scan key 0; callers mask their
    ``y_valid`` rows with ``x_valid``.

    Returns ``(x_ids, x_valid, x_count, x_overflow, y_ids, y_valid,
    y_count, y_overflow)`` shaped ``(Q,cap_x) ×2, (Q,) ×2,
    (Q,cap_x,cap_y) ×2, (Q,cap_x) ×2`` — 0-based coordinates throughout.
    "pallas" runs the fused ``kernels.k2_scan.k2_scan_rebind`` kernel (no
    host round-trip between the two traversals); "jnp" composes the two
    vmapped traversals.  Bit-identical outputs (tests/test_joins_kernel.py).
    """
    from repro.kernels import ops  # deferred: core must import without pallas

    preds1 = live_lanes(meta, f, preds1, keys1)
    keys1 = jnp.asarray(keys1, jnp.int32)
    axes1 = jnp.asarray(axes1, jnp.int32)
    preds2 = live_lanes(meta, f, preds2)
    axes2 = jnp.asarray(axes2, jnp.int32)
    be, interp = ops.resolve_exec(backend)
    if be == "pallas":
        return ops.k2_scan_rebind_forest(
            meta, f, preds1, keys1, axes1, preds2, axes2,
            cap_x=cap_x, cap_y=cap_y, interpret=interp,
        )
    (q,) = preds1.shape
    # pin the resolved pair for the two sub-scans: re-passing a bare "jnp"
    # string would re-resolve interpret from the environment — an env read
    # inside compiled plan paths (tests/test_backend_flag.py)
    pinned = types.SimpleNamespace(backend="jnp", interpret=interp)
    rx = scan_batch_mixed(meta, f, preds1, keys1, axes1, cap_x, pinned)
    keys2 = jnp.where(rx.valid, rx.ids, 0).reshape(q * cap_x)
    p2 = jnp.broadcast_to(preds2[:, None], (q, cap_x)).reshape(q * cap_x)
    a2 = jnp.broadcast_to(axes2[:, None], (q, cap_x)).reshape(q * cap_x)
    ry = scan_batch_mixed(meta, f, p2, keys2, a2, cap_y, pinned)
    return (
        rx.ids, rx.valid, rx.count, rx.overflow,
        ry.ids.reshape(q, cap_x, cap_y), ry.valid.reshape(q, cap_x, cap_y),
        ry.count.reshape(q, cap_x), ry.overflow.reshape(q, cap_x),
    )
