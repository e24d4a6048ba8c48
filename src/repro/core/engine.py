"""Query engine: ``Engine.compile(query, config) -> Plan`` over one jit'd
batched ``serve_step`` (single & sharded).

Two layers:

  * ``Engine`` — the ONE host-side entry point: lowers any ``core.query``
    description (``TriplePatternQ`` / ``JoinQ`` / ``BgpQ`` / ``ServeQ``)
    under a frozen ``ExecConfig`` into a cached compiled ``Plan``.  Every
    keyed pattern, join side-list, and BGP step rides the pooled serve-IR
    programs below; cap overflow recovers by CapPolicy doubling.  The
    pre-redesign ``Engine.pattern`` / ``Engine.join`` survive as
    deprecation shims over ``compile``.

  * ``make_serve_step`` / ``make_sharded_serve_step`` — the compiled
    substrate: one program serving a BATCH of queries spanning all keyed
    patterns — checks, mixed row/col scans, AND the unbounded-predicate
    lanes (the serve IR ops below).  ``backend`` accepts an ``ExecConfig``
    (explicit backend + interpret, zero env reads at trace time) or the
    legacy string/None forms.

Serve IR: a ``ServeBatch`` lane is ``(op, s, p, o)`` with

    OP_CHECK      (S, P, O)     -> hit flag
    OP_ROW        (S, P, ?O)    -> object list            (ids/valid/count)
    OP_COL        (?S, P, O)    -> subject list           (ids/valid/count)
    OP_S_ANY_O    (S, ?P, O)    -> matching predicates    (ids/valid/count)
    OP_S_ANY_ANY  (S, ?P, ?O)   -> per-pred object lists  (u_* block)
    OP_ANY_ANY_O  (?S, ?P, O)   -> per-pred subject lists (u_* block)

The two full-enumeration patterns ((?S,P,?O) pairs and the (?S,?P,?O) dump)
return pair sets and stay on ``k2forest.range_scan[_all_preds]``.

Unbounded-``?P`` lanes are the paper's conceded worst case.  With a
``predindex.PredIndex`` (k²-triples+, arXiv:1310.4954) they gather their
candidate predicate list from the SP/OP index and launch a PRUNED
``scan_batch_mixed`` of ``u_width`` lanes per query; without one
(``index=None``) they fall back to the all-preds broadcast sweep
(``u_width`` must then cover ``n_preds``) — the differential reference.

Distribution (the paper's vertical partitioning lifted to the mesh):
the forest arena is sharded by predicate over the ``model`` axis; the query
batch is sharded over ``data`` (× ``pod``); the (tiny) predicate index is
replicated.  Inside ``shard_map`` each model shard resolves the queries —
and the candidate predicates — it owns (others masked out) and a ``psum``
over the model axis combines.  The pruned unbounded path reduces
``[B, u_width, cap]`` instead of all-gathering ``[B, P, cap]``: predicate
pruning shrinks the wire bytes by the same factor as the compute.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import delta as dyn
from repro.core import bitvec, joins, k2forest, patterns, predindex, query as qapi
from repro.obs import cost as obs_cost
from repro.core.k2forest import K2Forest
from repro.core.k2tree import _compact
from repro.core.k2triples import K2TriplesStore
from repro.core.k2tree import K2Meta
from repro.core.predindex import PredIndex, PredIndexMeta
from repro.core import algebra
from repro.core.query import (
    BgpQ, CapOverflow, ExecConfig, JoinQ, Plan, SelectQ, ServeQ,
    TriplePatternQ,
)
from repro.core.sortedset import SENTINEL, IdSet
from repro.core import sortedset

# serve IR ops
OP_CHECK = 0  # (S, P, O)    -> hit flag
OP_ROW = 1  # (S, P, ?O)   -> object list
OP_COL = 2  # (?S, P, O)   -> subject list
OP_S_ANY_ANY = 3  # (S, ?P, ?O)  -> per-candidate-predicate object lists
OP_ANY_ANY_O = 4  # (?S, ?P, O)  -> per-candidate-predicate subject lists
OP_S_ANY_O = 5  # (S, ?P, O)   -> matching predicate list


class ServeBatch(NamedTuple):
    """Encoded queries (1-based ids; 0 for positions an op leaves free)."""

    op: jax.Array  # int32[B] in the serve IR ops above
    s: jax.Array  # int32[B] subject id (or 0)
    p: jax.Array  # int32[B] predicate id (0 for unbounded-?P ops)
    o: jax.Array  # int32[B] object id (or 0)


class ServeResult(NamedTuple):
    hit: jax.Array  # bool[B]      — checks
    ids: jax.Array  # int32[B,cap] — scans + S?PO predicate lists (1-based)
    valid: jax.Array  # bool[B,cap]
    count: jax.Array  # int32[B]
    overflow: jax.Array  # bool[B]
    # unbounded-?P pair ops (OP_S_ANY_ANY / OP_ANY_ANY_O); width-0 when the
    # serve step was built without unbounded support
    u_preds: jax.Array  # int32[B,L] candidate predicate ids (1-based; 0 dead)
    u_ids: jax.Array  # int32[B,L,cap] per-candidate results (1-based)
    u_valid: jax.Array  # bool[B,L,cap]
    u_count: jax.Array  # int32[B,L]


def take_lanes(q: ServeBatch, idx) -> ServeBatch:
    """Split a batch: the sub-``ServeBatch`` of lanes ``idx`` (any numpy
    fancy index).  The streamed-serving splitting hook — brokers carve
    per-tenant retry batches out of a coalesced one without re-encoding."""
    idx = np.asarray(idx)
    return ServeBatch(*(np.asarray(a)[idx] for a in q))


def host_result(r: ServeResult, *, unbounded: bool = True) -> ServeResult:
    """ONE blocking device->host fetch of a ``ServeResult`` (numpy fields):
    :func:`wait_result`, then the copy of each field, one array at a time.

    This is where a streamed decode pays its sync; calling it on batch N
    after submitting batch N+1 (``Plan.submit``) is the double-buffering
    pattern.  ``unbounded=False`` skips the ``u_*`` block — by far the
    largest transfer (``[B, L, cap]``) — for batches the caller knows
    carry no unbounded-``?P`` lanes; its fields come back as empty
    ``[B, 0, ...]`` placeholders.
    """
    wait_result(r)
    b = r.ids.shape[0]
    if unbounded:
        return ServeResult(*(np.asarray(a) for a in r))
    return ServeResult(
        hit=np.asarray(r.hit), ids=np.asarray(r.ids),
        valid=np.asarray(r.valid), count=np.asarray(r.count),
        overflow=np.asarray(r.overflow),
        u_preds=np.zeros((b, 0), np.int32),
        u_ids=np.zeros((b, 0, r.ids.shape[1]), np.int32),
        u_valid=np.zeros((b, 0, r.ids.shape[1]), np.bool_),
        u_count=np.zeros((b, 0), np.int32),
    )


def wait_result(r: ServeResult) -> None:
    """Block until the device has computed every field of ``r`` (a
    :func:`host_result` after it only copies)."""
    jax.block_until_ready(r)


def decode_lane(op: int, r: ServeResult, i: int):
    """Decode ONE lane of a host-side ``ServeResult`` into its python-level
    answer (the per-op shapes ``_PatternExec._decode`` returns):

      OP_CHECK -> bool;  OP_ROW / OP_COL -> sorted id array;
      OP_S_ANY_O -> matching predicate id array;
      OP_S_ANY_ANY / OP_ANY_ANY_O -> {pred id: id array}.

    Lane-at-a-time is the streaming decode unit: a broker resolves each
    tenant's queries as their lanes decode instead of materializing a
    batch-level result object.
    """
    if op == OP_CHECK:
        return bool(r.hit[i])
    if op in (OP_ROW, OP_COL, OP_S_ANY_O):
        return r.ids[i][r.valid[i]]
    if op in (OP_S_ANY_ANY, OP_ANY_ANY_O):
        return {
            int(r.u_preds[i, l]): r.u_ids[i, l][r.u_valid[i, l]]
            for l in range(r.u_preds.shape[1])
            if r.u_preds[i, l] and r.u_valid[i, l].any()
        }
    raise ValueError(f"not a decodable serve op: {op}")


def _u_candidates(
    q: ServeBatch, f: K2Forest, u_width: int,
    index: PredIndex | None, pmeta: PredIndexMeta | None,
    backend: str | None,
):
    """Candidate predicate lists for the unbounded lanes of a batch.

    Returns ``(is_u_pair, is_u_check, u_key, u_axis, cpreds, cvalid,
    ctrunc)``: 0-based candidates in ``cpreds[B, u_width]`` — from the SP/OP
    index when given (S?PO gathers SP; an optimizer may pre-swap s/o-keyed
    lanes), else the all-preds fallback sweep (requires u_width >= P).
    """
    is_u_pair = (q.op == OP_S_ANY_ANY) | (q.op == OP_ANY_ANY_O)
    is_u_check = q.op == OP_S_ANY_O
    is_u = is_u_pair | is_u_check
    u_axis = jnp.where(q.op == OP_ANY_ANY_O, 1, 0).astype(jnp.int32)
    u_key = jnp.where(is_u, jnp.where(u_axis == 1, q.o, q.s) - 1, 0)
    b = q.op.shape[0]
    if index is not None:
        rows = jnp.where(u_axis == 1, pmeta.n_subjects + u_key, u_key)
        g = predindex.gather_batch(
            pmeta, index, jnp.where(is_u, rows, 0), u_width, backend
        )
        cpreds, cvalid, ctrunc = g.ids, g.valid, g.overflow
    else:
        if u_width < f.n_preds:
            raise ValueError(
                f"all-preds fallback needs u_width >= n_preds "
                f"({u_width} < {f.n_preds}); pass an index to prune"
            )
        lane = jnp.arange(u_width, dtype=jnp.int32)
        cpreds = jnp.broadcast_to(lane, (b, u_width))
        cvalid = jnp.broadcast_to(lane < f.n_preds, (b, u_width))
        ctrunc = jnp.zeros((b,), jnp.bool_)
    cvalid = cvalid & is_u[:, None]
    return is_u_pair, is_u_check, u_key, u_axis, cpreds, cvalid, ctrunc


def _serve_local(
    meta: K2Meta, f: K2Forest, q: ServeBatch, cap: int,
    backend: str | None = None, *,
    index: PredIndex | None = None, pmeta: PredIndexMeta | None = None,
    u_width: int = 0,
) -> ServeResult:
    """Resolve a batch against a (possibly local-shard) forest.

    ``backend`` selects the scan substrate ("pallas" kernel / "jnp"
    traversal; None = the ``REPRO_SCAN_BACKEND`` flag in kernels/ops.py).
    ``u_width`` > 0 enables the unbounded-?P lanes (candidate slots per
    query); 0 compiles them out entirely.
    """
    b = q.op.shape[0]
    is_check = q.op == OP_CHECK
    with jax.named_scope("check"):
        hit = k2forest.check(
            meta, f, q.p - 1, q.s - 1, q.o - 1
        ) & is_check
    axes = jnp.where(q.op == OP_COL, 1, 0).astype(jnp.int32)
    key = jnp.where(q.op == OP_COL, q.o, q.s) - 1
    scan_lane = (q.op == OP_ROW) | (q.op == OP_COL)
    # lanes that are not scans park on pred -1: the traversal skips them,
    # as it skips a predicate or key outside the store (k2forest.live_lanes)
    with jax.named_scope("scan"):
        r = k2forest.scan_batch_mixed(
            meta, f, jnp.where(scan_lane, q.p - 1, -1), key, axes,
            cap, backend, name="k2_scan_bound",
        )
        valid = r.valid & scan_lane[:, None]
        ids = jnp.where(valid, r.ids + 1, 0)
        count = jnp.where(scan_lane, r.count, 0)
        overflow = r.overflow & scan_lane

    if u_width <= 0:
        return ServeResult(
            hit=hit, ids=ids, valid=valid, count=count, overflow=overflow,
            u_preds=jnp.zeros((b, 0), jnp.int32),
            u_ids=jnp.zeros((b, 0, cap), jnp.int32),
            u_valid=jnp.zeros((b, 0, cap), jnp.bool_),
            u_count=jnp.zeros((b, 0), jnp.int32),
        )

    with jax.named_scope("u_candidates"):
        is_u_pair, is_u_check, u_key, u_axis, cpreds, cvalid, ctrunc = (
            _u_candidates(q, f, u_width, index, pmeta, backend)
        )
    preds_f = jnp.where(cvalid, cpreds, 0).reshape(b * u_width)
    keys_f = jnp.repeat(u_key, u_width)
    pair_valid = cvalid & is_u_pair[:, None]

    # pair lanes: one pruned mixed scan replaces the P-way broadcast sweep
    with jax.named_scope("u_scan"):
        ru = k2forest.scan_batch_mixed(
            meta, f, jnp.where(pair_valid.reshape(-1), preds_f, -1), keys_f,
            jnp.repeat(u_axis, u_width), cap, backend, name="k2_scan_u",
        )
        u_valid = ru.valid.reshape(b, u_width, cap) & pair_valid[:, :, None]
        u_ids = jnp.where(u_valid, ru.ids.reshape(b, u_width, cap) + 1, 0)
        u_count = jnp.where(pair_valid, ru.count.reshape(b, u_width), 0)
        u_preds = jnp.where(pair_valid, cpreds + 1, 0)
        overflow = overflow | (
            is_u_pair
            & ((ru.overflow.reshape(b, u_width) & pair_valid).any(axis=1)
               | ctrunc)
        )

    # S?PO lanes: check candidates, compact matching predicate ids into ids.
    # NOTE this intentionally diverges from predindex.check_pruned_batch
    # (which compacts into u_width slots and so can never truncate): the
    # serve IR must fit the shared (B, cap) ids buffer, so matches beyond
    # cap truncate WITH the overflow bit set — callers (Engine.pattern)
    # must honor it.  Keep the three gather→check/scan→mask copies (here,
    # the sharded _local, predindex.*_pruned_batch) in sync when touching
    # the contract.
    with jax.named_scope("u_compact"):
        hitm = k2forest.check(
            meta, f, preds_f,
            jnp.repeat(q.s - 1, u_width),
            jnp.repeat(q.o - 1, u_width),
        ).reshape(b, u_width) & cvalid & is_u_check[:, None]
        valid5, count5, ovf5, (ids5,) = jax.vmap(
            lambda v, a: _compact(v, cap, a)
        )(hitm, jnp.where(hitm, cpreds + 1, 0))
        ids = jnp.where(is_u_check[:, None], ids5, ids)
        valid = jnp.where(is_u_check[:, None], valid5, valid)
        count = jnp.where(is_u_check, count5, count)
        overflow = overflow | (is_u_check & (ovf5 | ctrunc))

    return ServeResult(
        hit=hit, ids=ids, valid=valid, count=count, overflow=overflow,
        u_preds=u_preds, u_ids=u_ids, u_valid=u_valid, u_count=u_count,
    )


def make_serve_step(
    meta: K2Meta, cap: int, *, backend: str | None = None,
    pmeta: PredIndexMeta | None = None, u_width: int | None = None,
    donate: bool = False,
):
    """Single-device jit'd serve program.

    ``backend``: an ``ExecConfig`` (explicit backend + interpret — the
    compiled-plan path, no env reads at trace time), a "pallas"/"jnp"
    string, or ``None`` (legacy env resolution at trace time).
    ``u_width`` candidate slots per unbounded lane (default:
    ``pmeta.max_degree`` when an index meta is given, else 0 = unbounded
    ops compiled out).  Call as ``serve_step(forest, batch[, index])`` —
    passing ``index=None`` with ``u_width >= n_preds`` runs the all-preds
    fallback sweep.

    ``donate=True`` donates the per-batch ``ServeBatch`` buffers (argument
    1) to XLA: the program may alias their device memory for outputs, so a
    donated device batch is consumed by the call (``x.is_deleted()``
    afterwards).  Numpy batches are unaffected (they are copied in under
    jit anyway); callers that re-use a device batch must copy first — the
    engine's ``_ServeExec`` does this defensively.
    """
    if u_width is None:
        u_width = pmeta.max_degree if pmeta is not None else 0

    def serve_step(f: K2Forest, q: ServeBatch, index=None) -> ServeResult:
        return _serve_local(
            meta, f, q, cap, backend, index=index, pmeta=pmeta, u_width=u_width
        )

    return jax.jit(serve_step, donate_argnums=(1,) if donate else ())


# ---------------------------------------------------------------------------
# sharded serving
# ---------------------------------------------------------------------------


def shard_forest(f: K2Forest, mesh: Mesh, axis: str = "model") -> K2Forest:
    """Place the arena with the predicate dimension sharded over ``axis``.

    ``f.n_preds`` must divide the axis size (:func:`pad_preds`).  Each
    shard's block of ``P / mp`` trees is laid out in whole 8-row tiles of
    its own, so the rows of the result are shard blocks, not global
    predicate ids: it is meant for the sharded serve programs only.
    """
    mp = int(mesh.shape[axis])
    p, rows = f.n_preds, f.t_words.shape[0]
    if p % mp:
        raise ValueError(f"{p} predicates do not divide {mp} shards; pad_preds")
    p_loc = p // mp
    r_loc = bitvec.round_up(p_loc, bitvec.TILE_ROWS)
    if r_loc * mp != rows or p_loc != r_loc:

        def blocks(a):
            a = a[:p].reshape(mp, p_loc, a.shape[1])
            return jnp.pad(a, ((0, 0), (0, r_loc - p_loc), (0, 0))).reshape(
                mp * r_loc, -1)

        f = f._replace(**{k: blocks(getattr(f, k)) for k in K2Forest._fields
                          if k != "nnz"})
    sh = NamedSharding(mesh, P(axis))
    return K2Forest(*(jax.device_put(a, sh) for a in f))


def forest_pspecs(axis: str = "model") -> K2Forest:
    return K2Forest(
        t_words=P(axis), t_rank=P(axis), l_words=P(axis),
        ones_before=P(axis), level_start=P(axis), nnz=P(axis),
    )


def pad_preds(f: K2Forest, multiple: int) -> K2Forest:
    """Pad the predicate axis so it divides the model-axis size.

    Padded trees are all-zeros (valid empty k²-trees): queries routed to them
    return no results, so padding is semantically inert.  The arena rows
    stay whole 8-row tiles.
    """
    Pn = f.n_preds
    pad = (-Pn) % multiple
    if pad == 0:
        return f
    rows = bitvec.round_up(Pn + pad, bitvec.TILE_ROWS)
    out = [jnp.pad(a, ((0, rows - a.shape[0]), (0, 0))) for a in f[:-1]]
    return K2Forest(*out, nnz=jnp.pad(f.nnz, (0, pad)))


def make_sharded_serve_step(
    meta: K2Meta, mesh: Mesh, cap: int, *, data_axes=("data",),
    model_axis="model", backend: str | None = None,
    pmeta: PredIndexMeta | None = None, u_width: int | None = None,
):
    """shard_map'd serve program: forest by predicate, queries by batch.

    Every model shard holds P/mp trees with LOCAL indices; a query with
    global predicate g is owned by shard g // P_loc and resolved there with
    local id g % P_loc; other shards compute a masked (empty) traversal and
    the ``psum`` over the model axis merges.

    With ``pmeta`` (and a replicated ``PredIndex`` third argument) the
    unbounded IR ops are served too: candidates are gathered identically on
    every shard, each shard scans only the candidates it owns, and the psum
    assembles the ``[B, u_width, cap]`` block — the index-pruned counterpart
    of ``make_sharded_unbounded_scan``'s ``[B, P, cap]`` all-gather.
    Signature: ``fn(forest, batch)`` without an index, ``fn(forest, batch,
    index)`` with one.
    """
    if u_width is None:
        u_width = pmeta.max_degree if pmeta is not None else 0
    if u_width > 0 and pmeta is None:
        raise ValueError("sharded unbounded serve requires a pred index (pmeta)")
    mp = int(np.prod([mesh.shape[a] for a in (model_axis,)]))

    dax = data_axes if len(data_axes) > 1 else data_axes[0]
    qspec = ServeBatch(op=P(dax), s=P(dax), p=P(dax), o=P(dax))
    fspec = forest_pspecs(model_axis)
    out_spec = ServeResult(
        hit=P(dax), ids=P(dax), valid=P(dax),
        count=P(dax), overflow=P(dax),
        u_preds=P(dax), u_ids=P(dax), u_valid=P(dax), u_count=P(dax),
    )

    def _local(f_loc: K2Forest, q: ServeBatch, index=None) -> ServeResult:
        p_loc = f_loc.n_preds  # local predicate count
        b = q.op.shape[0]
        shard = jax.lax.axis_index(model_axis)
        g = q.p - 1  # 0-based global predicate
        owner = g // p_loc
        mine = owner == shard
        lp = jnp.where(mine, g % p_loc, 0).astype(jnp.int32)
        q_loc = ServeBatch(
            op=jnp.where(mine, q.op, -1), s=q.s, p=lp + 1, o=q.o
        )
        r = _serve_local(meta, f_loc, q_loc, cap, backend)
        # MINIMAL psum payload: only the id matrix and two bit-vectors go on
        # the wire; `valid` (== ids != 0) and `count` are re-derived locally
        # after the reduce.  This halves the all-reduce bytes vs reducing the
        # full ServeResult (§Perf hillclimb on the paper's own program).
        ids = jax.lax.psum(jnp.where(mine[:, None], r.ids, 0), model_axis)
        flags = jax.lax.psum(
            jnp.where(
                mine,
                r.hit.astype(jnp.int32) + 2 * r.overflow.astype(jnp.int32),
                0,
            ),
            model_axis,
        )
        valid = ids != 0
        hit = (flags & 1).astype(jnp.bool_)
        overflow = ((flags >> 1) & 1).astype(jnp.bool_)
        count = valid.sum(axis=-1).astype(jnp.int32)

        if u_width <= 0:
            return ServeResult(
                hit=hit, ids=ids, valid=valid, count=count, overflow=overflow,
                u_preds=jnp.zeros((b, 0), jnp.int32),
                u_ids=jnp.zeros((b, 0, cap), jnp.int32),
                u_valid=jnp.zeros((b, 0, cap), jnp.bool_),
                u_count=jnp.zeros((b, 0), jnp.int32),
            )

        # unbounded lanes: candidates gathered replicated (index is
        # replicated), each shard scans/checks only the candidates it owns
        is_u_pair, is_u_check, u_key, u_axis, cpreds, cvalid, ctrunc = (
            _u_candidates(q, f_loc, u_width, index, pmeta, backend)
        )
        owner_u = cpreds // p_loc
        mine_u = cvalid & (owner_u == shard)
        preds_f = jnp.where(mine_u, cpreds % p_loc, 0).reshape(b * u_width)
        keys_f = jnp.repeat(u_key, u_width)

        pair_mine = mine_u & is_u_pair[:, None]
        ru = k2forest.scan_batch_mixed(
            meta, f_loc, jnp.where(pair_mine.reshape(-1), preds_f, -1), keys_f,
            jnp.repeat(u_axis, u_width), cap, backend, name="k2_scan_u",
        )
        uv_loc = ru.valid.reshape(b, u_width, cap) & pair_mine[:, :, None]
        u_ids = jax.lax.psum(
            jnp.where(uv_loc, ru.ids.reshape(b, u_width, cap) + 1, 0),
            model_axis,
        )
        hitm_loc = k2forest.check(
            meta, f_loc, preds_f,
            jnp.repeat(q.s - 1, u_width),
            jnp.repeat(q.o - 1, u_width),
        ).reshape(b, u_width) & mine_u & is_u_check[:, None]
        # one packed [B, u_width] reduce: check hits (bit 0), per-candidate
        # counts (needed because a count can legitimately be 0 with no ids)
        packed = jax.lax.psum(
            hitm_loc.astype(jnp.int32)
            + 2 * jnp.where(pair_mine, ru.count.reshape(b, u_width), 0),
            model_axis,
        )
        hitm = (packed & 1) == 1
        u_count = packed >> 1
        pair_ovf = jax.lax.psum(
            (ru.overflow.reshape(b, u_width) & pair_mine)
            .any(axis=1).astype(jnp.int32),
            model_axis,
        ) > 0
        # replicated post-reduce compute (identical on every shard)
        u_valid = u_ids != 0
        u_preds = jnp.where(cvalid & is_u_pair[:, None], cpreds + 1, 0)
        valid5, count5, ovf5, (ids5,) = jax.vmap(
            lambda v, a: _compact(v, cap, a)
        )(hitm, jnp.where(hitm, cpreds + 1, 0))
        ids = jnp.where(is_u_check[:, None], ids5, ids)
        valid = jnp.where(is_u_check[:, None], valid5, valid)
        count = jnp.where(is_u_check, count5, count)
        overflow = (
            overflow
            | (is_u_pair & (pair_ovf | ctrunc))
            | (is_u_check & (ovf5 | ctrunc))
        )
        return ServeResult(
            hit=hit, ids=ids, valid=valid, count=count, overflow=overflow,
            u_preds=u_preds, u_ids=u_ids, u_valid=u_valid, u_count=u_count,
        )

    if u_width > 0:
        ispec = PredIndex(*(P() for _ in PredIndex._fields))  # replicated
        fn = jax.shard_map(
            _local, mesh=mesh, in_specs=(fspec, qspec, ispec),
            out_specs=out_spec,
            check_vma=False,  # pallas_call has no replication rule
        )
    else:
        fn = jax.shard_map(
            lambda f_loc, q: _local(f_loc, q), mesh=mesh,
            in_specs=(fspec, qspec), out_specs=out_spec,
            check_vma=False,  # pallas_call has no replication rule (scan kernel)
        )
    return jax.jit(fn)


def make_sharded_unbounded_scan(
    meta: K2Meta, mesh: Mesh, cap: int, *, data_axes=("data",), model_axis="model",
    backend: str | None = None,
):
    """(S,?P,?O) / (?S,?P,O) batch: every shard scans its LOCAL predicates,
    results all-gathered over the model axis -> [B, P_padded, cap].

    This is the paper's vertical-partitioning worst case turned into an
    embarrassingly parallel sweep — kept as the index-free fallback and the
    differential reference for the index-pruned unbounded lanes of
    ``make_sharded_serve_step``.  The local sweep is one flat
    (b · P_loc)-query ``scan_batch_mixed`` launch, so it follows the
    ``REPRO_SCAN_BACKEND`` flag (Pallas kernel / jnp reference) like the
    bounded-predicate serve path.
    """
    dax = data_axes if len(data_axes) > 1 else data_axes[0]
    qP = P(dax)
    fspec = forest_pspecs(model_axis)

    def _local(f_loc: K2Forest, keys: jax.Array, axes: jax.Array):
        p_loc = f_loc.n_preds
        b = keys.shape[0]
        # the all-preds sweep as one batched mixed scan with broadcast keys
        preds_f = jnp.tile(jnp.arange(p_loc, dtype=jnp.int32), b)
        keys_f = jnp.repeat(keys - 1, p_loc)
        axes_f = jnp.repeat(axes, p_loc)
        r = k2forest.scan_batch_mixed(
            meta, f_loc, preds_f, keys_f, axes_f, cap, backend
        )
        ids = jnp.where(r.valid, r.ids + 1, 0).reshape(b, p_loc, cap)
        valid = r.valid.reshape(b, p_loc, cap)
        count = r.count.reshape(b, p_loc)
        ids = jax.lax.all_gather(ids, model_axis, axis=1, tiled=True)
        valid = jax.lax.all_gather(valid, model_axis, axis=1, tiled=True)
        count = jax.lax.all_gather(count, model_axis, axis=1, tiled=True)
        return ids, valid, count

    fn = jax.shard_map(
        _local, mesh=mesh, in_specs=(fspec, qP, qP), out_specs=(qP, qP, qP),
        check_vma=False,  # all_gather(tiled) replication defeats VMA inference
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# the compiled-plan pipeline: Query -> Engine.compile(ExecConfig) -> Plan
# ---------------------------------------------------------------------------


_OP_FOR_SHAPE = {
    (True, True, True): OP_CHECK,
    (True, True, False): OP_ROW,
    (False, True, True): OP_COL,
    (True, False, True): OP_S_ANY_O,
    (True, False, False): OP_S_ANY_ANY,
    (False, False, True): OP_ANY_ANY_O,
}
_UNBOUNDED_OPS = (OP_S_ANY_O, OP_S_ANY_ANY, OP_ANY_ANY_O)


class _ExecBase:
    """Shared executor state: one per ``(shape_key, config)`` cache slot.

    Holds the effective caps — grown in place by the :class:`CapPolicy`
    doubling loop, so every plan sharing this executor benefits from a
    growth paid once.
    """

    def __init__(self, engine: "Engine", cfg: ExecConfig):
        self.engine = engine
        self.cfg = cfg
        self.cap = cfg.cap
        self.cap_y = cfg.cap_y
        # the store epoch this executor was compiled against — a dynamic
        # store bumps it on compaction swap, and running a stale executor
        # would silently serve dropped triples from the old forest
        self.epoch = engine.store_epoch

    def _grow(self, fn):
        self.engine._check_epoch(self.epoch)
        t, m = obs.STATE.tracer, obs.STATE.metrics
        if t is not None or m is not None:
            inner = fn

            def fn(cap, cap_y):
                try:
                    return inner(cap, cap_y)
                except CapOverflow:
                    # the policy loop will recompile at doubled caps —
                    # that retry is the event worth counting
                    if m is not None:
                        m.counter("plan.cap_overflow").inc()
                    if t is not None:
                        t.instant("plan.cap_overflow", cap=cap, cap_y=cap_y)
                    raise

        out, self.cap, self.cap_y = qapi.run_with_policy(
            self.cfg.cap_policy, self.cap, self.cap_y, fn
        )
        return out

    def submit(self, q, batch):
        raise NotImplementedError(
            f"{type(self).__name__} has no raw device surface; "
            "Plan.submit is a ServeQ-only streaming hook"
        )

    def compiled_text(self, q, batch):
        raise NotImplementedError(f"{type(self).__name__} has no HLO view")

    def cost_profile(self, q, batch):
        raise NotImplementedError(
            f"{type(self).__name__} has no compiled-program cost surface"
        )

    @staticmethod
    def _overflow_guard(r):
        if bool(np.asarray(r.overflow).any()):
            raise CapOverflow(
                "result lane truncated at cap; CapPolicy(grow=True) doubles"
            )


class _PatternExec(_ExecBase):
    """Any of the eight triple-pattern shapes, single query or batched."""

    def run(self, q: TriplePatternQ, batch):
        s, p, o, b, single = self._consts(q, batch)
        bound = q.bound
        if bound == (False, True, False):  # (?S, P, ?O) pair enumeration
            out = self._grow(lambda cap, _: self._run_pairs(p, b, cap))
        elif bound == (False, False, False):  # (?S, ?P, ?O) dump
            if batch is not None:
                raise ValueError("the dump pattern takes no batch")
            out = self._grow(lambda cap, _: self._run_dump(cap))
        else:
            op = _OP_FOR_SHAPE[bound]
            out = self._grow(
                lambda cap, _: self._run_serve(op, s, p, o, b, cap)
            )
        return out[0] if single else out

    def _consts(self, q: TriplePatternQ, batch):
        vals = {"s": q.s, "p": q.p, "o": q.o}
        bound = dict(zip("spo", q.bound))
        if batch is None:
            b, single = 1, True
            batch = {}
        else:
            if not batch:
                raise ValueError(
                    "batch must be a non-empty dict of bound-position id "
                    "arrays (or None to use the query's own constants)"
                )
            bad = set(batch) - {k for k in "spo" if bound[k]}
            if bad:
                raise ValueError(
                    f"batch keys {sorted(bad)} are not bound positions of {q!r}"
                )
            b, single = len(np.asarray(next(iter(batch.values())))), False
        arrs = []
        for k in "spo":
            if k in batch:
                a = np.asarray(batch[k], np.int64).reshape(-1)
                if a.shape[0] != b:
                    raise ValueError("batch arrays must share one length")
            else:
                a = np.full(b, vals[k] if bound[k] else 0, np.int64)
            arrs.append(a)
        return (*arrs, b, single)

    def _run_serve(self, op, s, p, o, b, cap):
        eng, cfg = self.engine, self.cfg
        ops_a = np.full(b, op, np.int32)
        if op not in _UNBOUNDED_OPS:
            r = eng._run_lanes(cfg, cap, ops_a, s, p, o)
            self._overflow_guard(r)
            return self._decode(op, r, range(b))

        bi = eng.store.pred_index if cfg.use_pred_index else None
        if bi is None:
            if cfg.mesh is not None:
                raise ValueError(
                    "sharded unbounded-?P serving needs the SP/OP index; "
                    "build the store with_pred_index=True or drop mesh"
                )
            r = eng._run_lanes(
                cfg, cap, ops_a, s, p, o,
                u_width=max(eng.store.n_preds, 1), with_index=False,
            )
            self._overflow_guard(r)
            return self._decode(op, r, range(b))

        u_width = eng._u_width(cfg)
        # quantile-sized lanes: pre-route outlier entities (candidate list
        # longer than the lane — the device gather's `truncated` bit,
        # mirrored on the host CSR) to the all-preds sweep fallback
        rows = (
            bi.meta.n_subjects + o - 1 if op == OP_ANY_ANY_O else s - 1
        )
        outlier = predindex.host_degrees(bi, rows) > u_width
        out = [None] * b
        in_idx = np.nonzero(~outlier)[0]
        out_idx = np.nonzero(outlier)[0]
        if in_idx.size:
            r = eng._run_lanes(
                cfg, cap, ops_a[in_idx], s[in_idx], p[in_idx], o[in_idx],
                u_width=u_width, with_index=True,
            )
            self._overflow_guard(r)
            for j, res in zip(in_idx, self._decode(op, r, range(in_idx.size))):
                out[j] = res
        if out_idx.size:
            # outliers are the degree-distribution tail: served by the
            # single-device all-preds sweep program, exact at any quantile
            r = eng._run_lanes(
                cfg.replace(mesh=None), cap,
                ops_a[out_idx], s[out_idx], p[out_idx], o[out_idx],
                u_width=max(eng.store.n_preds, 1), with_index=False,
            )
            self._overflow_guard(r)
            for j, res in zip(out_idx, self._decode(op, r, range(out_idx.size))):
                out[j] = res
        return out

    @staticmethod
    def _decode(op, r, idxs):
        h = jax.tree.map(np.asarray, r)
        return [decode_lane(op, h, i) for i in idxs]

    def _run_pairs(self, p, b, cap):
        eng = self.engine
        view = eng.dynamic_view()
        if view is None:
            r = k2forest.range_scan_batch(
                eng.meta, eng.forest, jnp.asarray(p - 1, jnp.int32), cap,
                self.cfg,
            )
            self._overflow_guard(r)
            rows, cols, valid = (
                np.asarray(a) for a in (r.rows, r.cols, r.valid)
            )
            return [
                np.stack(
                    [rows[i][valid[i]] + 1, cols[i][valid[i]] + 1], axis=1
                )
                for i in range(b)
            ]
        # dynamic: delta-only preds (beyond the static forest) are clamped
        # to a safe tree for dispatch and answered purely from the snapshot
        p = np.asarray(p, np.int64).reshape(-1)
        safe = p <= view.preds_static
        empty = np.empty(0, np.int64)
        if safe.any():
            p_run = np.where(safe, p, 1)
            r = k2forest.range_scan_batch(
                eng.meta, eng.forest, jnp.asarray(p_run - 1, jnp.int32),
                cap, self.cfg,
            )
            if bool((np.asarray(r.overflow) & safe).any()):
                raise CapOverflow(
                    "result lane truncated at cap; CapPolicy(grow=True) "
                    "doubles"
                )
            rows, cols, valid = (
                np.asarray(a) for a in (r.rows, r.cols, r.valid)
            )
        out = []
        for i in range(b):
            if safe[i]:
                ss = rows[i][valid[i]].astype(np.int64) + 1
                oo = cols[i][valid[i]].astype(np.int64) + 1
            else:
                ss, oo = empty, empty
            ss, oo = view.snap.merge_pairs(int(p[i]), ss, oo)
            out.append(np.stack([ss, oo], axis=1).reshape(-1, 2))
        return out

    def _run_dump(self, cap):
        eng = self.engine
        view = eng.dynamic_view()
        r = patterns.dump(eng.meta, eng.forest, cap, self.cfg)
        self._overflow_guard(r)
        rows, cols, valid = (np.asarray(a) for a in (r.rows, r.cols, r.valid))
        out = {}
        for pi in range(eng.store.n_preds):
            if valid[pi].any():
                out[pi + 1] = np.stack(
                    [rows[pi][valid[pi]], cols[pi][valid[pi]]], axis=1
                )
        if view is not None:
            merged = {}
            empty = np.empty(0, np.int64)
            for p in range(1, view.total_preds + 1):
                pairs = out.get(p)
                ss = pairs[:, 0].astype(np.int64) if pairs is not None else empty
                oo = pairs[:, 1].astype(np.int64) if pairs is not None else empty
                ss, oo = view.snap.merge_pairs(p, ss, oo)
                if len(ss):
                    merged[p] = np.stack(
                        [np.asarray(ss), np.asarray(oo)], axis=1
                    )
            out = merged
        return [out]


class _JoinExec(_ExecBase):
    """Join categories A–F.  A–C are pure serve-IR side-list lanes through
    the shared compiled serve step (+ ``sortedset`` algebra); D–F run the
    fused scan→rebind kernel path of ``core.joins``."""

    def run(self, q: JoinQ, batch):
        if batch is not None:
            raise ValueError("join plans take no batch")
        if q.category in "ABC":
            return self._grow(lambda cap, _: self._run_abc(q, cap))
        return self._grow(
            lambda cap, cap_y: self._run_def(q, cap, cap_y)
        )

    @staticmethod
    def _lane(vpos, p, c):
        # ?X in subject position -> reverse neighbors (?S,P,O) = OP_COL;
        # ?X in object position -> direct neighbors (S,P,?O) = OP_ROW
        return (OP_COL, 0, p, c) if vpos == "s" else (OP_ROW, c, p, 0)

    def _idset(self, r, i):
        ids = jnp.where(r.valid[i], r.ids[i], SENTINEL)
        return IdSet(ids, r.valid[i], r.count[i], jnp.asarray(False))

    def _run_abc(self, q, cap):
        eng, cfg = self.engine, self.cfg
        # the B/C per-pred side-list enumerations must cover delta-only
        # appended predicates too; those lanes are sanitized to dead on the
        # device and answered from the snapshot in the merge
        Pn = dyn.total_preds(eng.store)
        if q.category == "A":
            lanes = [
                self._lane(q.vpos1, q.p1, q.c1),
                self._lane(q.vpos2, q.p2, q.c2),
            ]
        elif q.category == "B":
            lanes = [self._lane(q.vpos1, q.p1, q.c1)] + [
                self._lane(q.vpos2, pp, q.c2) for pp in range(1, Pn + 1)
            ]
        else:  # C
            lanes = [
                self._lane(q.vpos1, pp, q.c1) for pp in range(1, Pn + 1)
            ] + [self._lane(q.vpos2, pp, q.c2) for pp in range(1, Pn + 1)]
        arr = np.asarray(lanes, np.int64)
        r = eng._run_lanes(cfg, cap, arr[:, 0], arr[:, 1], arr[:, 2], arr[:, 3])
        self._overflow_guard(r)

        if q.category == "A":
            rr = sortedset.intersect(self._idset(r, 0), self._idset(r, 1))
            return np.asarray(rr.ids)[np.asarray(rr.valid)]
        if q.category == "B":
            a = self._idset(r, 0)
            ids2 = jnp.where(r.valid[1:], r.ids[1:], SENTINEL)

            def one(idp, vp):
                b = IdSet(idp, vp, vp.sum().astype(jnp.int32), jnp.asarray(False))
                rr = sortedset.intersect(a, b)
                return rr.ids, rr.valid

            ids, valid = jax.vmap(one)(ids2, r.valid[1:])
            ids, valid = np.asarray(ids), np.asarray(valid)
            return {
                pi + 1: ids[pi][valid[pi]]
                for pi in range(Pn)
                if valid[pi].any()
            }
        ids = jnp.where(r.valid, r.ids, SENTINEL)
        u1 = sortedset.union_rows(ids[:Pn], r.valid[:Pn], cap, False)
        u2 = sortedset.union_rows(ids[Pn:], r.valid[Pn:], cap, False)
        if bool(np.asarray(u1.overflow | u2.overflow)):
            raise CapOverflow("side-list union truncated at cap")
        rr = sortedset.intersect(u1, u2)
        return np.asarray(rr.ids)[np.asarray(rr.valid)]

    def _run_def(self, q, cap, cap_y):
        eng, cfg = self.engine, self.cfg
        view = eng.dynamic_view()
        if view is not None:
            # the fused scan->rebind kernels read only the static forest;
            # with a live delta the join decomposes into two serve-lane
            # stages (X side list, then per-x rebind) so every stage rides
            # the sanitize+merge path
            return self._run_def_dynamic(q, cap, cap_y)
        m, f = eng.meta, eng.forest
        if q.category == "D":
            r = joins.join_d(
                m, f, q.p1, q.c1, q.vpos1, q.p2, q.vpos2,
                cap_x=cap, cap_y=cap_y, backend=cfg,
            )
            self._overflow_guard(r)
            return _pairs_to_dict(r)
        if q.category == "E":
            r = joins.join_e(
                m, f, q.p1, q.c1, q.vpos1, q.vpos2,
                cap_x=cap, cap_y=cap_y, backend=cfg,
            )
        else:  # F
            r = joins.join_f(
                m, f, q.c1, q.vpos1, q.vpos2,
                cap_x=cap, cap_y=cap_y, backend=cfg,
            )
        self._overflow_guard(r)
        return _pairs_to_dict_pred(r)

    def _run_def_dynamic(self, q, cap, cap_y):
        eng, cfg = self.engine, self.cfg
        pe = _PatternExec(eng, cfg)
        # stage 1: the shared-variable side list X
        if q.category in ("D", "E"):
            lane = np.asarray([self._lane(q.vpos1, q.p1, q.c1)], np.int64)
            r = eng._run_lanes(
                cfg, cap, lane[:, 0], lane[:, 1], lane[:, 2], lane[:, 3]
            )
            self._overflow_guard(r)
            xs = np.asarray(r.ids[0])[np.asarray(r.valid[0])].astype(np.int64)
        else:  # F: ?X linked to c1 by ANY predicate — unbounded lane, union
            op1 = OP_ANY_ANY_O if q.vpos1 == "s" else OP_S_ANY_ANY
            key = np.asarray([q.c1], np.int64)
            zero = np.zeros(1, np.int64)
            s1, o1 = (zero, key) if q.vpos1 == "s" else (key, zero)
            per = pe._run_serve(op1, s1, zero, o1, 1, cap)[0]
            xs = (
                np.unique(np.concatenate([np.asarray(v) for v in per.values()]))
                .astype(np.int64)
                if per else np.empty(0, np.int64)
            )
        if not xs.size:
            return {}
        # stage 2: rebind each x
        if q.category == "D":
            if q.vpos2 == "s":
                ops2 = np.full(xs.size, OP_ROW, np.int32)
                s2, o2 = xs, np.zeros(xs.size, np.int64)
            else:
                ops2 = np.full(xs.size, OP_COL, np.int32)
                s2, o2 = np.zeros(xs.size, np.int64), xs
            p2 = np.full(xs.size, q.p2, np.int64)
            r2 = eng._run_lanes(cfg, cap_y, ops2, s2, p2, o2)
            self._overflow_guard(r2)
            ids, valid = np.asarray(r2.ids), np.asarray(r2.valid)
            return {
                int(x): ids[i][valid[i]]
                for i, x in enumerate(xs)
                if valid[i].any()
            }
        op2 = OP_S_ANY_ANY if q.vpos2 == "s" else OP_ANY_ANY_O
        zero = np.zeros(xs.size, np.int64)
        s2, o2 = (xs, zero) if q.vpos2 == "s" else (zero, xs)
        per_x = pe._run_serve(op2, s2, zero, o2, xs.size, cap_y)
        out: dict[int, dict[int, np.ndarray]] = {}
        for i, x in enumerate(xs):
            for pl, ys in per_x[i].items():
                if len(ys):
                    out.setdefault(int(pl), {})[int(x)] = np.asarray(ys)
        return {p: d for p, d in sorted(out.items())}


_ANON = algebra.ANON  # internal prefix for None (anonymous) BGP positions


class _BgpExec(_ExecBase):
    """Basic graph patterns: the optimizer plans per call (its join order
    is data-dependent), but every check / bounded-scan step resolves
    through the engine's pooled serve-step programs.

    ``None`` positions are EXISTENTIAL: they join like variables inside
    the optimizer but are projected away from the result — only named
    variables come back, with distinct rows over those columns.
    """

    def run(self, q: BgpQ, batch):
        if batch is not None:
            raise ValueError("BGP plans take no batch")
        from repro.core import optimizer  # deferred: optimizer imports engine

        pats = algebra.name_anon(q.patterns)

        def fn(cap, _):
            return optimizer.run_bgp(
                self.engine.store, pats, cap=cap, exec_=self.cfg,
                serve=self.engine._lanes_runner(self.cfg, cap),
            )

        # project the anonymous columns away and dedup the named rows —
        # the shared algebra helper (run_bgp dedups over ALL columns, so
        # dropping some can leave duplicate rows in the named ones)
        return algebra.project_named(self._grow(fn))


class _SelectExec(_ExecBase):
    """SPARQL-shaped SELECT: the query lowers to a ``core.algebra``
    operator tree and ``core.planner`` executes it — cost-ordered (DP)
    conjunctive blocks with sideways information passing, every check /
    bounded-scan step through the engine's pooled serve-step programs.

    Returns columnar named bindings like ``_BgpExec``; with ``order_by``
    the row order is the query's (deterministic total order), otherwise
    rows come back in dedup order (set semantics either way).
    """

    def run(self, q: SelectQ, batch):
        if batch is not None:
            raise ValueError("SELECT plans take no batch")
        from repro.core import planner  # deferred: planner imports engine

        tree = algebra.from_select(q)

        def fn(cap, _):
            return planner.execute(
                self.engine.store, tree, cap=cap, exec_=self.cfg,
                serve=self.engine._lanes_runner(self.cfg, cap),
            )

        # the tree ends in Project (+ Slice): columns are already the
        # named selection, rows already distinct (and ordered if asked)
        return dict(self._grow(fn).cols)


class _ServeExec(_ExecBase):
    """Raw serve-IR passthrough: ``plan(ServeBatch) -> ServeResult``."""

    @staticmethod
    def _coerce(batch):
        if batch is None:
            raise ValueError("ServeQ plans take a ServeBatch")
        if not isinstance(batch, ServeBatch):
            batch = ServeBatch(*(jnp.asarray(a, jnp.int32) for a in batch))
        return batch

    def _donates(self) -> bool:
        """Whether the dispatched program donates its batch argument
        (mirrors ``Engine._program``'s donate condition)."""
        return self.cfg.donate_batch and self.cfg.mesh is None

    def _donation_copy(self, qb: ServeBatch) -> ServeBatch:
        """Fresh batch buffers for one donating dispatch.

        The donating program consumes (aliases) its batch argument, so a
        caller-held DEVICE batch is copied per call — this also makes cap
        growth safe (the retry dispatch gets its own copy).  Numpy inputs
        are copied in by jit anyway and skip the defensive copy.
        """
        if not self._donates():
            return qb
        return ServeBatch(*(
            jnp.array(a, jnp.int32, copy=True)
            if isinstance(a, jax.Array) else jnp.asarray(a, jnp.int32)
            for a in qb
        ))

    def run(self, q: ServeQ, batch):
        batch = self._coerce(batch)

        def one(cap):
            view = self.engine.dynamic_view()
            qb = batch if view is None else view.sanitize_batch(batch)
            r = self._call(qb, cap, q.unbounded)
            if view is not None:
                # the delta merge needs host arrays anyway; fetch, fold the
                # snapshot in (host-side widening means the delta itself can
                # never trip the guard), keep static overflow bits
                r = view.merge_lanes(
                    batch.op, batch.s, batch.p, batch.o,
                    host_result(r, unbounded=q.unbounded),
                )
            return r

        def fn(cap, _):
            t = obs.STATE.tracer
            if t is None:
                r = one(cap)
                self._overflow_guard(r)
                return r
            with t.span("plan.call", cat="plan",
                        b=int(batch.op.shape[0]), cap=cap,
                        unbounded=q.unbounded):
                with t.span("plan.dispatch", cat="plan"):
                    r = one(cap)
                with t.span("plan.sync", cat="plan"):
                    self._overflow_guard(r)
            return r

        return self._grow(fn)

    def submit(self, q: ServeQ, batch) -> ServeResult:
        """Streamed-serving dispatch: device ``ServeResult`` with NO host
        sync — the overflow guard and any cap growth are the caller's job
        (``launch.broker`` handles both per tenant).  The executor's cap
        never grows through this path, so a shared base plan stays at its
        configured geometry no matter what overflows ride through it.

        Dynamic stores: this is the STATIC lane only — the caller grabs
        ``Engine.dynamic_view()`` at dispatch, sanitizes the batch, and
        merges the snapshot into the fetched result itself (the broker
        does all three)."""
        self.engine._check_epoch(self.epoch)
        t = obs.STATE.tracer
        if t is None:
            return self._call(self._coerce(batch), self.cap, q.unbounded)
        batch = self._coerce(batch)
        with t.span("plan.submit", cat="plan", b=int(batch.op.shape[0]),
                    cap=self.cap, unbounded=q.unbounded):
            return self._call(batch, self.cap, q.unbounded)

    def _args(self, qb, cap, unbounded):
        eng, cfg = self.engine, self.cfg
        f = eng._forest_for(cfg)
        if not unbounded:
            return eng._program(cfg, cap, 0, False), (f, qb)
        bi = eng.store.pred_index if cfg.use_pred_index else None
        if bi is None:
            if cfg.mesh is not None:
                raise ValueError(
                    "sharded unbounded-?P serving needs the SP/OP index"
                )
            fn = eng._program(cfg, cap, max(eng.store.n_preds, 1), False)
            return fn, (f, qb, None)
        fn = eng._program(cfg, cap, eng._u_width(cfg), True)
        return fn, (f, qb, bi.select(cfg.pred_index_layout)[0])

    def _call(self, qb, cap, unbounded):
        fn, args = self._args(self._donation_copy(qb), cap, unbounded)
        return fn(*args)

    def compiled_text(self, q, batch):
        """Compiled-module text of the current program for this batch —
        lets callers assert communication properties (e.g. the
        sharded-smoke 'no all-gather on the wire' check)."""
        fn, args = self._args(batch, self.cap, q.unbounded)
        return fn.lower(*args).compile().as_text()

    def _u_width_of(self, unbounded: bool) -> int:
        """The unbounded-lane width the current program geometry carries
        (mirrors :meth:`_args` without building the program)."""
        if not unbounded:
            return 0
        eng, cfg = self.engine, self.cfg
        if eng.store.pred_index is None or not cfg.use_pred_index:
            return max(eng.store.n_preds, 1)
        return eng._u_width(cfg)

    def cost_profile(self, q: ServeQ, batch=None) -> dict:
        """Static XLA cost profile of the serve program this plan would
        dispatch for ``batch`` (8 pow2-padded lanes when ``None``) —
        cached per program geometry in the engine's program cache."""
        eng, cfg = self.engine, self.cfg
        if batch is None:
            b = eng._pad_b(1, cfg)
            z = np.zeros(b, np.int32)
            batch = ServeBatch(op=z, s=z, p=z, o=z)
        batch = self._coerce(batch)
        b = int(batch.op.shape[0])
        u_width = self._u_width_of(q.unbounded)
        key = (
            "cost_profile", cfg.backend, cfg.interpret, cfg.mesh,
            cfg.data_axes, cfg.model_axis, self.cap, u_width, b,
            q.unbounded, cfg.pred_index_layout, cfg.donate_batch,
        )
        prof = eng._programs.get(key)
        if prof is None:
            fn, args = self._args(batch, self.cap, q.unbounded)
            geometry = {
                "lanes": b,
                "cap": self.cap,
                "u_width": u_width,
                "unbounded": q.unbounded,
                "backend": cfg.backend,
                "sharded": cfg.mesh is not None,
            }
            prof = obs_cost.profile_jit(fn, args, geometry)
            eng._programs[key] = prof
        return dict(prof)


# ---------------------------------------------------------------------------
# host-side engine: compile queries against one store
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Engine:
    """The one query entry point: ``Engine.compile(query, config) -> Plan``.

    Queries are ``core.query`` descriptions (``TriplePatternQ`` / ``JoinQ``
    / ``BgpQ`` / ``ServeQ``); execution knobs travel ONLY inside a frozen
    :class:`ExecConfig`.  Compiled plans are cached on
    ``(shape_key(query), config)`` — two queries of the same shape share
    programs, caps, and growth state — and every keyed + unbounded pattern,
    join side-list, and BGP step rides the same cached ``serve_step``
    programs underneath.

    ``cap`` / ``backend`` / ``use_pred_index`` are legacy construction
    knobs (pre-ExecConfig); they seed :attr:`default_config` and feed the
    deprecation shims :meth:`pattern` and :meth:`join`.
    """

    store: K2TriplesStore
    cap: int = 4096
    backend: str | None = None
    use_pred_index: bool = True
    config: ExecConfig | None = None
    _plan_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _programs: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _sharded: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    _stats: dict = dataclasses.field(
        default_factory=lambda: {"hits": 0, "misses": 0, "denied": 0},
        repr=False, compare=False,
    )
    _env_cfg: ExecConfig | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # store epoch the caches were built against; a DynamicStore bumps its
    # epoch on compaction swap and the caches (plans, programs, sharded
    # forests) all close over the old forest/meta, so they are dropped
    # wholesale at the next compile
    _built_epoch: int = dataclasses.field(default=-1, repr=False, compare=False)

    @property
    def meta(self) -> K2Meta:
        return self.store.meta

    @property
    def store_epoch(self) -> int:
        """Compaction epoch of a dynamic store (0 for a static one)."""
        return getattr(self.store, "epoch", 0)

    def _check_epoch(self, epoch: int) -> None:
        cur = self.store_epoch
        if epoch != cur:
            raise qapi.StaleEpoch(
                f"plan compiled at store epoch {epoch}, store is now at "
                f"{cur} (compacted); recompile"
            )

    def dynamic_view(self):
        """The delta read view for this dispatch, or ``None`` when the
        store is static (or the delta is empty) — the static fast path."""
        return dyn.view_of(self.store)

    @property
    def forest(self) -> K2Forest:
        return self.store.forest

    @property
    def default_config(self) -> ExecConfig:
        """Engine-level default: the explicit ``config`` if given, else the
        one-time ``ExecConfig.from_env()`` snapshot overlaid with the
        legacy ``cap``/``backend``/``use_pred_index`` fields."""
        if self.config is not None:
            return self.config.resolved()
        if self._env_cfg is None:
            self._env_cfg = ExecConfig.from_env()
        cfg = self._env_cfg.replace(
            cap=self.cap, use_pred_index=self.use_pred_index
        )
        if self.backend is not None:
            cfg = cfg.replace(backend=self.backend)
        return cfg.resolved()

    @property
    def plan_cache_stats(self) -> dict:
        return dict(self._stats, size=len(self._plan_cache))

    # -- compile -------------------------------------------------------

    def compile(self, q, config: ExecConfig | None = None, *, admit=None) -> Plan:
        """Lower ``q`` under ``config`` (default :attr:`default_config`).

        Plans are cached on ``(shape_key, config)``: the constants inside
        ``q`` are runtime inputs, so compiling a second query of the same
        shape is a cache hit.

        ``admit`` is the plan-cache admission hook: a callable invoked with
        the cache key ONLY on a miss; returning falsy raises
        :class:`~repro.core.query.AdmissionError` instead of compiling.
        Hits bypass it entirely — admission charges the expensive event
        (a new compiled executor), never the reuse of a shared one.  The
        multi-tenant broker uses this to budget per-tenant recompiles.
        """
        cfg = (config or self.default_config).resolved()
        cur = self.store_epoch
        if self._built_epoch != cur:
            # post-compaction: every cached executor/program closes over the
            # old epoch's forest+meta — invalidate them all before compiling
            self._plan_cache.clear()
            self._programs.clear()
            self._sharded.clear()
            self._built_epoch = cur
        self._validate(q, cfg)
        key = (qapi.shape_key(q), cfg)
        t, m = obs.STATE.tracer, obs.STATE.metrics
        ex = self._plan_cache.get(key)
        if ex is None:
            if admit is not None and not admit(key):
                self._stats["denied"] += 1
                if m is not None:
                    m.counter("engine.plan_cache.denied").inc()
                if t is not None:
                    t.instant("engine.admission_denied", shape=str(key[0]))
                raise qapi.AdmissionError(
                    f"plan-cache admission denied for {key[0]!r}"
                )
            self._stats["misses"] += 1
            if m is not None:
                m.counter("engine.plan_cache.misses").inc()
            if t is not None:
                with t.span("engine.compile", cat="engine",
                            shape=str(key[0]), backend=cfg.backend,
                            cap=cfg.cap, hit=False):
                    ex = self._build_executor(q, cfg)
            else:
                ex = self._build_executor(q, cfg)
            self._plan_cache[key] = ex
        else:
            self._stats["hits"] += 1
            if m is not None:
                m.counter("engine.plan_cache.hits").inc()
        return Plan(q, cfg, ex)

    def _validate(self, q, cfg: ExecConfig):
        if isinstance(q, TriplePatternQ):
            named = [t for t in (q.s, q.p, q.o) if isinstance(t, str)]
            if len(named) != len(set(named)):
                raise ValueError(
                    "a variable repeated inside one pattern needs join "
                    f"semantics; wrap it in BgpQ: {q!r}"
                )
        # a mesh request must never be silently dropped: only the serve-IR
        # shapes are sharded today.  Pair enumeration / dump (range kernel),
        # join rebinds D-F, and the BGP host loop's enumeration steps run
        # on the unsharded forest, so reject the combination loudly.
        if cfg.mesh is not None:
            if isinstance(q, TriplePatternQ) and q.bound in (
                (False, True, False), (False, False, False)
            ):
                raise ValueError(
                    "pair-enumeration/dump plans are not sharded; drop "
                    "ExecConfig.mesh for this shape"
                )
            if isinstance(q, JoinQ) and q.category in "DEF":
                raise ValueError(
                    f"join category {q.category} (fused scan->rebind) is "
                    "not sharded; drop ExecConfig.mesh"
                )
            if isinstance(q, (BgpQ, SelectQ)):
                raise ValueError(
                    "BGP/SELECT plans are not sharded (enumeration steps "
                    "run single-device); drop ExecConfig.mesh"
                )
        if isinstance(q, BgpQ):
            names = {v for tp in q.patterns for v in tp.variables}
            if any(v.startswith(_ANON) for v in names):
                raise ValueError(
                    f"variable names starting with {_ANON!r} are reserved "
                    "for anonymous (None) positions"
                )
            if not names and any(
                qapi.is_var(t)
                for tp in q.patterns for t in (tp.s, tp.p, tp.o)
            ):
                raise ValueError(
                    "a BGP whose variables are all anonymous has no "
                    "projectable columns; name at least one variable "
                    "(or use a TriplePatternQ check shape)"
                )
        if isinstance(q, SelectQ):
            blocks = (q.where,) + q.optional + q.union
            names = {v for blk in blocks for tp in blk for v in tp.variables}
            reserved = [v for v in names if v.startswith(algebra.INTERNAL)]
            if q.select:
                reserved += [
                    v for v in q.select if v.startswith(algebra.INTERNAL)
                ]
            if reserved:
                raise ValueError(
                    f"variable names starting with {algebra.INTERNAL!r} "
                    f"are reserved for internal columns: {reserved!r}"
                )
            if not names:
                raise ValueError(
                    "a SELECT whose variables are all anonymous has no "
                    "projectable columns; name at least one variable"
                )
            for ex in q.filter:  # raises TypeError on non-expressions
                algebra.expr_vars(ex)
        if (
            isinstance(q, ServeQ)
            and q.unbounded
            and cfg.u_width_quantile < 1.0
            and cfg.use_pred_index
            and self.store.pred_index is not None
        ):
            raise ValueError(
                "quantile-sized unbounded lanes need the decode-level sweep "
                "fallback; raw ServeQ plans require u_width_quantile=1.0 "
                "(use TriplePatternQ plans for quantile sizing)"
            )

    def _build_executor(self, q, cfg: ExecConfig):
        if isinstance(q, TriplePatternQ):
            return _PatternExec(self, cfg)
        if isinstance(q, JoinQ):
            return _JoinExec(self, cfg)
        if isinstance(q, BgpQ):
            return _BgpExec(self, cfg)
        if isinstance(q, SelectQ):
            return _SelectExec(self, cfg)
        if isinstance(q, ServeQ):
            return _ServeExec(self, cfg)
        raise TypeError(f"not a Query: {q!r}")

    # -- shared compiled-program machinery ------------------------------

    def _u_width(self, cfg: ExecConfig) -> int:
        bi = self.store.pred_index
        if cfg.u_width_quantile >= 1.0:
            return max(bi.meta.max_degree, 1)
        # the quantile pass walks the whole host CSR — memoize per quantile
        # so unbounded serve calls don't pay it repeatedly
        key = ("u_width", cfg.u_width_quantile)
        w = self._programs.get(key)
        if w is None:
            w = max(predindex.quantile_u_width(bi, cfg.u_width_quantile), 1)
            self._programs[key] = w
        return w

    def _forest_for(self, cfg: ExecConfig) -> K2Forest:
        if cfg.mesh is None:
            return self.forest
        key = (cfg.mesh, cfg.model_axis)
        f = self._sharded.get(key)
        if f is None:
            mp = int(cfg.mesh.shape[cfg.model_axis])
            f = shard_forest(
                pad_preds(self.forest, mp), cfg.mesh, cfg.model_axis
            )
            self._sharded[key] = f
        return f

    def _program(self, cfg: ExecConfig, cap: int, u_width: int, with_index: bool):
        """One cached compiled serve program per distinct geometry; shared
        by every executor of this engine."""
        donate = cfg.donate_batch and cfg.mesh is None
        key = (
            cfg.backend, cfg.interpret, cfg.mesh, cfg.data_axes,
            cfg.model_axis, cap, u_width, with_index,
            cfg.pred_index_layout, donate,
        )
        fn = self._programs.get(key)
        if fn is None:
            m = obs.STATE.metrics
            if m is not None:
                m.counter("engine.programs_built").inc()
            with obs.span("engine.program_build", cat="engine",
                          cap=cap, u_width=u_width, with_index=with_index,
                          sharded=cfg.mesh is not None):
                pmeta = (
                    self.store.pred_index.select(cfg.pred_index_layout)[1]
                    if with_index else None
                )
                if cfg.mesh is None:
                    fn = make_serve_step(
                        self.meta, cap, backend=cfg, pmeta=pmeta,
                        u_width=u_width, donate=donate,
                    )
                else:
                    fn = make_sharded_serve_step(
                        self.meta, cfg.mesh, cap, data_axes=cfg.data_axes,
                        model_axis=cfg.model_axis, backend=cfg, pmeta=pmeta,
                        u_width=u_width,
                    )
            self._programs[key] = fn
        return fn

    def _pad_b(self, b: int, cfg: ExecConfig) -> int:
        """Pad host batches to pow2 buckets (bounds retraces to log2 sizes);
        sharded programs additionally need data-axis divisibility."""
        n = 8
        while n < b:
            n <<= 1
        if cfg.mesh is not None:
            d = int(np.prod([cfg.mesh.shape[a] for a in cfg.data_axes]))
            n = max(n, d)
            n = ((n + d - 1) // d) * d
        return n

    def _run_lanes(
        self, cfg: ExecConfig, cap: int, ops_a, s, p, o,
        *, u_width: int = 0, with_index: bool = False,
    ) -> ServeResult:
        """Run serve-IR lanes through the cached program for this geometry.

        Lanes are padded to a pow2 bucket with dead (op=-1) entries —
        masked to zero output by ``_serve_local`` — and sliced back.  This
        is the ONE dispatch every pattern plan, join side-list, and BGP
        step shares.
        """
        b = int(np.shape(ops_a)[0])
        n = self._pad_b(b, cfg)
        t = obs.STATE.tracer
        if t is not None:
            with t.span("plan.lanes", cat="plan", b=b, padded=n, cap=cap,
                        u_width=u_width, sharded=cfg.mesh is not None):
                return self._run_lanes_inner(
                    cfg, cap, ops_a, s, p, o, b=b, n=n,
                    u_width=u_width, with_index=with_index,
                )
        return self._run_lanes_inner(
            cfg, cap, ops_a, s, p, o, b=b, n=n,
            u_width=u_width, with_index=with_index,
        )

    def _run_lanes_inner(
        self, cfg: ExecConfig, cap: int, ops_a, s, p, o,
        *, b: int, n: int, u_width: int, with_index: bool,
    ) -> ServeResult:
        view = self.dynamic_view()
        ops_run = (
            view.sanitize_ops(ops_a, s, p, o) if view is not None else ops_a
        )

        def pad(a, fill):
            out = np.full(n, fill, np.int32)
            out[:b] = np.asarray(a, np.int64)
            return out

        qb = ServeBatch(
            op=jnp.asarray(pad(ops_run, -1)),
            s=jnp.asarray(pad(s, 0)),
            p=jnp.asarray(pad(p, 0)),
            o=jnp.asarray(pad(o, 0)),
        )
        f = self._forest_for(cfg)
        fn = self._program(cfg, cap, u_width, with_index)
        if with_index:
            dev = self.store.pred_index.select(cfg.pred_index_layout)[0]
            r = fn(f, qb, dev)
        elif u_width > 0 and cfg.mesh is None:
            r = fn(f, qb, None)
        else:
            r = fn(f, qb)
        r = jax.tree.map(lambda a: a[:b], r)
        if view is not None:
            # the delta lane: fold the snapshot into the static results on
            # the host — subtract tombstones, union inserts, widen caps so
            # the delta can never cause a false overflow
            r = view.merge_lanes(ops_a, s, p, o, jax.tree.map(np.asarray, r))
        return r

    def _lanes_runner(self, cfg: ExecConfig, cap: int):
        """Bound-pred serve-lane callable handed to the BGP optimizer."""
        return lambda ops_a, s, p, o: self._run_lanes(cfg, cap, ops_a, s, p, o)

    # -- deprecation shims ----------------------------------------------

    def pattern(self, s: int | None, p: int | None, o: int | None):
        """DEPRECATED: build a ``TriplePatternQ`` and ``compile`` it.

        Kept as a thin shim over the plan pipeline — identical results,
        plus the CapPolicy growth the old path lacked.
        """
        warnings.warn(
            "Engine.pattern is deprecated; use "
            "Engine.compile(TriplePatternQ(s, p, o), ExecConfig(...))()",
            DeprecationWarning, stacklevel=2,
        )
        q = TriplePatternQ(s or None, p or None, o or None)
        return self.compile(q)()

    def join(self, category: str, **kw):
        """DEPRECATED: build a ``JoinQ`` and ``compile`` it."""
        warnings.warn(
            "Engine.join is deprecated; use "
            "Engine.compile(JoinQ(category, ...), ExecConfig(...))()",
            DeprecationWarning, stacklevel=2,
        )
        cap = kw.pop("cap", self.cap)
        cap_y = kw.pop("cap_y", 256)
        backend = kw.pop("backend", None)  # legacy per-call override
        q = JoinQ(category=category, **kw)
        cfg = self.default_config.replace(cap=cap, cap_y=cap_y)
        if backend is not None:
            cfg = cfg.replace(backend=backend)
        return self.compile(q, cfg)()


def _pairs_to_dict(r: joins.JoinPairs) -> dict[int, np.ndarray]:
    xs, xv = np.asarray(r.x_ids), np.asarray(r.x_valid)
    ys, yv = np.asarray(r.y_ids), np.asarray(r.y_valid)
    out = {}
    for i in range(xs.shape[0]):
        if xv[i] and yv[i].any():
            out[int(xs[i])] = ys[i][yv[i]]
    return out


def _pairs_to_dict_pred(r: joins.JoinPairs) -> dict[int, dict[int, np.ndarray]]:
    out: dict[int, dict[int, np.ndarray]] = {}
    xs, xv = np.asarray(r.x_ids), np.asarray(r.x_valid)
    ys, yv = np.asarray(r.y_ids), np.asarray(r.y_valid)
    for p in range(xs.shape[0]):
        d = {}
        for i in range(xs.shape[1]):
            if xv[p, i] and yv[p, i].any():
                d[int(xs[p, i])] = ys[p, i][yv[p, i]]
        if d:
            out[p + 1] = d
    return out
