"""Pallas TPU kernels: batched k²-tree row/col scans, and the fused scan→rebind.

A query lane carries (pred, key, axis): ``axis == 0`` scans a row (direct
neighbors, (S,P,?O)), ``axis == 1`` a column (reverse neighbors, (?S,P,O)) —
the mixed-batch contract of ``core/k2forest.scan_batch_mixed``.  A lane whose
``pred`` is not a row of the arena is dead: it answers empty and reads
nothing (``scan_batch_mixed`` sends every id outside ``[0, n_preds)`` here
as -1).

The forest arenas stay in HBM.  At the geonames Table-1 scale the padded
forest is 176 MB (``t_words`` and ``t_rank`` are (24, 868,992) each, in
whole (8, 128) tiles as the store builds them), far beyond VMEM, so the traversal runs on the scalar core and reads words
through the SMEM tile cache of ``kernels/tiles.py``.

The traversal is the level-synchronous frontier BFS of ``core/k2tree``,
statically unrolled over the tree height.  The frontier lives in two SMEM
buffers; per level, each frontier node in order does

    j     = rank1(T, pos) - ones_before[pred, lvl]
    child = level_start[pred, lvl + 1] + j * k² + (free-axis offset of ch)
    keep the children whose bit is set, while fewer than ``cap`` are kept

which is the jnp reference's compaction: the survivors are the first ``cap``
set children in free-axis order, and ``overflow`` latches when a level holds
more.  A level stops at its first overflow, since no later child survives.

Outputs per lane: ``ids[cap]`` (free-axis coordinates, ascending, zero past
``count``), ``valid`` (the prefix ``i < count``), ``count`` and ``overflow``.
Bit-exact against ``ref.k2_scan_ref`` and ``k2forest.scan_batch_mixed``
(jnp backend), checked in interpret mode by ``tests/test_k2_scan.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.k2tree import K2Meta
from repro.kernels import tiles
from repro.kernels.tiles import Record, Tiles

_U32, _I32 = jnp.uint32, jnp.int32


class Arena(NamedTuple):
    """The forest's tile slots inside a kernel, and its shape."""

    rank: Tiles  # (t_words, t_rank) at the rank position
    tbit: Tiles  # t_words at a child position
    lbit: Tiles  # l_words at a child position
    tab: Tiles  # (ones_before, level_start) of the lane's predicate
    wt: int
    wl: int
    rows: int  # predicate rows of the arena


def arena_scratch() -> list:
    return [
        *tiles.tile_bufs(_U32, _I32, _U32, _U32, _I32, _I32),
        pltpu.SMEM((4,), _I32),  # tile tags
        pltpu.SemaphoreType.DMA((6,)),
    ]


def arena(refs, scratch) -> Arena:
    """Bind the five arena refs and :func:`arena_scratch` into tile slots."""
    tw, tr, lw, ob, ls = refs
    bw, br, bt, bl, bo, bs, tags, sems = scratch
    a = Arena(
        rank=Tiles((tw, tr), (bw, br), tags, 0, sems.at[pl.ds(0, 2)]),
        tbit=Tiles((tw,), (bt,), tags, 1, sems.at[pl.ds(2, 1)]),
        lbit=Tiles((lw,), (bl,), tags, 2, sems.at[pl.ds(3, 1)]),
        tab=Tiles((ob, ls), (bo, bs), tags, 3, sems.at[pl.ds(4, 2)]),
        wt=tw.shape[1], wl=lw.shape[1], rows=tw.shape[0],
    )
    for t in (a.rank, a.tbit, a.lbit, a.tab):
        t.reset()
    return a


def bit_at(t: Tiles, width: int, pred, pos):
    widx = jnp.clip(pos >> 5, 0, width - 1)
    (word,) = t.get(pred, widx)
    return ((word >> (pos & 31).astype(_U32)) & _U32(1)).astype(_I32)


def rank_at(a: Arena, pred, pos):
    widx = jnp.clip(pos >> 5, 0, a.wt - 1)
    word, base = a.rank.get(pred, widx)
    mask = (_U32(1) << (pos & 31).astype(_U32)) - _U32(1)
    return base + tiles.popcount(word & mask)


def frontier_loop(n, step):
    """Run ``step(i, m, ovf) -> (m, ovf)`` over frontier nodes ``i < n``,
    stopping at the first overflow.  Returns the final ``(m, ovf)``."""

    def cond(c):
        i, _, ovf = c
        return (i < n) & (ovf == 0)

    def body(c):
        i, m, ovf = c
        m, ovf = step(i, m, ovf)
        return i + 1, m, ovf

    _, m, ovf = jax.lax.while_loop(cond, body, (_I32(0), _I32(0), _I32(0)))
    return m, ovf


def append(cap: int, bit, m, ovf, writes):
    """Keep a child whose ``bit`` is set when there is room (``writes(m)``
    stores it at slot m); latch overflow when there is none."""
    take = (bit == 1) & (m < cap)

    @pl.when(take)
    def _():
        writes(m)

    ovf = ovf | ((bit == 1) & (m >= cap)).astype(_I32)
    return m + take.astype(_I32), ovf


GEO_ROWS = 2  # per-level subside, and the lane's key digit


def geo_scratch():
    return pltpu.SMEM((GEO_ROWS, tiles.TC), _I32)


def geo_init(meta: K2Meta, geo) -> None:
    """Per-level tree geometry into SMEM, so levels can run in a loop."""
    for lvl, sub in enumerate(meta.subsides):
        geo[0, lvl] = _I32(sub)


def descend(meta: K2Meta, a: Arena, fpos, geo, pred, n, level):
    """Levels 1..H-1 of a frontier BFS.

    Level ``lvl + 1`` is built from the ``n`` nodes of ``fpos[lvl % 2]``:
    node i's children start at ``cb0 = level_start + (rank1(T, pos) -
    ones_before) * k²``.  ``level(lvl, k) -> (offs, node)`` gives the
    offsets from ``cb0`` of the children a node reads, ascending and each
    below k², and ``node(i, cpos, bits, m, ovf) -> (m, ovf)``, which
    appends the set ones given their positions and bits in ``offs`` order.
    With k² ≤ 33 (the store builds k = 4 and 2) a node's child bits lie in
    one word and the next, read as one or two words rather than a tile
    lookup per child.  Levels run as one loop per run of equal arity ``k``
    (static, so a node's children unroll); the last (its bits are in L) runs
    after.  Returns ``(n, overflow)`` of the last level.
    """
    H, ks = meta.n_levels, meta.ks

    def expand(lvl, k, n, tc, wc):
        ob = a.tab.get(pred, lvl)[0]
        ls = a.tab.get(pred, lvl + 1)[1]
        src = lvl & 1
        if k * k > 33:
            raise ValueError(f"arity {k}: a node's child bits span more than "
                             "a word and the next")
        offs, node = level(lvl, k)
        span = offs[-1] - offs[0]

        def step(i, m, o):
            cb0 = ls + (rank_at(a, pred, fpos[src, i]) - ob) * (k * k)
            cpos = [cb0 + d for d in offs]
            wi = cpos[0] >> 5
            (w0,) = tc.get(pred, jnp.clip(wi, 0, wc - 1))
            w1 = jax.lax.cond(
                (cpos[0] & 31) + span >= 32,
                lambda: tc.get(pred, jnp.clip(wi + 1, 0, wc - 1))[0],
                lambda: w0)

            def bit(c):
                w = jnp.where((c >> 5) == wi, w0, w1)
                return ((w >> (c & 31).astype(_U32)) & _U32(1)).astype(_I32)

            return node(i, cpos, [bit(c) for c in cpos], m, o)

        return frontier_loop(n, step)

    ovf, lvl = _I32(0), 0
    while lvl < H - 2:  # runs of inner levels whose children have arity k
        k, end = ks[lvl + 1], lvl + 1
        while end < H - 2 and ks[end + 1] == k:
            end += 1

        def inner(l, c, k=k):
            m, o = expand(l, k, c[0], a.tbit, a.wt)
            return m, c[1] | o

        n, ovf = jax.lax.fori_loop(lvl, end, inner, (n, ovf))
        lvl = end
    if H >= 2:
        n, lo = expand(H - 2, ks[H - 1], n, a.lbit, a.wl)
        ovf = ovf | lo
    return n, ovf


def scan_traverse(meta: K2Meta, cap: int, a: Arena, fpos, fbase, geo, pred,
                  key, is_row):
    """One lane's row/col scan.  Returns ``(n, overflow)``; the ids are
    ``fbase[(H - 1) % 2, :n]``."""
    H, k0, sub0 = meta.n_levels, meta.ks[0], meta.subsides[0]
    rem = key
    for lvl, sub in enumerate(meta.subsides):
        geo[1, lvl] = tiles.fdiv(rem, sub)
        rem = tiles.fmod(rem, sub)

    t0, w0 = (a.lbit, a.wl) if H == 1 else (a.tbit, a.wt)
    d0 = geo[1, 0]

    def root(j, n):
        p0 = jnp.where(is_row, d0 * k0 + j, j * k0 + d0)
        fpos[0, n] = p0  # n <= j < cap; a clear bit leaves it overwritable
        fbase[0, n] = j * sub0
        return n + bit_at(t0, w0, pred, p0)

    n = jax.lax.fori_loop(0, min(k0, cap), root, _I32(0))

    def level(lvl, k):
        sub, d = geo[0, lvl + 1], geo[1, lvl + 1]
        src, dst = lvl & 1, (lvl + 1) & 1
        lo, stride = jnp.where(is_row, d * k, d), jnp.where(is_row, 1, k)

        def node(i, cpos, bits, m, o):
            base = fbase[src, i]
            for ch, (c, b) in enumerate(zip(cpos, bits)):

                def w(m, c=c, ch=ch):
                    fpos[dst, m] = c
                    fbase[dst, m] = base + ch * sub

                m, o = append(cap, b, m, o, w)
            return m, o

        return [lo + ch * stride for ch in range(k)], node

    n, ovf = descend(meta, a, fpos, geo, pred, n, level)
    return n, ovf | _I32(k0 > cap)


def run_lane(st, a: Arena, pred, fn):
    """``st[0], st[1] = fn()`` for a live lane (pred a row of the arena),
    else 0, 0."""
    st[0] = _I32(0)
    st[1] = _I32(0)

    @pl.when((pred >= 0) & (pred < a.rows))
    def _():
        n, ovf = fn()
        st[0] = n
        st[1] = ovf


def decode(out, q: int, cap: int):
    """Records -> ``(ids, valid, count, overflow)``: ids in words
    ``[0, cap)``, count and overflow at ``cap`` and ``cap + 1``."""
    flat = out.reshape(out.shape[0], -1)[:q]
    count = flat[:, cap]
    valid = jnp.arange(cap, dtype=_I32)[None, :] < count[:, None]
    return flat[:, :cap], valid, count, flat[:, cap + 1] != 0


def _qspec(bq: int):
    return pl.BlockSpec((bq,), lambda i: (i,), memory_space=pltpu.SMEM)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def lane_pad(qp: int, *vecs):
    """int32 lane vectors padded to ``qp`` lanes with dead (-1) entries."""
    out = []
    for v in vecs:
        v = jnp.asarray(v, _I32)
        out.append(jnp.pad(v, (0, qp - v.shape[0]), constant_values=-1))
    return out


@functools.partial(
    jax.jit, static_argnames=("meta", "cap", "interpret", "name")
)
def k2_scan(
    meta: K2Meta,
    preds: jax.Array,
    keys: jax.Array,
    axes: jax.Array,
    t_words: jax.Array,
    t_rank: jax.Array,
    l_words: jax.Array,
    ones_before: jax.Array,
    level_start: jax.Array,
    *,
    cap: int,
    interpret: bool = False,
    name: str = "k2_scan",
):
    """Batched mixed row/col scans over a K2Forest arena.

    Returns ``(ids, valid, count, overflow)`` with shapes
    ``(Q, cap) / (Q, cap) / (Q,) / (Q,)``.  ``name`` is the launch's name in
    the compiled program and in a profile (``%<name>.N``), so that callers
    that launch the kernel for different purposes can be told apart.
    """
    (q,) = preds.shape
    bq, qp = tiles.lane_blocks(q)
    arrs = tiles.tiled("k2_scan", t_words, t_rank, l_words, ones_before,
                       level_start)
    rows = tiles.rec_rows(cap + 2)
    par = (meta.n_levels - 1) % 2

    def kernel(preds_ref, keys_ref, axes_ref, tw, tr, lw, ob, ls, out_ref,
               *scratch):
        *ascr, fpos, fbase, geo, rbuf, hw, osem, st = scratch
        a = arena((tw, tr, lw, ob, ls), ascr)
        geo_init(meta, geo)
        rec = Record(rbuf, hw, osem.at[0])
        rec.clear()
        blk = pl.program_id(0)

        def lane(i, c):
            qi = blk * bq + i

            @pl.when(qi < q)
            def _():
                pred = preds_ref[i]
                run_lane(st, a, pred, lambda: scan_traverse(
                    meta, cap, a, fpos, fbase, geo, pred, keys_ref[i],
                    axes_ref[i] == 0,
                ))
                rec.fill(0, st[0], lambda j: fbase[par, j])
                rec.put(cap, st[0])
                rec.put(cap + 1, st[1])
                rec.flush(out_ref, qi)

            return c

        jax.lax.fori_loop(0, bq, lane, 0)

    p, k, x = lane_pad(qp, preds, keys, axes)
    out = pl.pallas_call(
        kernel,
        grid=(qp // bq,),
        in_specs=[_qspec(bq)] * 3 + [tiles.ANY] * 5,
        out_specs=tiles.ANY,
        out_shape=jax.ShapeDtypeStruct((qp, rows, tiles.TC), _I32),
        scratch_shapes=[
            *arena_scratch(),
            pltpu.SMEM((2, cap), _I32), pltpu.SMEM((2, cap), _I32),
            geo_scratch(),
            pltpu.SMEM((rows, tiles.TC), _I32), pltpu.SMEM((1,), _I32),
            pltpu.SemaphoreType.DMA((1,)), pltpu.SMEM((2,), _I32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name=name,
    )(p, k, x, *arrs)
    return decode(out, q, cap)


# ---------------------------------------------------------------------------
# fused scan → rebind (join categories D–F: resolve ?X, re-bind into pattern 2)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("meta", "cap_x", "cap_y", "interpret")
)
def k2_scan_rebind(
    meta: K2Meta,
    preds1: jax.Array,
    keys1: jax.Array,
    axes1: jax.Array,
    preds2: jax.Array,
    axes2: jax.Array,
    t_words: jax.Array,
    t_rank: jax.Array,
    l_words: jax.Array,
    ones_before: jax.Array,
    level_start: jax.Array,
    *,
    cap_x: int,
    cap_y: int,
    interpret: bool = False,
):
    """Fused X-resolution + re-bind: two chained traversals, one kernel.

    Per query lane: scan (preds1, keys1, axes1) into a ``cap_x`` side-list of
    ?X candidates, then — without leaving the kernel — run ``cap_x``
    pattern-2 scans (preds2, X, axes2) at ``cap_y`` each; a dead X slot
    scans key 0, as the jnp composition does.  Returns ``(x_ids, x_valid,
    x_count, x_overflow, y_ids, y_valid, y_count, y_overflow)`` shaped
    ``(Q,cap_x) ×2, (Q,) ×2, (Q,cap_x,cap_y) ×2, (Q,cap_x) ×2``.
    """
    (q,) = preds1.shape
    bq, qp = tiles.lane_blocks(q)
    arrs = tiles.tiled("k2_scan_rebind", t_words, t_rank, l_words,
                       ones_before, level_start)
    cmax = max(cap_x, cap_y)
    rx, ry = tiles.rec_rows(cap_x + 2), tiles.rec_rows(cap_y + 2)
    par = (meta.n_levels - 1) % 2

    def kernel(p1_ref, k1_ref, a1_ref, p2_ref, a2_ref, tw, tr, lw, ob, ls,
               xo_ref, yo_ref, *scratch):
        *ascr, fpos, fbase, geo, xs, xbuf, ybuf, xhw, yhw, osem, st = scratch
        a = arena((tw, tr, lw, ob, ls), ascr)
        geo_init(meta, geo)
        xrec = Record(xbuf, xhw, osem.at[0])
        yrec = Record(ybuf, yhw, osem.at[1])
        xrec.clear()
        yrec.clear()
        blk = pl.program_id(0)

        def lane(i, c):
            qi = blk * bq + i

            @pl.when(qi < q)
            def _():
                p1, p2 = p1_ref[i], p2_ref[i]
                row2 = a2_ref[i] == 0
                run_lane(st, a, p1, lambda: scan_traverse(
                    meta, cap_x, a, fpos, fbase, geo, p1, k1_ref[i],
                    a1_ref[i] == 0,
                ))
                nx = st[0]

                def keep(j, c):
                    xs[j] = fbase[par, j]
                    return c

                jax.lax.fori_loop(0, nx, keep, 0)
                xrec.fill(0, nx, lambda j: xs[j])
                xrec.put(cap_x, nx)
                xrec.put(cap_x + 1, st[1])
                xrec.flush(xo_ref, qi)

                def rebind(xi, c):
                    key2 = jnp.where(xi < nx, xs[jnp.minimum(xi, cap_x - 1)], 0)
                    run_lane(st, a, p2, lambda: scan_traverse(
                        meta, cap_y, a, fpos, fbase, geo, p2, key2, row2,
                    ))
                    yrec.fill(0, st[0], lambda j: fbase[par, j])
                    yrec.put(cap_y, st[0])
                    yrec.put(cap_y + 1, st[1])
                    yrec.flush(yo_ref, qi * cap_x + xi)
                    return c

                jax.lax.fori_loop(0, cap_x, rebind, 0)

            return c

        jax.lax.fori_loop(0, bq, lane, 0)

    vecs = lane_pad(qp, preds1, keys1, axes1, preds2, axes2)
    xo, yo = pl.pallas_call(
        kernel,
        grid=(qp // bq,),
        in_specs=[_qspec(bq)] * 5 + [tiles.ANY] * 5,
        out_specs=(tiles.ANY, tiles.ANY),
        out_shape=(
            jax.ShapeDtypeStruct((qp, rx, tiles.TC), _I32),
            jax.ShapeDtypeStruct((qp * cap_x, ry, tiles.TC), _I32),
        ),
        scratch_shapes=[
            *arena_scratch(),
            pltpu.SMEM((2, cmax), _I32), pltpu.SMEM((2, cmax), _I32),
            geo_scratch(), pltpu.SMEM((cap_x,), _I32),
            pltpu.SMEM((rx, tiles.TC), _I32), pltpu.SMEM((ry, tiles.TC), _I32),
            pltpu.SMEM((1,), _I32), pltpu.SMEM((1,), _I32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), _I32),
        ],
        compiler_params=_params(),
        interpret=interpret,
    )(*vecs, *arrs)
    x_ids, x_valid, x_count, x_ovf = decode(xo, q, cap_x)
    y_ids, y_valid, y_count, y_ovf = decode(yo, q * cap_x, cap_y)
    return (
        x_ids, x_valid, x_count, x_ovf,
        y_ids.reshape(q, cap_x, cap_y), y_valid.reshape(q, cap_x, cap_y),
        y_count.reshape(q, cap_x), y_ovf.reshape(q, cap_x),
    )
