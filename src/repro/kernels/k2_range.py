"""Pallas TPU kernel: batched k²-tree range scans (the (?S,P,?O) path).

Pair enumeration over whole matrices: one query lane = one predicate's tree,
and the traversal walks EVERY 1-node instead of a single row/column slab.
Each lane carries a frontier of up to ``cap`` nodes as ``(pos, rbase,
cbase)`` — tree bit position plus the node's row/column submatrix origin —
and per level expands by the full radix ``k²_{l+1}`` (vs the scan kernel's
``k`` free-axis children), so results come out in Morton (level-order)
sequence: the order the paper's DFS would emit.  Like ``k2_scan`` it runs
on the scalar core over the HBM-resident arena (``kernels/tiles.py``), with
the frontier in SMEM.

Level 0 materializes ALL ``k0²`` root children, tests their bits, and only
then compacts into the ``cap`` frontier — overflow latches only when more
than ``cap`` root children are actually occupied.  (The original jnp
traversal truncated the root radix to ``cap`` *before* the bit test, so a
sparse matrix under a large root radix both falsely reported overflow and
silently dropped candidates; ``core/k2forest.range_scan`` is fixed to the
same compact-after-test semantics and is the differential reference.)

Outputs per lane: ``rows[cap] / cols[cap]`` (Morton-ordered pair
coordinates), ``valid[cap]``, ``count``, ``overflow``.  Bit-exact against
``ref.k2_range_ref`` and ``k2forest.range_scan_batch(backend="jnp")``;
validated with ``interpret=True`` against the numpy Morton-order oracle in
``tests/test_k2_range.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.k2tree import K2Meta
from repro.kernels import tiles
from repro.kernels.k2_scan import (
    append, arena, arena_scratch, bit_at, descend,
    geo_init, geo_scratch, lane_pad, run_lane,
)
from repro.kernels.tiles import Record

_I32 = jnp.int32


def range_traverse(meta: K2Meta, cap: int, a, fpos, frb, fcb, geo, pred):
    """One predicate's full-matrix enumeration.  Returns ``(n, overflow)``;
    the pairs are ``(frb, fcb)[(H - 1) % 2, :n]``."""
    H, k0, r0, sub0 = meta.n_levels, meta.ks[0], meta.radices[0], meta.subsides[0]
    t0, w0 = (a.lbit, a.wl) if H == 1 else (a.tbit, a.wt)

    # level 0: every root child, bit-tested before the frontier is capped
    def root(d, c):
        m, o = c

        def w(m):
            fpos[0, m] = d
            frb[0, m] = (d // k0) * sub0
            fcb[0, m] = (d % k0) * sub0

        return append(cap, bit_at(t0, w0, pred, d), m, o, w)

    n, ovf = jax.lax.fori_loop(0, r0, root, (_I32(0), _I32(0)))

    def level(lvl, k):
        sub = geo[0, lvl + 1]
        src, dst = lvl & 1, (lvl + 1) & 1

        def node(i, cpos, bits, m, o):
            rb, cb = frb[src, i], fcb[src, i]
            for d, (c, bit) in enumerate(zip(cpos, bits)):

                def w(m, c=c, d=d):
                    fpos[dst, m] = c
                    frb[dst, m] = rb + (d // k) * sub
                    fcb[dst, m] = cb + (d % k) * sub

                m, o = append(cap, bit, m, o, w)
            return m, o

        return list(range(k * k)), node

    n, lo = descend(meta, a, fpos, geo, pred, n, level)
    return n, ovf | lo


@functools.partial(
    jax.jit, static_argnames=("meta", "cap", "interpret")
)
def k2_range(
    meta: K2Meta,
    preds: jax.Array,
    t_words: jax.Array,
    t_rank: jax.Array,
    l_words: jax.Array,
    ones_before: jax.Array,
    level_start: jax.Array,
    *,
    cap: int,
    interpret: bool = False,
):
    """Batched full-matrix pair enumeration over a K2Forest arena.

    Returns ``(rows, cols, valid, count, overflow)`` with shapes
    ``(Q, cap) ×3, (Q,) ×2``.
    """
    (q,) = preds.shape
    bq, qp = tiles.lane_blocks(q)
    arrs = tiles.tiled("k2_range", t_words, t_rank, l_words, ones_before,
                       level_start)
    rows = tiles.rec_rows(2 * cap + 2)
    par = (meta.n_levels - 1) % 2

    def kernel(preds_ref, tw, tr, lw, ob, ls, out_ref, *scratch):
        *ascr, fpos, frb, fcb, geo, rbuf, hw, osem, st = scratch
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
                run_lane(st, a, pred, lambda: range_traverse(
                    meta, cap, a, fpos, frb, fcb, geo, pred,
                ))
                n = st[0]
                rec.fill(0, n, lambda j: frb[par, j], mark=0)
                rec.fill(cap, n, lambda j: fcb[par, j], mark=1)
                rec.put(2 * cap, n)
                rec.put(2 * cap + 1, st[1])
                rec.flush(out_ref, qi)

            return c

        jax.lax.fori_loop(0, bq, lane, 0)

    (p,) = lane_pad(qp, preds)
    out = pl.pallas_call(
        kernel,
        grid=(qp // bq,),
        in_specs=[pl.BlockSpec((bq,), lambda i: (i,), memory_space=pltpu.SMEM)]
        + [tiles.ANY] * 5,
        out_specs=tiles.ANY,
        out_shape=jax.ShapeDtypeStruct((qp, rows, tiles.TC), _I32),
        scratch_shapes=[
            *arena_scratch(),
            *(pltpu.SMEM((2, cap), _I32) for _ in range(3)), geo_scratch(),
            pltpu.SMEM((rows, tiles.TC), _I32), pltpu.SMEM((2,), _I32),
            pltpu.SemaphoreType.DMA((1,)), pltpu.SMEM((2,), _I32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(p, *arrs)
    flat = out.reshape(qp, -1)[:q]
    count = flat[:, 2 * cap]
    valid = jnp.arange(cap, dtype=_I32)[None, :] < count[:, None]
    return (flat[:, :cap], flat[:, cap: 2 * cap], valid, count,
            flat[:, 2 * cap + 1] != 0)
