"""Pallas TPU kernel: ragged candidate-predicate gather (the SP/OP index read).

The pruned unbounded-``?P`` path (``core/predindex.scan_pruned_batch``) first
expands every query into its candidate predicate list — a ragged CSR gather
that this kernel phrases as a fixed-shape ``(BQ, L)`` launch layout: lane
``(q, j)`` holds the j-th predicate of query q's entity row, ready to feed
the flat ``(query, pred)`` grid of the batched ``k2_scan`` kernel.

The index arena stays in HBM and each lane is decoded on the scalar core,
reading words through the SMEM tile cache of ``kernels/tiles.py`` (the
Mosaic compiler lowers no dynamic vector gather from an HBM arena):

    start  = offsets[row]            deg = offsets[row + 1] - start
    elem   = start + j                              (j = 0 .. L-1)
    word   = words[(elem * bpp) >> 2]               (1-D dynamic gather)
    pred   = (word >> (8 * ((elem * bpp) & 3))) & ((1 << 8*bpp) - 1)

``bytes_per_pred`` ∈ {1, 2, 4} divides the word size, so an entry never
straddles a word.  Outputs follow the ``QueryResult`` contract: ``ids``
(0-based predicate ids, ascending — the lists are stored sorted), prefix
``valid`` mask, ``count`` = min(deg, L), ``overflow`` = deg > L.  Bit-exact
against ``ref.pred_gather_ref`` and ``predindex._gather_traced``
(tests/test_pred_gather.py).

``pred_gather_dac`` is the same launch layout over the DAC(b=8) layout
(``predindex`` ``layout="dac"``), decoding the compressed index entirely
on device:

    1. row pointers: ``start = anchors[row / RB] + Σ_{k < row mod RB}
       deg[k]`` — the packed ``deg_width``-bit degrees of one block span
       exactly 4 uint32 words, so the sum is a statically unrolled masked
       SWAR loop; ``deg`` itself is one more gather + shift + mask.
    2. chunk decode: lane j reads level-0 byte ``start + j``; while the
       level's continuation flag is set, the flag's in-level rank
       (``frank[word] + popcount(word & below)``) is the lane's position
       in the next level's byte stream, whose chunk ors in at bits 8·l.
    3. gaps → ids: a running sum over the lane's gaps turns them back
       into ascending 0-based predicate ids (first gap is id+1, so the
       running sum minus 1).

Bit-exact against ``ref.pred_gather_dac_ref`` (vectorized jnp with
``jnp.cumsum`` — an independent implementation) and the fixed-width
baseline on the same store (tests/test_pred_gather.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles
from repro.kernels.k2_scan import decode, lane_pad
from repro.kernels.tiles import Record, Tiles

_U32, _I32 = jnp.uint32, jnp.int32


def _call(kernel, rows, arrays, dtypes, *, cap, interpret):
    """Launch a per-row decode kernel over tiled index ``arrays``.

    ``kernel(rec, tiles_list, row)`` decodes one row into ``rec`` and
    returns ``(n, overflow)``; ``dtypes`` are the arrays' dtypes (one tile
    slot each).  Returns ``(ids, valid, count, overflow)``.
    """
    (q,) = rows.shape
    bq, qp = tiles.lane_blocks(q)
    nrec = tiles.rec_rows(cap + 2)
    na = len(arrays)

    def body(rows_ref, *refs):
        srcs, out_ref, bufs = refs[:na], refs[na], refs[na + 1: 2 * na + 1]
        tags, sems, rbuf, hw, osem = refs[2 * na + 1:]
        ts = [Tiles((s,), (b,), tags, i, sems.at[pl.ds(i, 1)])
              for i, (s, b) in enumerate(zip(srcs, bufs))]
        for t in ts:
            t.reset()
        rec = Record(rbuf, hw, osem.at[0])
        rec.clear()
        blk = pl.program_id(0)

        def lane(i, c):
            qi = blk * bq + i

            @pl.when(qi < q)
            def _():
                n, ovf = kernel(rec, ts, rows_ref[i])
                rec.put(cap, n)
                rec.put(cap + 1, ovf)
                rec.flush(out_ref, qi)

            return c

        jax.lax.fori_loop(0, bq, lane, 0)

    (r,) = lane_pad(qp, rows)
    out = pl.pallas_call(
        body,
        grid=(qp // bq,),
        in_specs=[pl.BlockSpec((bq,), lambda i: (i,), memory_space=pltpu.SMEM)]
        + [tiles.ANY] * na,
        out_specs=tiles.ANY,
        out_shape=jax.ShapeDtypeStruct((qp, nrec, tiles.TC), _I32),
        scratch_shapes=[
            *tiles.tile_bufs(*dtypes),
            pltpu.SMEM((na,), _I32), pltpu.SemaphoreType.DMA((na,)),
            pltpu.SMEM((nrec, tiles.TC), _I32), pltpu.SMEM((1,), _I32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(r, *tiles.tiled("pred_gather", *arrays))
    return decode(out, q, cap)


@functools.partial(
    jax.jit, static_argnames=("bytes_per_pred", "cap", "interpret")
)
def pred_gather(
    rows: jax.Array,
    offsets: jax.Array,
    words: jax.Array,
    *,
    bytes_per_pred: int,
    cap: int,
    interpret: bool = False,
):
    """Batched CSR predicate-list gather.

    Returns ``(ids, valid, count, overflow)`` with shapes
    ``(Q, cap) / (Q, cap) / (Q,) / (Q,)``.  ``rows`` must be pre-clipped
    to ``[0, len(offsets) - 2]``.
    """
    mask = (1 << (8 * bytes_per_pred)) - 1 if bytes_per_pred < 4 else 0xFFFFFFFF
    nw = int(words.shape[0])

    def one(rec, ts, row):
        off, wd = ts
        start = off.get_flat(row)[0]
        deg = off.get_flat(row + 1)[0] - start
        n = jnp.minimum(deg, cap)

        def pred(j):
            bidx = (start + j) * bytes_per_pred
            (word,) = wd.get_flat(jnp.clip(bidx >> 2, 0, nw - 1))
            return ((word >> ((bidx & 3) * 8).astype(_U32))
                    & _U32(mask)).astype(_I32)

        rec.fill(0, n, pred)
        return n, (deg > cap).astype(_I32)

    return _call(one, rows, (offsets, words), (_I32, _U32), cap=cap,
                 interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=(
        "levels", "level_byte_start", "flag_word_start", "deg_width",
        "rows_per_block", "cap", "interpret",
    ),
)
def pred_gather_dac(
    rows: jax.Array,
    anchors: jax.Array,
    words: jax.Array,
    degs: jax.Array,
    flags: jax.Array,
    frank: jax.Array,
    *,
    levels: int,
    level_byte_start: tuple,
    flag_word_start: tuple,
    deg_width: int,
    rows_per_block: int,
    cap: int,
    interpret: bool = False,
):
    """Batched DAC(b=8) predicate-list gather + on-device decode.

    Returns ``(ids, valid, count, overflow)`` with shapes
    ``(Q, cap) / (Q, cap) / (Q,) / (Q,)``.  ``rows`` must be pre-clipped
    to ``[0, n_rows - 1]``.
    """
    per_word = 32 // deg_width
    dmask = (1 << deg_width) - 1 if deg_width < 32 else 0xFFFFFFFF
    na, nw, nd, nf = (int(a.shape[0]) for a in (anchors, words, degs, flags))

    def one(rec, ts, row):
        anc, wd, dg, fl, fr = ts
        block = tiles.fdiv(row, rows_per_block)
        within = tiles.fmod(row, rows_per_block)
        w0 = block * 4
        start = anc.get_flat(jnp.clip(block, 0, na - 1))[0]
        # the degrees before `within` in the block: 4 packed words, unrolled
        for k in range(4):
            (dword,) = dg.get_flat(jnp.clip(w0 + k, 0, nd - 1))
            for j in range(per_word):
                dv = ((dword >> _U32(j * deg_width)) & _U32(dmask)).astype(_I32)
                start = start + jnp.where(k * per_word + j < within, dv, 0)
        (dword,) = dg.get_flat(
            jnp.clip(w0 + tiles.fdiv(within, per_word), 0, nd - 1))
        dsh = (tiles.fmod(within, per_word) * deg_width).astype(_U32)
        deg = ((dword >> dsh) & _U32(dmask)).astype(_I32)
        n = jnp.minimum(deg, cap)

        def byte_at(bidx):
            (w,) = wd.get_flat(jnp.clip(bidx >> 2, 0, nw - 1))
            return ((w >> ((bidx & 3) * 8).astype(_U32))
                    & _U32(0xFF)).astype(_I32)

        def gap_at(j):
            pos = start + j
            gap = byte_at(level_byte_start[0] + pos)
            alive = _I32(1)
            for lvl in range(levels - 1):
                fidx = jnp.clip(flag_word_start[lvl] + (pos >> 5), 0, nf - 1)
                (fword,) = fl.get_flat(fidx)
                (frk,) = fr.get_flat(fidx)
                sh = (pos & 31).astype(_U32)
                alive = alive & ((fword >> sh) & _U32(1)).astype(_I32)
                low = fword & ((_U32(1) << sh) - _U32(1))
                pos = jnp.where(alive == 1, frk + tiles.popcount(low), 0)
                chunk = byte_at(level_byte_start[lvl + 1] + pos)
                gap = gap + jnp.where(alive == 1, chunk << (8 * (lvl + 1)), 0)
            return gap

        def lane(j, acc):
            acc = acc + gap_at(j)
            rec.put(j, acc - 1)
            return acc

        jax.lax.fori_loop(0, n, lane, _I32(0))
        rec.trim(0, n)
        return n, (deg > cap).astype(_I32)

    return _call(
        one, rows, (anchors, words, degs, flags, frank),
        (_I32, _U32, _U32, _U32, _I32), cap=cap,
        interpret=interpret,
    )
