"""Word reads from HBM-resident arenas, for the scalar-core traversal kernels.

The forest and the predicate index stay in HBM (``memory_space=pl.ANY``):
at real scale they are far beyond VMEM (the geonames Table-1 forest is a
176 MB arena in whole tiles), and Mosaic lowers no vector gather from them.  The
traversal kernels therefore run on the TPU's scalar core.  A read of word
``a[r, c]`` DMAs the aligned ``(8, 128)`` tile that holds it into an SMEM
buffer and keeps it there: further reads that fall in the held tile cost one
SMEM load.  A frontier walks a level in position order, but a k²-tree scan's
level does not stay in a few tiles: it fans out to a few hundred nodes over
about a hundred tiles (~113 rank-tile and ~134 bit-tile fetches per geonames
scanning lane, PERF.md §5), so consecutive nodes often share a tile and a
level as a whole does not.

The store builds every array in whole tiles (``core/bitvec.py``), so each
tile DMA is in bounds on the chip and in interpret mode alike; the kernels
take the arrays as they are (:func:`tiled`).

Kernel outputs use the same scheme in reverse: one lane's answer is a
*record* of ``rec_rows(n) * 128`` int32 words built in SMEM and DMA'd to row
``q`` of a ``(Q, rec_rows, 128)`` HBM output (``rec_rows`` a multiple of 8,
so every record is whole tiles).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitvec import TILE_COLS as TC
from repro.core.bitvec import TILE_ROWS as TR
from repro.core.bitvec import round_up

LANE_BLOCK = 1024  # 1-D SMEM operand blocks follow the (1024,) HBM tile

ANY = pl.BlockSpec(memory_space=pl.ANY)  # an arena left in HBM


def tiled(name: str, *arrays: jax.Array) -> list:
    """The arrays as a kernel reads them: a 2-D arena as it is, a 1-D array
    as its ``(N / 128, 128)`` view (element e at ``[e >> 7, e & 127]``; a
    bitcast, no copy).  Each must be whole (8, 128) tiles, as the store
    builds it: a tile read past the end would leave the array on the chip."""
    out = []
    for a in arrays:
        if a.ndim == 1 and a.shape[0] % (TR * TC) == 0:
            out.append(a.reshape(-1, TC))
        elif a.ndim == 2 and a.shape[0] % TR == 0 and a.shape[1] % TC == 0:
            out.append(a)
        else:
            raise ValueError(
                f"{name}: an operand of shape {a.shape} is not whole "
                f"({TR}, {TC}) tiles; build it with core.bitvec's tile padding"
            )
    return out


def rec_rows(n_words: int) -> int:
    """Tile rows of a record holding ``n_words`` int32 words."""
    return round_up(max(n_words, 1), TR * TC) // TC


def lane_blocks(q: int) -> tuple[int, int]:
    """``(lanes per grid step, padded lane count)``.

    1-D SMEM operand blocks must be the whole array or a multiple of the
    (1024,) HBM tile: a batch of up to 1024 lanes runs as one whole block,
    a larger one in blocks of 1024.
    """
    if q <= LANE_BLOCK:
        return q, q
    return LANE_BLOCK, round_up(q, LANE_BLOCK)


def fdiv(x, d: int):
    """Floor division by a static positive int (a shift for powers of 2)."""
    if d & (d - 1) == 0:
        return x >> (d.bit_length() - 1)
    return x // d


def fmod(x, d: int):
    if d & (d - 1) == 0:
        return x & (d - 1)
    return x % d


def popcount(w):
    """SWAR popcount of a uint32 scalar -> int32."""
    w = w - ((w >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    w = (w & jnp.uint32(0x33333333)) + ((w >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    w = (w + (w >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return ((w * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def tile_bufs(*dtypes) -> list:
    return [pltpu.SMEM((TR, TC), dt) for dt in dtypes]


class Tiles:
    """One cached tile slot over arrays of one shape, read together.

    ``srcs`` are tiled HBM refs, ``bufs`` their SMEM tile buffers,
    ``tags[slot]`` the id of the tile held (-1: none), ``sems`` one DMA
    semaphore per source.
    """

    def __init__(self, srcs, bufs, tags, slot: int, sems):
        self.srcs, self.bufs = tuple(srcs), tuple(bufs)
        self.tags, self.slot, self.sems = tags, slot, sems
        self.ncol = srcs[0].shape[1] // TC

    def reset(self) -> None:
        self.tags[self.slot] = jnp.int32(-1)

    def get(self, r, c) -> tuple:
        """Words ``src[r, c]`` of every source (in-bounds r, c)."""
        tag = (r >> 3) * self.ncol + (c >> 7)

        @pl.when(self.tags[self.slot] != tag)
        def _fetch():
            r0 = pl.multiple_of((r >> 3) * TR, TR)
            c0 = pl.multiple_of((c >> 7) * TC, TC)
            cps = [
                pltpu.make_async_copy(
                    src.at[pl.ds(r0, TR), pl.ds(c0, TC)], buf, self.sems.at[i]
                )
                for i, (src, buf) in enumerate(zip(self.srcs, self.bufs))
            ]
            for cp in cps:
                cp.start()
            for cp in cps:
                cp.wait()
            self.tags[self.slot] = tag

        return tuple(buf[r & (TR - 1), c & (TC - 1)] for buf in self.bufs)

    def get_flat(self, e) -> tuple:
        """Element ``e`` of a 1-D array in its :func:`tiled` view."""
        return self.get(e >> 7, e & (TC - 1))


class Record:
    """A lane's output record in SMEM, flushed to ``out.at[q]`` by DMA.

    Words past what the last lane wrote are kept zero: :meth:`fill` writes
    ``n`` values then zeroes up to the previous lane's high-water mark, so
    a record never carries a stale id.
    """

    def __init__(self, buf, hw, sem):
        self.buf, self.hw, self.sem = buf, hw, sem

    def clear(self) -> None:
        rows = self.buf.shape[0]

        def z(i, c):
            self.buf[i >> 7, i & (TC - 1)] = jnp.int32(0)
            return c

        jax.lax.fori_loop(0, rows * TC, z, 0)
        for m in range(self.hw.shape[0]):
            self.hw[m] = jnp.int32(0)

    def put(self, i, v) -> None:
        self.buf[i >> 7, i & (TC - 1)] = v

    def fill(self, off: int, n, src, mark: int = 0) -> None:
        """Words ``off + [0, n)`` from ``src(i)``, then :meth:`trim`."""

        def w(i, c):
            self.put(off + i, src(i))
            return c

        jax.lax.fori_loop(0, n, w, 0)
        self.trim(off, n, mark)

    def trim(self, off: int, n, mark: int = 0) -> None:
        """Zero words ``off + [n, hw[mark])`` — what the previous lane left
        in the run that starts at ``off`` — and set the mark to ``n``."""

        def z(i, c):
            self.put(off + i, jnp.int32(0))
            return c

        jax.lax.fori_loop(n, jnp.maximum(n, self.hw[mark]), z, 0)
        self.hw[mark] = n

    def flush(self, out_ref, q) -> None:
        cp = pltpu.make_async_copy(self.buf, out_ref.at[q], self.sem)
        cp.start()
        cp.wait()

