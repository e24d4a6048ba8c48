"""Packed bit vectors with O(1) rank — the k²-tree storage primitive.

The paper stores the tree as plain bit arrays T and L navigated with
``rank1``.  On TPU we pack bits LSB-first into ``uint32`` words and keep a
per-word exclusive cumulative popcount (``rank_blocks``) so that

    rank1(p) = rank_blocks[p >> 5] + popcount(word[p >> 5] & ((1 << (p & 31)) - 1))

is a gather + integer ALU op — fully vectorizable on the VPU.

Host-side construction is numpy; query-side helpers are jnp and are used by
both the pure-JAX reference paths and as oracles for the Pallas kernels.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32

# Device arrays are stored in whole 32-bit TPU tiles of (8, 128) words:
# 2-D arenas pad rows to 8 and columns to 128, 1-D arrays pad to 1024 words
# (a (N/128, 128) view of them is then a free bitcast).  The traversal
# kernels DMA whole tiles from these arrays, so aligning them once at build
# time keeps every tile read in bounds without a per-call copy.
TILE_ROWS, TILE_COLS = 8, 128


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_pad_1d(a: np.ndarray) -> np.ndarray:
    """Zero-pad a 1-D array to whole (8, 128) tiles (a multiple of 1024)."""
    n = round_up(max(a.shape[0], 1), TILE_ROWS * TILE_COLS)
    return np.pad(a, (0, n - a.shape[0]))


class BitVec(NamedTuple):
    """A packed bit vector plus rank acceleration structure.

    Attributes:
      words:       uint32[n_words]  bits, LSB-first within each word.
      rank_blocks: int32[n_words]   exclusive cumulative popcount per word.
      n_bits:      int              logical length (python int, static).
    """

    words: jax.Array
    rank_blocks: jax.Array
    n_bits: int


# ---------------------------------------------------------------------------
# host-side (numpy) construction
# ---------------------------------------------------------------------------


def pack_bits_np(bits: np.ndarray) -> np.ndarray:
    """Pack a {0,1} uint8 array into uint32 words, LSB-first."""
    bits = np.asarray(bits, dtype=np.uint8)
    n = bits.shape[0]
    n_words = max(1, (n + WORD_BITS - 1) // WORD_BITS)
    padded = np.zeros(n_words * WORD_BITS, dtype=np.uint64)
    padded[:n] = bits
    lanes = padded.reshape(n_words, WORD_BITS)
    weights = (np.uint64(1) << np.arange(WORD_BITS, dtype=np.uint64))
    return (lanes * weights).sum(axis=1).astype(np.uint32)


def rank_blocks_np(words: np.ndarray) -> np.ndarray:
    """Exclusive cumulative popcount per word (int32)."""
    pops = popcount_np(words)
    out = np.zeros_like(pops, dtype=np.int64)
    np.cumsum(pops[:-1], out=out[1:])
    return out.astype(np.int32)


def popcount_np(words: np.ndarray) -> np.ndarray:
    w = words.astype(np.uint32)
    w = w - ((w >> np.uint32(1)) & np.uint32(0x55555555))
    w = (w & np.uint32(0x33333333)) + ((w >> np.uint32(2)) & np.uint32(0x33333333))
    w = (w + (w >> np.uint32(4))) & np.uint32(0x0F0F0F0F)
    return ((w * np.uint32(0x01010101)) >> np.uint32(24)).astype(np.int32)


def bitvec_from_bits(bits: np.ndarray) -> BitVec:
    words = pack_bits_np(bits)
    return BitVec(
        words=jnp.asarray(words),
        rank_blocks=jnp.asarray(rank_blocks_np(words)),
        n_bits=int(bits.shape[0]),
    )


# ---------------------------------------------------------------------------
# device-side (jnp) queries — vectorized over arbitrary index shapes
# ---------------------------------------------------------------------------


def get_bit(words: jax.Array, pos: jax.Array) -> jax.Array:
    """bit value at position(s) ``pos`` (int32) -> int32 {0,1}.

    Out-of-range positions are clamped by jnp.take's default mode; callers
    must mask invalid lanes themselves.
    """
    word = jnp.take(words, pos >> 5, mode="clip")
    return ((word >> (pos & 31).astype(jnp.uint32)) & 1).astype(jnp.int32)


def rank1(words: jax.Array, rank_blocks: jax.Array, pos: jax.Array) -> jax.Array:
    """Number of set bits strictly before ``pos`` (vectorized)."""
    widx = pos >> 5
    base = jnp.take(rank_blocks, widx, mode="clip")
    word = jnp.take(words, widx, mode="clip")
    mask = (jnp.uint32(1) << (pos & 31).astype(jnp.uint32)) - jnp.uint32(1)
    return base + jax.lax.population_count(word & mask).astype(jnp.int32)


def get_bit_2d(words2d: jax.Array, row: jax.Array, pos: jax.Array) -> jax.Array:
    """get_bit over a (P, W) padded word arena: row selects the tree."""
    word = words2d[row, jnp.clip(pos >> 5, 0, words2d.shape[-1] - 1)]
    return ((word >> (pos & 31).astype(jnp.uint32)) & 1).astype(jnp.int32)


def rank1_2d(
    words2d: jax.Array, rank2d: jax.Array, row: jax.Array, pos: jax.Array
) -> jax.Array:
    widx = jnp.clip(pos >> 5, 0, words2d.shape[-1] - 1)
    base = rank2d[row, widx]
    word = words2d[row, widx]
    mask = (jnp.uint32(1) << (pos & 31).astype(jnp.uint32)) - jnp.uint32(1)
    return base + jax.lax.population_count(word & mask).astype(jnp.int32)
