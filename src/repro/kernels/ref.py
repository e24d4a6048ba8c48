"""Pure-jnp oracles for every Pallas kernel (the ``interpret=True`` ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitvec
from repro.core.k2tree import K2Meta


def popcount_ref(words: jax.Array) -> jax.Array:
    return jax.lax.population_count(words).astype(jnp.int32)


def k2_check_ref(
    meta: K2Meta,
    rows: jax.Array,
    cols: jax.Array,
    t_words: jax.Array,
    t_rank: jax.Array,
    l_words: jax.Array,
    ones_before: jax.Array,
    level_start: jax.Array,
) -> jax.Array:
    """Identical math to core/k2tree.check, phrased on raw arrays."""
    H = meta.n_levels
    rrem, crem = rows.astype(jnp.int32), cols.astype(jnp.int32)
    rdig, cdig = [], []
    for sub in meta.subsides:
        rdig.append(rrem // sub)
        cdig.append(crem // sub)
        rrem, crem = rrem % sub, crem % sub
    alive = jnp.ones(rows.shape, jnp.bool_)
    pos = (rdig[0] * meta.ks[0] + cdig[0]).astype(jnp.int32)
    for lvl in range(H):
        last = lvl == H - 1
        words = l_words if last else t_words
        bit = bitvec.get_bit(words, pos)
        alive = alive & (bit == 1)
        if not last:
            j = bitvec.rank1(t_words, t_rank, pos) - ones_before[lvl]
            nxt = rdig[lvl + 1] * meta.ks[lvl + 1] + cdig[lvl + 1]
            pos = level_start[lvl + 1] + j * meta.radices[lvl + 1] + nxt
            pos = jnp.where(alive, pos, 0).astype(jnp.int32)
    return alive


def k2_scan_ref(
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
):
    """Identical semantics to kernels.k2_scan, phrased on raw forest arrays.

    Deliberately uses the scatter-based ``_compact`` (vs the kernel's
    in-order append to an SMEM frontier) so kernel-vs-ref agreement checks
    two independent compaction algorithms.  Returns (ids, valid, count, overflow).
    """
    from repro.core.k2tree import _compact, _row_digits

    H = meta.n_levels

    def one(pred, key, axis):
        pred = pred.astype(jnp.int32)
        is_row = axis.astype(jnp.int32) == 0
        fdig = _row_digits(meta, key.astype(jnp.int32))
        k0, sub0 = meta.ks[0], meta.subsides[0]
        init_n = min(k0, cap)
        j0 = jnp.arange(init_n, dtype=jnp.int32)
        p0 = jnp.where(is_row, fdig[0] * k0 + j0, j0 * k0 + fdig[0])
        pos = jnp.zeros((cap,), jnp.int32).at[:init_n].set(p0)
        base = jnp.zeros((cap,), jnp.int32).at[:init_n].set(j0 * sub0)
        live = pred >= 0  # dead lane: empty, no overflow
        valid = jnp.zeros((cap,), jnp.bool_).at[:init_n].set(live)
        overflow = jnp.asarray(k0 > cap) & live

        words0 = l_words if H == 1 else t_words
        valid = valid & (bitvec.get_bit_2d(words0, pred, pos) == 1)

        for lvl in range(H - 1):
            last_child = lvl + 1 == H - 1
            k, r, sub = meta.ks[lvl + 1], meta.radices[lvl + 1], meta.subsides[lvl + 1]
            j = bitvec.rank1_2d(t_words, t_rank, pred, pos) - ones_before[pred, lvl]
            child_base0 = level_start[pred, lvl + 1] + j * r
            ch = jnp.arange(k, dtype=jnp.int32)
            cpos = child_base0[:, None] + jnp.where(
                is_row, fdig[lvl + 1] * k + ch[None, :], ch[None, :] * k + fdig[lvl + 1]
            )
            cbase = base[:, None] + ch[None, :] * sub
            wordsc = l_words if last_child else t_words
            cbit = bitvec.get_bit_2d(wordsc, pred, jnp.where(valid[:, None], cpos, 0))
            cvalid = valid[:, None] & (cbit == 1)
            valid, _, ovf, (pos, base) = _compact(
                cvalid.reshape(-1), cap, cpos.reshape(-1), cbase.reshape(-1)
            )
            overflow = overflow | ovf
            pos = jnp.where(valid, pos, 0)

        valid, count, ovf, (ids,) = _compact(valid, cap, base)
        return ids, valid, count, overflow | ovf

    return jax.vmap(one)(
        jnp.asarray(preds, jnp.int32), jnp.asarray(keys, jnp.int32),
        jnp.asarray(axes, jnp.int32),
    )


def k2_range_ref(
    meta: K2Meta,
    preds: jax.Array,
    t_words: jax.Array,
    t_rank: jax.Array,
    l_words: jax.Array,
    ones_before: jax.Array,
    level_start: jax.Array,
    *,
    cap: int,
):
    """Identical semantics to kernels.k2_range, phrased on raw forest arrays.

    Like ``k2_scan_ref`` this deliberately uses the scatter-based
    ``_compact`` (vs the kernel's in-order append) so agreement checks two
    independent compaction algorithms.  Level 0 bit-tests every root child
    and only then compacts — the fixed overflow semantics.  Returns
    ``(rows, cols, valid, count, overflow)``.
    """
    from repro.core.k2tree import _compact

    H = meta.n_levels

    def one(pred):
        pred = pred.astype(jnp.int32)
        k0, r0, sub0 = meta.ks[0], meta.radices[0], meta.subsides[0]
        d0 = jnp.arange(r0, dtype=jnp.int32)
        words0 = l_words if H == 1 else t_words
        bit0 = bitvec.get_bit_2d(words0, pred, d0)
        valid, _, ovf, (pos, rbase, cbase) = _compact(  # pred < 0: dead
            (bit0 == 1) & (pred >= 0), cap, d0, (d0 // k0) * sub0,
            (d0 % k0) * sub0,
        )
        overflow = ovf
        pos = jnp.where(valid, pos, 0)

        for lvl in range(H - 1):
            last_child = lvl + 1 == H - 1
            k, r, sub = meta.ks[lvl + 1], meta.radices[lvl + 1], meta.subsides[lvl + 1]
            j = bitvec.rank1_2d(t_words, t_rank, pred, pos) - ones_before[pred, lvl]
            child_base0 = level_start[pred, lvl + 1] + j * r
            d = jnp.arange(r, dtype=jnp.int32)
            cpos = child_base0[:, None] + d[None, :]
            crb = rbase[:, None] + (d[None, :] // k) * sub
            ccb = cbase[:, None] + (d[None, :] % k) * sub
            wordsc = l_words if last_child else t_words
            cbit = bitvec.get_bit_2d(wordsc, pred, jnp.where(valid[:, None], cpos, 0))
            cvalid = valid[:, None] & (cbit == 1)
            valid, _, ovf, (pos, rbase, cbase) = _compact(
                cvalid.reshape(-1), cap, cpos.reshape(-1), crb.reshape(-1),
                ccb.reshape(-1)
            )
            overflow = overflow | ovf
            pos = jnp.where(valid, pos, 0)

        valid, count, ovf, (rows, cols) = _compact(valid, cap, rbase, cbase)
        return rows, cols, valid, count, overflow | ovf

    return jax.vmap(one)(jnp.asarray(preds, jnp.int32))


def k2_scan_rebind_ref(
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
):
    """Fused scan→rebind reference: ``k2_scan_ref`` composed with itself.

    Dead X lanes are clamped to key 0 exactly as the kernel does (their
    ``y_valid`` rows are masked by the caller).  Returns the kernel's 8-tuple.
    """
    q = jnp.shape(preds1)[0]
    x_ids, x_valid, x_count, x_ovf = k2_scan_ref(
        meta, preds1, keys1, axes1, t_words, t_rank, l_words,
        ones_before, level_start, cap=cap_x,
    )
    keys2 = jnp.where(x_valid, x_ids, 0).reshape(q * cap_x)
    p2 = jnp.broadcast_to(
        jnp.asarray(preds2, jnp.int32)[:, None], (q, cap_x)
    ).reshape(q * cap_x)
    a2 = jnp.broadcast_to(
        jnp.asarray(axes2, jnp.int32)[:, None], (q, cap_x)
    ).reshape(q * cap_x)
    y_ids, y_valid, y_count, y_ovf = k2_scan_ref(
        meta, p2, keys2, a2, t_words, t_rank, l_words,
        ones_before, level_start, cap=cap_y,
    )
    return (
        x_ids, x_valid, x_count, x_ovf,
        y_ids.reshape(q, cap_x, cap_y), y_valid.reshape(q, cap_x, cap_y),
        y_count.reshape(q, cap_x), y_ovf.reshape(q, cap_x),
    )


def pred_gather_ref(
    rows: jax.Array,
    offsets: jax.Array,
    words: jax.Array,
    *,
    bytes_per_pred: int,
    cap: int,
):
    """Identical semantics to kernels.pred_gather, phrased on raw CSR arrays.

    Lane (q, j) holds the j-th packed entry of row ``rows[q]``; prefix
    ``valid``, dead lanes zeroed, ``overflow`` = row longer than ``cap``.
    The byte unpacking is ``predindex.payload_at`` — one source of truth
    for the packing scheme; the Pallas kernel is the independent
    implementation the differential harness checks against.
    Returns (ids, valid, count, overflow).
    """
    from repro.core.predindex import payload_at

    rows = jnp.asarray(rows, jnp.int32)
    start = offsets[rows]
    deg = offsets[rows + 1] - start
    lane = jnp.arange(cap, dtype=jnp.int32)[None, :]
    n = jnp.minimum(deg, cap)
    valid = lane < n[:, None]
    elem = jnp.where(valid, start[:, None] + lane, 0)
    ids = jnp.where(valid, payload_at(words, elem, bytes_per_pred), 0)
    return ids, valid, n.astype(jnp.int32), deg > cap


def pred_gather_dac_ref(
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
):
    """Identical semantics to kernels.pred_gather_dac, on raw DAC arrays.

    Decodes the multi-level DAC(b=8) payload of ``core/predindex``
    (``layout="dac"``): row pointers are reconstructed from one int32
    anchor per ``rows_per_block`` rows plus ``deg_width``-bit packed
    degrees; per lane, the level-0 chunk is read at ``start + lane``, and
    each continuation flag's in-level rank re-addresses the lane into the
    next level's byte stream; the recovered gaps prefix-sum back to
    0-based predicate ids.  This reference is vectorized jnp with
    ``jnp.cumsum``; the Pallas kernel uses a log-doubling prefix sum and a
    masked SWAR loop — two independent implementations for the
    differential harness.  Returns (ids, valid, count, overflow).
    """
    rows = jnp.asarray(rows, jnp.int32)
    per_word = 32 // deg_width
    dmask = jnp.uint32((1 << deg_width) - 1 if deg_width < 32 else 0xFFFFFFFF)
    block = rows // rows_per_block
    within = rows % rows_per_block

    kidx = jnp.arange(rows_per_block, dtype=jnp.int32)
    widx = block[:, None] * 4 + kidx[None, :] // per_word
    dword = degs[jnp.clip(widx, 0, degs.shape[0] - 1)]
    shift = ((kidx % per_word) * deg_width).astype(jnp.uint32)
    dvals = ((dword >> shift[None, :]) & dmask).astype(jnp.int32)  # (B, rb)
    start = anchors[jnp.clip(block, 0, anchors.shape[0] - 1)] + jnp.sum(
        dvals * (kidx[None, :] < within[:, None]), axis=1
    )
    deg = jnp.take_along_axis(dvals, within[:, None], axis=1)[:, 0]

    def byte_at(bidx):
        w = words[jnp.clip(bidx >> 2, 0, words.shape[0] - 1)]
        return ((w >> ((bidx & 3) * 8).astype(jnp.uint32)) & 0xFF).astype(
            jnp.int32
        )

    lane = jnp.arange(cap, dtype=jnp.int32)[None, :]
    n = jnp.minimum(deg, cap)
    valid = lane < n[:, None]
    pos = jnp.where(valid, start[:, None] + lane, 0)
    gap = byte_at(level_byte_start[0] + pos)
    alive = valid
    for lvl in range(levels - 1):
        fidx = jnp.clip(flag_word_start[lvl] + (pos >> 5), 0, flags.shape[0] - 1)
        fword = flags[fidx]
        sh = (pos & 31).astype(jnp.uint32)
        bit = ((fword >> sh) & 1) == 1
        low = fword & ((jnp.uint32(1) << sh) - jnp.uint32(1))
        rank = frank[fidx] + popcount_ref(low)
        alive = alive & bit
        pos = jnp.where(alive, rank, 0)
        chunk = byte_at(level_byte_start[lvl + 1] + pos)
        gap = gap + jnp.where(alive, chunk << (8 * (lvl + 1)), 0)
    preds = jnp.cumsum(jnp.where(valid, gap, 0), axis=1) - 1
    ids = jnp.where(valid, preds, 0).astype(jnp.int32)
    return ids, valid, n.astype(jnp.int32), deg > cap


def sorted_intersect_mask_ref(a_ids: jax.Array, b_ids: jax.Array) -> jax.Array:
    pos = jnp.searchsorted(b_ids, a_ids)
    got = jnp.take(b_ids, jnp.clip(pos, 0, b_ids.shape[0] - 1), mode="clip")
    return (got == a_ids) & (a_ids != jnp.int32(2**31 - 1))


def block_spmm_ref(mask: jax.Array, a: jax.Array, x: jax.Array,
                   block_m: int = 128, block_k: int = 128) -> jax.Array:
    """Masked matmul: zero out masked-off tiles of A, then dense matmul."""
    m, k = a.shape
    mm = jnp.repeat(jnp.repeat(mask, block_m, 0), block_k, 1).astype(a.dtype)
    return jnp.dot(a * mm, x, preferred_element_type=jnp.float32)
