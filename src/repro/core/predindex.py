"""Compressed SP/OP predicate indexes — the k²-triples+ subsystem.

The paper concedes that vertical partitioning's worst case is the
unbounded-predicate pattern: resolving ``(S,?P,?O)`` / ``(?S,?P,O)`` /
``(S,?P,O)`` means touching **all** |P| trees.  The follow-up work
*Compressed Vertical Partitioning for Full-In-Memory RDF Management*
(arXiv:1310.4954) fixes this with two compact indexes:

  * **SP** — for every subject s, the sorted list of predicates p such that
    some triple (s, p, ·) exists;
  * **OP** — for every object o, the sorted list of predicates p such that
    some triple (·, p, o) exists.

An unbounded-``?P`` query then scans only the candidate predicates named by
the index instead of sweeping the whole forest — predicate pruning, which
arXiv:2002.11622 confirms as the decisive optimization for this layout.

Layout (device, jit-able): both indexes share ONE arena so a mixed batch of
subject- and object-keyed queries needs a single gather program (row r of
subject s is ``s-1``, row of object o is ``|S| + o - 1``, 1-based ids).
Two on-device layouts exist, selected by ``PredIndexMeta.layout``:

  * ``layout="dac"`` (default) — the real multi-level **DAC(b=8)** of the
    paper: each list is gap-encoded (first entry +1, then deltas, all >= 1)
    and split into 8-bit chunks; level k holds the k-th chunk of every gap
    still alive at that level, in stable order, as one byte stream.  A
    rank-enabled flag bitmap per non-final level says "this element
    continues", and the in-level rank of a set flag is the element's
    position in the next level's stream.  The row-pointer side is also
    compressed: one int32 anchor per ``rows_per_block`` rows plus
    ``deg_width``-bit packed per-row degrees, so ``offsets[r]`` is an
    anchor plus a short masked SWAR sum.  The gather kernel decodes chunks,
    ranks flags, and prefix-sums the gaps back to predicate ids on device.
  * ``layout="fixed"`` — the byte-packed fallback: ``words`` holds the
    concatenated lists at ``bytes_per_pred`` ∈ {1, 2, 4} bytes per entry
    (the fixed-width special case of byte-aligned DACs — direct access is
    shift+mask) under plain int32 CSR ``offsets``.  Kept for differential
    testing and as an escape hatch (``ExecConfig.pred_index_layout``).

Size accounting is honest on two axes (``PredIndexStats``): the bits each
device arena *actually* costs (payload + row pointers, measured from the
materialized arrays), and the analytic multi-level DAC(b=8) size of the
gap-encoded lists — the number a 1310.4954-style host implementation would
report.  Since the DAC layout is real, measured ``payload_bits`` +
``offsets_bits`` now lands within word-padding distance of ``dac_bits``
(CI gates the ratio at 1.25×; ``benchmarks/check_compression.py``).

The batched query ops at the bottom (``gather_batch``, ``scan_pruned_batch``,
``check_pruned_batch``) are the substrate of the engine's unbounded serve
lanes and the optimizer's bound-``?P`` resolves.  ``gather_batch`` routes
through the ``kernels/pred_gather`` Pallas kernels or their jnp mirrors
exactly like ``k2forest.scan_batch_mixed`` routes (``REPRO_SCAN_BACKEND`` /
per-call ``backend=``); the decode layout follows ``pmeta.layout``, which
the engine selects per ``ExecConfig.pred_index_layout``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitvec, k2forest
from repro.core.bitvec import popcount_np
from repro.core.k2forest import K2Forest
from repro.core.k2tree import K2Meta, QueryResult, _compact

DAC_CHUNK_BITS = 8


class PredIndex(NamedTuple):
    """Device arrays (a pytree; shards replicated next to the forest).

    Union of both layouts — unused fields are size-(1) placeholders so the
    pytree structure (and the shard_map in_specs built from it) is layout
    independent.

      * fixed: ``offsets`` int32[R+1] CSR row pointers, ``words`` the
        byte-packed predicate ids; ``degs``/``flags``/``frank`` unused.
      * dac:   ``offsets`` int32[n_blocks] block anchors, ``degs``
        uint32[n_blocks*4] packed per-row degrees, ``words`` the
        concatenated per-level chunk byte streams, ``flags`` the per-level
        continuation bitmaps (word aligned per level), ``frank``
        int32 exclusive in-level popcount per flag word.

    Every used array is zero-padded to whole (8, 128) tiles (a multiple of
    1024 entries, ``bitvec.tile_pad_1d``), the form the kernels read.
    """

    offsets: jax.Array  # int32 — CSR row pointers (fixed) | block anchors (dac)
    words: jax.Array  # uint32 — packed predicate ids (fixed) | DAC chunk bytes
    degs: jax.Array  # uint32 — deg_width-bit packed per-row degrees (dac)
    flags: jax.Array  # uint32 — continuation bitmaps, levels 0..L-2 (dac)
    frank: jax.Array  # int32 — exclusive in-level rank per flag word (dac)


@dataclasses.dataclass(frozen=True)
class PredIndexMeta:
    """Static (hashable) geometry — travels like ``K2Meta``."""

    n_subjects: int
    n_objects: int
    n_preds: int
    bytes_per_pred: int  # 1, 2 or 4 (word-aligned: an entry never straddles)
    max_degree: int  # max list length over all subjects and objects
    # per-axis maxima: a hub object (e.g. a class object touching ~all P
    # predicates) inflates max_degree and with it any u_width sized from
    # it; callers serving subject-keyed batches can size from the SP side
    # alone (and rely on the `truncated` overflow bit otherwise)
    max_sp_degree: int = 0
    max_op_degree: int = 0
    # --- DAC layout geometry (static; meaningful when layout == "dac") ---
    layout: str = "fixed"  # "fixed" | "dac"
    levels: int = 1  # number of DAC chunk levels L
    level_byte_start: tuple = (0,)  # len L: start byte of each level stream
    flag_word_start: tuple = ()  # len L-1: word start of each level's bitmap
    deg_width: int = 32  # bits per packed degree (4 | 8 | 16 | 32)
    rows_per_block: int = 1  # rows sharing one anchor (4 words of degrees)


class PredIndexStats(NamedTuple):
    """Honest size accounting (the 1310.4954 Table analogue).

    ``payload_bits``/``offsets_bits`` are MEASURED from the default (DAC)
    device arrays — what the serving index actually costs resident —
    while ``dac_bits`` stays the analytic chunks+flags figure for the
    gap streams alone (no row pointers), so the measured-vs-analytic gap
    is visible.  The fixed-width fallback's cost is reported alongside.
    """

    sp_entries: int  # Σ_s |SP(s)|  (== #distinct (s,p) pairs)
    op_entries: int  # Σ_o |OP(o)|
    payload_bits: int  # measured: chunk streams + flag bitmaps + flag ranks
    offsets_bits: int  # measured: block anchors + packed per-row degrees
    dac_bits: int  # analytic DAC(b=8) of the gap-encoded lists
    bits_per_triple: float  # (payload + offsets) / n_triples, DAC layout
    fixed_payload_bits: int = 0  # byte-packed payload of the fixed fallback
    fixed_offsets_bits: int = 0  # its int32 CSR row pointers
    fixed_bits_per_triple: float = 0.0


@dataclasses.dataclass(frozen=True)
class BuiltPredIndex:
    """Everything ``K2TriplesStore`` carries: device + static + host views.

    ``device``/``meta`` are the default DAC layout; the fixed-width
    fallback rides along as ``device_fixed``/``meta_fixed`` (differential
    tests, ``ExecConfig.pred_index_layout="fixed"``).  ``select`` picks a
    layout pair by name.
    """

    device: PredIndex
    meta: PredIndexMeta
    stats: PredIndexStats
    host_offsets: np.ndarray  # int64[R + 1]
    host_preds: np.ndarray  # int32[total] 0-based, sorted within each row
    device_fixed: PredIndex | None = None
    meta_fixed: PredIndexMeta | None = None

    def select(self, layout: str | None = None):
        """(device, meta) for ``layout`` ("dac" | "fixed" | None=default)."""
        if (
            layout is not None
            and layout != self.meta.layout
            and self.device_fixed is not None
            and layout == self.meta_fixed.layout
        ):
            return self.device_fixed, self.meta_fixed
        return self.device, self.meta

    def host_list(self, row: int) -> np.ndarray:
        """0-based predicate list of one entity row (subjects then objects)."""
        return self.host_preds[self.host_offsets[row] : self.host_offsets[row + 1]]


def subject_row(s):
    """Entity row of 1-based subject id ``s`` (plain arithmetic, jit-safe)."""
    return s - 1


def object_row(pmeta: PredIndexMeta, o):
    """Entity row of 1-based object id ``o``."""
    return pmeta.n_subjects + o - 1


# ---------------------------------------------------------------------------
# construction (numpy, host)
# ---------------------------------------------------------------------------


def _dac_bits(values: np.ndarray, chunk: int = 8) -> int:
    """Analytic multi-level DAC size: ``chunk``-bit chunks + 1 flag bit each."""
    if values.size == 0:
        return 0
    v = values.astype(np.int64)
    nbits = np.maximum(1, np.floor(np.log2(np.maximum(v, 1))) + 1)
    nchunks = np.ceil(nbits / chunk)
    return int(nchunks.sum() * (chunk + 1))


def _encode_dac(gaps: np.ndarray):
    """Encode positive gaps into the multi-level DAC(b=8) arrays.

    Returns ``(words, levels, level_byte_start, flag_word_start, flags,
    frank)``: ``words`` uint32 holds the concatenated per-level byte
    streams (level boundaries are the static ``level_byte_start`` tuple);
    ``flags``/``frank`` hold the per-level continuation bitmaps and their
    exclusive in-level word ranks (word starts in ``flag_word_start``).
    """
    g = np.asarray(gaps, np.int64)
    if g.size == 0:
        return (
            np.zeros(1, np.uint32), 1, (0,), (),
            np.zeros(1, np.uint32), np.zeros(1, np.int32),
        )
    nbits = np.maximum(1, np.floor(np.log2(np.maximum(g, 1))).astype(np.int64) + 1)
    nchunks = (nbits + DAC_CHUNK_BITS - 1) // DAC_CHUNK_BITS
    levels = int(nchunks.max())

    streams, flag_words, frank_words, level_byte_start, flag_word_start = (
        [], [], [], [], []
    )
    byte_pos = 0
    flag_pos = 0
    cur, cur_nchunks = g, nchunks
    for lvl in range(levels):
        level_byte_start.append(byte_pos)
        stream = (cur & 0xFF).astype(np.uint8)
        streams.append(stream)
        byte_pos += int(stream.size)
        cont = cur_nchunks > (lvl + 1)
        if lvl < levels - 1:
            n_words = max((int(stream.size) + 31) // 32, 1)
            fw = np.zeros(n_words, np.int64)
            idx = np.nonzero(cont)[0]
            np.bitwise_or.at(fw, idx >> 5, np.int64(1) << (idx & 31))
            fw = fw.astype(np.uint32)
            fr = np.zeros(n_words, np.int64)
            np.cumsum(popcount_np(fw)[:-1], out=fr[1:])
            flag_word_start.append(flag_pos)
            flag_pos += n_words
            flag_words.append(fw)
            frank_words.append(fr.astype(np.int32))
        cur = cur[cont] >> DAC_CHUNK_BITS
        cur_nchunks = cur_nchunks[cont]

    chunk_bytes = np.concatenate(streams)
    padded = np.zeros((chunk_bytes.size + 3) // 4 * 4, np.uint8)
    padded[: chunk_bytes.size] = chunk_bytes
    words = padded.view("<u4").copy()
    if flag_words:
        flags = np.concatenate(flag_words)
        frank = np.concatenate(frank_words)
    else:
        flags = np.zeros(1, np.uint32)
        frank = np.zeros(1, np.int32)
    return (
        words, levels, tuple(level_byte_start), tuple(flag_word_start),
        flags, frank,
    )


def _pack_degrees(counts: np.ndarray, offsets: np.ndarray, max_degree: int):
    """Pack per-row degrees at the narrowest SWAR width + block anchors.

    Returns ``(anchors, degs, deg_width, rows_per_block)``.  A block is
    sized so its packed degrees span exactly 4 uint32 words, which bounds
    the kernel's offset-reconstruction unroll.
    """
    deg_width = next(w for w in (4, 8, 16, 32) if max_degree < (1 << w))
    per_word = 32 // deg_width
    rows_per_block = 4 * per_word
    n_rows = int(counts.size)
    n_blocks = max((n_rows + rows_per_block - 1) // rows_per_block, 1)
    padded = np.zeros(n_blocks * rows_per_block, np.uint64)
    padded[:n_rows] = counts.astype(np.uint64)
    lanes = padded.reshape(n_blocks * 4, per_word)
    shifts = np.arange(per_word, dtype=np.uint64) * deg_width
    degs = np.bitwise_or.reduce(lanes << shifts[None, :], axis=1).astype(np.uint32)
    anchors = offsets[: n_blocks * rows_per_block : rows_per_block].astype(np.int32)
    if anchors.size < n_blocks:  # counts.size == 0 degenerate
        anchors = np.zeros(n_blocks, np.int32)
    return anchors, degs, deg_width, rows_per_block


def _tiled(a: np.ndarray) -> jax.Array:
    """A device array in whole (8, 128) tiles, zero-padded past its end."""
    return jnp.asarray(bitvec.tile_pad_1d(a))


def build(
    ids: np.ndarray, *, n_subjects: int, n_objects: int, n_preds: int,
    n_triples: int | None = None,
) -> BuiltPredIndex:
    """Build SP+OP from int64[N,3] 1-based (s, p, o) ID triples."""
    ids = np.asarray(ids, dtype=np.int64).reshape(-1, 3)
    n_triples = int(ids.shape[0]) if n_triples is None else n_triples
    sp = np.unique(ids[:, [0, 1]], axis=0)  # sorted (s, p): lists come sorted
    op = np.unique(ids[:, [2, 1]], axis=0)

    R = n_subjects + n_objects
    counts = np.zeros(R, np.int64)
    np.add.at(counts, sp[:, 0] - 1, 1)
    np.add.at(counts, n_subjects + op[:, 0] - 1, 1)
    offsets = np.zeros(R + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])

    preds = np.zeros(max(int(offsets[-1]), 1), np.int32)
    # np.unique's lexsort already groups rows by entity with ascending preds,
    # so the payload is one concatenation per index half
    preds[: sp.shape[0]] = sp[:, 1] - 1
    op_base = int(offsets[n_subjects])
    preds[op_base : op_base + op.shape[0]] = op[:, 1] - 1

    bpp = 1 if n_preds <= 0xFF else (2 if n_preds <= 0xFFFF else 4)
    per_word = 4 // bpp
    n_entries = int(offsets[-1])
    padded = np.zeros(((max(n_entries, 1) + per_word - 1) // per_word) * per_word,
                      np.uint32)
    padded[:n_entries] = preds[:n_entries].astype(np.uint32)
    lanes = padded.reshape(-1, per_word)
    shifts = (np.arange(per_word, dtype=np.uint64) * 8 * bpp)
    words_fixed = np.bitwise_or.reduce(
        (lanes.astype(np.uint64) << shifts[None, :]), axis=1
    ).astype(np.uint32)

    max_degree = int(counts.max()) if R else 0
    max_sp = int(counts[:n_subjects].max()) if n_subjects else 0
    max_op = int(counts[n_subjects:].max()) if n_objects else 0
    # gap-encode each list: first entry +1, then deltas (all gaps >= 1)
    gaps = preds[:n_entries].astype(np.int64) + 1
    if n_entries:
        starts = offsets[:-1][counts > 0]
        inner = np.ones(n_entries, np.bool_)
        inner[starts] = False
        gaps[inner] = np.diff(preds[:n_entries].astype(np.int64))[inner[1:]]

    dac_words, levels, lbs, fws, flags, frank = _encode_dac(gaps)
    anchors, degs, deg_width, rows_per_block = _pack_degrees(
        counts, offsets, max_degree
    )

    payload_bits = int((dac_words.size + flags.size) * 32 + frank.size * 32)
    offsets_bits = int((anchors.size + degs.size) * 32)
    fixed_payload = int(words_fixed.size * 32)
    fixed_offsets = int((R + 1) * 32)
    stats = PredIndexStats(
        sp_entries=int(sp.shape[0]),
        op_entries=int(op.shape[0]),
        payload_bits=payload_bits,
        offsets_bits=offsets_bits,
        dac_bits=_dac_bits(gaps),
        bits_per_triple=float(payload_bits + offsets_bits) / max(n_triples, 1),
        fixed_payload_bits=fixed_payload,
        fixed_offsets_bits=fixed_offsets,
        fixed_bits_per_triple=float(fixed_payload + fixed_offsets)
        / max(n_triples, 1),
    )
    placeholder_u = jnp.zeros(1, jnp.uint32)
    placeholder_i = jnp.zeros(1, jnp.int32)
    common = dict(
        n_subjects=n_subjects, n_objects=n_objects, n_preds=n_preds,
        bytes_per_pred=bpp, max_degree=max_degree,
        max_sp_degree=max_sp, max_op_degree=max_op,
    )
    return BuiltPredIndex(
        device=PredIndex(
            offsets=_tiled(anchors.astype(np.int32)),
            words=_tiled(dac_words),
            degs=_tiled(degs),
            flags=_tiled(flags),
            frank=_tiled(frank),
        ),
        meta=PredIndexMeta(
            layout="dac", levels=levels, level_byte_start=lbs,
            flag_word_start=fws, deg_width=deg_width,
            rows_per_block=rows_per_block, **common,
        ),
        stats=stats,
        host_offsets=offsets,
        host_preds=preds[:n_entries],
        device_fixed=PredIndex(
            offsets=_tiled(offsets.astype(np.int32)),
            words=_tiled(words_fixed),
            degs=placeholder_u,
            flags=placeholder_u,
            frank=placeholder_i,
        ),
        meta_fixed=PredIndexMeta(layout="fixed", **common),
    )


def quantile_u_width(bi: BuiltPredIndex, quantile: float) -> int:
    """Candidate-lane width at a degree quantile, sized PER AXIS.

    ``max_degree`` is dominated by hub entities (a class object touching
    ~all |P| predicates widens every unbounded lane back toward the sweep);
    sizing at a quantile of the nonzero per-entity degree distribution —
    separately for the SP (subject) and OP (object) halves, then unified
    with ``max`` so either axis of a mixed batch is covered at its own
    quantile — keeps the lane narrow.  Entities whose list exceeds the
    returned width trip the gather's ``truncated`` bit and must be routed
    to the all-preds sweep fallback (``degree_rows``/``host_degrees`` give
    the host-side pre-route; the plan layer does this automatically).

    ``quantile=1.0`` reproduces ``max(max_sp_degree, max_op_degree, 1)``
    exactly.
    """
    if not (0.0 < quantile <= 1.0):
        raise ValueError(f"quantile must be in (0, 1], got {quantile}")
    offs = bi.host_offsets
    ns = bi.meta.n_subjects
    widths = []
    for deg in (np.diff(offs[: ns + 1]), np.diff(offs[ns:])):
        deg = deg[deg > 0]
        if deg.size:
            widths.append(int(np.ceil(np.quantile(deg, quantile))))
    return max(widths, default=1) if widths else 1


def host_degrees(bi: BuiltPredIndex, rows: np.ndarray) -> np.ndarray:
    """Per-entity candidate-list lengths from the host CSR (O(1) per row).

    ``rows`` are 0-based entity rows (subjects then objects, the shared
    arena layout); out-of-range rows report degree 0.  This is the exact
    host-side mirror of the device gather's ``truncated`` criterion
    (``degree > u_width``), used to pre-route outliers to the sweep.
    """
    offs = bi.host_offsets
    rows = np.asarray(rows, np.int64)
    ok = (rows >= 0) & (rows < offs.shape[0] - 1)
    r = np.where(ok, rows, 0)
    return np.where(ok, offs[r + 1] - offs[r], 0)


# ---------------------------------------------------------------------------
# device queries
# ---------------------------------------------------------------------------


def payload_at(words: jax.Array, elem: jax.Array, bytes_per_pred: int) -> jax.Array:
    """Direct access: the ``elem``-th packed entry -> 0-based predicate id."""
    bidx = elem * bytes_per_pred
    word = words[jnp.clip(bidx >> 2, 0, words.shape[0] - 1)]
    shift = ((bidx & 3) * 8).astype(jnp.uint32)
    mask = jnp.uint32((1 << (8 * bytes_per_pred)) - 1 if bytes_per_pred < 4
                      else 0xFFFFFFFF)
    return ((word >> shift) & mask).astype(jnp.int32)


def _gather_traced(
    pmeta: PredIndexMeta, index: PredIndex, rows: jax.Array, cap: int
) -> QueryResult:
    """jnp reference gather: rows int32[B] (0-based entity rows) -> the
    ``QueryResult`` contract over 0-based predicate ids (prefix-valid,
    dead lanes zeroed, overflow = list longer than ``cap``).

    The math is ``ref.pred_gather_ref`` / ``ref.pred_gather_dac_ref``
    (per ``pmeta.layout``) — one jnp source of truth; the Pallas kernels
    are the independent implementations checked against it.
    """
    from repro.kernels import ref  # deferred: core must import without pallas

    rows = jnp.clip(jnp.asarray(rows, jnp.int32), 0,
                    max(pmeta.n_subjects + pmeta.n_objects - 1, 0))
    if pmeta.layout == "dac":
        ids, valid, count, overflow = ref.pred_gather_dac_ref(
            rows, index.offsets, index.words, index.degs, index.flags,
            index.frank, levels=pmeta.levels,
            level_byte_start=pmeta.level_byte_start,
            flag_word_start=pmeta.flag_word_start,
            deg_width=pmeta.deg_width, rows_per_block=pmeta.rows_per_block,
            cap=cap,
        )
    else:
        ids, valid, count, overflow = ref.pred_gather_ref(
            rows, index.offsets, index.words,
            bytes_per_pred=pmeta.bytes_per_pred, cap=cap,
        )
    return QueryResult(ids=ids, valid=valid, count=count, overflow=overflow)


def gather_batch(
    pmeta: PredIndexMeta, index: PredIndex, rows, cap: int,
    backend: str | None = None,
) -> QueryResult:
    """Batched candidate-predicate gather (the ragged-gather launch layout).

    ``backend`` resolves exactly like ``k2forest.scan_batch_mixed``
    (ExecConfig / string / None): "pallas" runs the ``kernels.pred_gather``
    kernel, "jnp" the reference above; the decode follows ``pmeta.layout``.
    Bit-identical outputs across backends AND layouts
    (tests/test_pred_gather.py, tests/test_predindex.py).
    """
    from repro.kernels import ops  # deferred: core must import without pallas

    rows = jnp.asarray(rows, jnp.int32)
    be, interp = ops.resolve_exec(backend)
    if be == "pallas":
        ids, valid, count, overflow = ops.pred_gather_index(
            pmeta, index, rows, cap=cap, interpret=interp
        )
        return QueryResult(ids=ids, valid=valid, count=count, overflow=overflow)
    return _gather_traced(pmeta, index, rows, cap)


class PredScanResult(NamedTuple):
    """Pruned unbounded scan: per-candidate-predicate result lists.

    All ids 0-based (the ``k2forest`` convention; the patterns layer shifts).
    ``u_width`` candidate slots per query; ``pvalid`` marks live candidates.
    """

    preds: jax.Array  # int32[..., L] candidate predicate ids (0 where dead)
    pvalid: jax.Array  # bool[..., L]
    ids: jax.Array  # int32[..., L, cap]
    valid: jax.Array  # bool[..., L, cap]
    count: jax.Array  # int32[..., L]
    overflow: jax.Array  # bool[..., L] per-candidate scan overflow
    truncated: jax.Array  # bool[...] candidate list exceeded L (never when
    #   L >= pmeta.max_degree)


def scan_pruned_batch(
    meta: K2Meta, f: K2Forest, pmeta: PredIndexMeta, index: PredIndex,
    keys, axes, cap: int, u_width: int, backend: str | None = None,
) -> PredScanResult:
    """(S,?P,?O) / (?S,?P,O) batch via the index: scan candidates only.

    ``keys`` int32[B] 0-based subject (axes==0) or object (axes==1) ids;
    one flat ``scan_batch_mixed`` launch of B·u_width lanes replaces the
    B·P broadcast sweep.
    """
    keys = jnp.asarray(keys, jnp.int32)
    axes = jnp.asarray(axes, jnp.int32)
    b = keys.shape[0]
    rows = jnp.where(axes == 1, pmeta.n_subjects + keys, keys)
    g = gather_batch(pmeta, index, rows, u_width, backend)
    preds_f = jnp.where(g.valid, g.ids, 0).reshape(b * u_width)
    keys_f = jnp.repeat(keys, u_width)
    axes_f = jnp.repeat(axes, u_width)
    r = k2forest.scan_batch_mixed(meta, f, preds_f, keys_f, axes_f, cap, backend)
    valid = r.valid.reshape(b, u_width, cap) & g.valid[:, :, None]
    return PredScanResult(
        preds=jnp.where(g.valid, g.ids, 0),
        pvalid=g.valid,
        ids=jnp.where(valid, r.ids.reshape(b, u_width, cap), 0),
        valid=valid,
        count=jnp.where(g.valid, r.count.reshape(b, u_width), 0),
        overflow=r.overflow.reshape(b, u_width) & g.valid,
        truncated=g.overflow,
    )


def check_pruned_batch(
    meta: K2Meta, f: K2Forest, pmeta: PredIndexMeta, index: PredIndex,
    rows, cols, u_width: int, backend: str | None = None,
) -> QueryResult:
    """(S,?P,O) batch via the SP index: check candidates only.

    ``rows``/``cols`` int32[B] 0-based subject/object ids.  Returns the
    matching predicate ids (0-based, ascending, compacted to the front of
    ``u_width`` slots); ``overflow`` latches only if the candidate list
    itself was truncated.
    """
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    b = rows.shape[0]
    g = gather_batch(pmeta, index, rows, u_width, backend)
    preds_f = jnp.where(g.valid, g.ids, 0).reshape(b * u_width)
    hit = k2forest.check(
        meta, f, preds_f, jnp.repeat(rows, u_width), jnp.repeat(cols, u_width)
    ).reshape(b, u_width) & g.valid
    valid, count, _, (ids,) = jax.vmap(
        lambda v, a: _compact(v, u_width, a)
    )(hit, jnp.where(hit, g.ids, 0))
    return QueryResult(ids=ids, valid=valid, count=count, overflow=g.overflow)


class PredBitmap:
    """Tiny host-side entity -> predicate-set bitmap for the delta lane.

    The SP/OP candidate-predicate index above is static (built once with the
    forest) and is consulted only for the STATIC side of a dynamic store.
    Recent inserts are covered by this structure instead: one arbitrary-width
    Python-int bitmask per touched entity (1-based predicate p sets bit p-1),
    so the delta lane's unbounded-?P merges cost a dict lookup plus a
    popcount-sized decode — no device rebuild per write.
    """

    __slots__ = ("_bits",)

    def __init__(self) -> None:
        self._bits: dict[int, int] = {}

    def add(self, entity: int, pred: int) -> None:
        self._bits[entity] = self._bits.get(entity, 0) | (1 << (pred - 1))

    def preds_of(self, entity: int) -> np.ndarray:
        """Sorted 1-based predicate ids recorded for ``entity``."""
        w = self._bits.get(entity, 0)
        if not w:
            return np.empty(0, dtype=np.int64)
        out = []
        p = 1
        while w:
            if w & 1:
                out.append(p)
            w >>= 1
            p += 1
        return np.asarray(out, dtype=np.int64)

    def __contains__(self, entity: int) -> bool:
        return entity in self._bits

    def __len__(self) -> int:
        return len(self._bits)

    def entities(self):
        return self._bits.keys()
