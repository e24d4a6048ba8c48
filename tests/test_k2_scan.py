"""Differential harness for the batched k²-scan Pallas kernel.

Three-way agreement on every case:

    kernels.k2_scan (interpret)  ==  kernels.ref.k2_scan_ref (jnp, scatter
    compaction)  ==  core.k2forest.scan_batch_mixed(backend="jnp") (vmapped
    traced-axis traversal)         — bit-exact, all four output arrays;

and each against the numpy dense-matrix oracle (tests/oracle.py) for the
capped-result contract.  Forest configs cover randomized matrices at several
heights, empty trees, full rows, the minimal single-cell matrix, and caps
straddling the true result count (overflow boundary); the sweep runs well
over 200 distinct (matrix, axis, key, cap) cases.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import k2forest
from repro.core.k2tree import K2Meta, hybrid_ks
from repro.kernels import ref

from oracle import (
    assert_results_identical,
    assert_scan_result,
    dense_from_coords,
    scan_truth,
)


def _forest(coords, side):
    meta = K2Meta(hybrid_ks(side))
    f, _ = k2forest.build_forest(coords, meta)
    return meta, f


def _run_all_backends(meta, f, preds, keys, axes, cap):
    """(pallas, jnp, ref) results for one query batch; asserts 3-way equality."""
    preds = jnp.asarray(preds, jnp.int32)
    keys = jnp.asarray(keys, jnp.int32)
    axes = jnp.asarray(axes, jnp.int32)
    r_pl = k2forest.scan_batch_mixed(meta, f, preds, keys, axes, cap,
                                     backend="pallas")
    r_jnp = k2forest.scan_batch_mixed(meta, f, preds, keys, axes, cap,
                                      backend="jnp")
    r_ref = ref.k2_scan_ref(
        meta, preds, keys, axes, f.t_words, f.t_rank, f.l_words,
        f.ones_before, f.level_start, cap=cap,
    )
    assert_results_identical(tuple(r_pl), tuple(r_jnp), "pallas-vs-jnp")
    assert_results_identical(tuple(r_pl), tuple(r_ref), "pallas-vs-ref")
    return r_pl


def _sweep(coords, side, caps, n_keys, seed, counter):
    """Run the 3-way differential + dense oracle over a (matrix, cap) grid."""
    rng = np.random.default_rng(seed)
    meta, f = _forest(coords, side)
    dense = dense_from_coords(coords, meta.side)
    P = len(coords)
    keys1 = np.unique(
        np.concatenate([[0, side - 1], rng.integers(0, side, n_keys)])
    ).astype(np.int32)
    # every key queried on both axes, predicates round-robin
    keys = np.repeat(keys1, 2)
    axes = np.tile(np.array([0, 1], np.int32), len(keys1))
    preds = (np.arange(len(keys)) % P).astype(np.int32)
    for cap in caps:
        r = _run_all_backends(meta, f, preds, keys, axes, cap)
        ids, valid = np.asarray(r.ids), np.asarray(r.valid)
        count, ovf = np.asarray(r.count), np.asarray(r.overflow)
        for i in range(len(keys)):
            truth = scan_truth(dense[preds[i]], int(keys[i]), int(axes[i]))
            assert_scan_result(
                ids[i], valid[i], count[i], ovf[i], truth, cap,
                label=f"side={side} cap={cap} pred={preds[i]} "
                      f"key={keys[i]} axis={axes[i]}",
            )
            counter[0] += 1


def test_k2_scan_randomized_sweep():
    """≥200 randomized (matrix, axis, key, cap) cases, 3-way + dense oracle."""
    counter = [0]
    rng = np.random.default_rng(7)
    # randomized forests at three tree heights / densities
    for side, n_preds, nnz_hi, caps, n_keys, seed in [
        (60, 4, 400, (8, 64), 40, 1),     # H=3, mixed densities
        (200, 3, 900, (16, 128), 30, 2),  # H=4
        (900, 2, 1500, (32,), 30, 3),     # H=5
    ]:
        coords = []
        for _ in range(n_preds):
            n = int(rng.integers(0, nnz_hi))
            coords.append((rng.integers(0, side, n), rng.integers(0, side, n)))
        _sweep(coords, side, caps, n_keys=n_keys, seed=seed, counter=counter)
    assert counter[0] >= 200, counter[0]


def test_k2_scan_empty_trees():
    """Empty forests: zero results, no overflow, on every backend."""
    side = 120
    empty = np.zeros(0, np.int64)
    counter = [0]
    _sweep([(empty, empty)] * 2, side, caps=(1, 16), n_keys=6, seed=4,
           counter=counter)
    meta, f = _forest([(empty, empty)], side)
    r = _run_all_backends(meta, f, [0, 0], [0, side - 1], [0, 1], 8)
    assert not np.asarray(r.valid).any()
    assert (np.asarray(r.count) == 0).all()
    assert not np.asarray(r.overflow).any()


def test_k2_scan_dead_lanes_and_lane_blocks():
    """Lanes with pred < 0 answer empty (no overflow, even at a cap below
    the root arity) on every backend, and a batch larger than one
    1024-lane SMEM block runs as padded blocks."""
    side = 100
    rng = np.random.default_rng(11)
    coords = [(rng.integers(0, side, 400), rng.integers(0, side, 400))
              for _ in range(2)]
    meta, f = _forest(coords, side)
    dense = dense_from_coords(coords, meta.side)
    q = 2100
    preds = np.full(q, -1)
    live = rng.choice(q - 1, 39, replace=False).tolist() + [q - 1]
    preds[live] = rng.integers(0, 2, len(live))
    keys = rng.integers(0, side, q)
    axes = rng.integers(0, 2, q)
    for cap in (2, 16):
        r = _run_all_backends(meta, f, preds, keys, axes, cap)
        dead = preds < 0
        assert not np.asarray(r.valid)[dead].any()
        assert not np.asarray(r.overflow)[dead].any()
        assert (np.asarray(r.count)[dead] == 0).all()
    for i in live:
        d = dense[preds[i]]
        truth = np.nonzero(d[keys[i]] if axes[i] == 0 else d[:, keys[i]])[0]
        got = np.asarray(r.ids[i])[np.asarray(r.valid[i])]
        assert got.tolist() == truth[: len(got)].tolist()
        assert bool(r.overflow[i]) or len(got) == len(truth)


def test_out_of_range_preds_are_dead():
    """Predicate ids past the forest answer empty on every traversal entry
    and backend: ids in the arena's padding rows, past its last row, and
    negative.  The kernel's own guard holds too when it is handed a row
    past the arena directly (on the chip that read would leave the array)."""
    from repro.kernels import ops

    side = 100
    rng = np.random.default_rng(12)
    coords = [(rng.integers(0, side, 400), rng.integers(0, side, 400))
              for _ in range(2)]
    meta, f = _forest(coords, side)
    rows = f.t_words.shape[0]
    assert f.n_preds == 2 and rows == 8  # one 8-row tile, 6 padding rows
    bad = jnp.asarray([2, 7, rows, rows + 1, 1000, 2**30, -5], jnp.int32)
    n = bad.shape[0]
    keys = jnp.asarray(rng.integers(0, side, n), jnp.int32)
    axes = jnp.asarray(np.arange(n) % 2, jnp.int32)
    for backend in ("pallas", "jnp"):
        r = k2forest.scan_batch_mixed(meta, f, bad, keys, axes, 16, backend)
        assert not np.asarray(r.valid).any() and not np.asarray(r.overflow).any()
        assert (np.asarray(r.count) == 0).all()
        pr = k2forest.range_scan_batch(meta, f, bad, 16, backend)
        assert not np.asarray(pr.valid).any() and not np.asarray(pr.overflow).any()
        x = k2forest.scan_rebind_batch(meta, f, bad, keys, axes, bad, axes,
                                       4, 4, backend)
        assert not np.asarray(x[1]).any() and not np.asarray(x[5]).any()
    assert not np.asarray(k2forest.check(meta, f, bad, keys, keys)).any()
    # the kernel itself, bypassing the dispatch's id check
    past = jnp.asarray([rows, rows + 9, 1000, -1], jnp.int32)
    ids, valid, count, ovf = ops.k2_scan_forest(
        meta, f, past, keys[:4], axes[:4], cap=16, interpret=True)
    assert not np.asarray(valid).any() and not np.asarray(ovf).any()


def test_kernels_refuse_untiled_arenas():
    """An arena that is not whole (8, 128) tiles is refused by name, never
    read past its end."""
    from repro.kernels import k2_scan

    side = 64
    meta, f = _forest([(np.arange(side), np.arange(side))], side)
    q = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="k2_scan: .* not whole"):
        k2_scan.k2_scan(meta, q, q, q, f.t_words[:5], f.t_rank[:5],
                        f.l_words[:5], f.ones_before[:5], f.level_start[:5],
                        cap=8, interpret=True)


def test_forest_arena_is_tiled():
    """``build_forest`` lays every arena out in whole (8, 128) tiles and
    keeps the logical predicate count in ``nnz``."""
    side = 300
    rng = np.random.default_rng(13)
    coords = [(rng.integers(0, side, 500), rng.integers(0, side, 500))
              for _ in range(11)]
    meta, f = _forest(coords, side)
    assert f.n_preds == 11
    for a in f[:-1]:
        assert a.shape[0] == 16 and a.shape[1] % 128 == 0, a.shape
    assert not np.asarray(f.t_words[11:]).any()  # padding rows: empty trees


def test_k2_scan_full_rows():
    """A fully-populated matrix: every scan returns a full line (or caps)."""
    side = 64
    rr = np.repeat(np.arange(side), side)
    cc = np.tile(np.arange(side), side)
    counter = [0]
    _sweep([(rr, cc)], side, caps=(16, 64, 100), n_keys=5, seed=5,
           counter=counter)
    meta, f = _forest([(rr, cc)], side)
    r = _run_all_backends(meta, f, [0], [3], [0], 64)
    assert int(r.count[0]) == side
    assert not bool(r.overflow[0])
    assert (np.asarray(r.ids[0]) == np.arange(side)).all()


def test_k2_scan_single_cell_matrix():
    """Minimal geometry: one 1-cell in the smallest (side-2) matrix."""
    side = 2
    counter = [0]
    _sweep([(np.array([1]), np.array([0]))], side, caps=(1, 2, 4), n_keys=2,
           seed=6, counter=counter)
    meta, f = _forest([(np.array([1]), np.array([0]))], side)
    assert meta.n_levels == 1  # the L-only tree exercises the H==1 path
    r = _run_all_backends(meta, f, [0, 0, 0, 0], [1, 0, 0, 1], [0, 0, 1, 1], 2)
    assert np.asarray(r.count).tolist() == [1, 0, 1, 0]


@pytest.mark.parametrize("cap_delta", [-1, 0, 1])
def test_k2_scan_cap_overflow_boundary(cap_delta):
    """cap straddling the exact result count: count/overflow semantics."""
    side = 64
    n = 40  # 1-cells in row 0
    rng = np.random.default_rng(8)
    cols = np.sort(rng.choice(side, n, replace=False))
    meta, f = _forest([(np.zeros(n, np.int64), cols)], side)
    cap = n + cap_delta
    r = _run_all_backends(meta, f, [0], [0], [0], cap)
    truth = cols.astype(np.int32)
    assert_scan_result(r.ids[0], r.valid[0], r.count[0], r.overflow[0],
                       truth, cap, label=f"cap_delta={cap_delta}")
    if cap_delta < 0:
        assert bool(r.overflow[0])
        assert int(r.count[0]) == cap
    else:
        assert not bool(r.overflow[0])
        assert int(r.count[0]) == n


def test_k2_scan_cap_below_root_arity():
    """cap < k0 truncates the INITIAL frontier and must latch overflow."""
    side = 64  # k0 == 4
    rr = np.repeat(np.arange(side), side)
    cc = np.tile(np.arange(side), side)
    meta, f = _forest([(rr, cc)], side)
    r = _run_all_backends(meta, f, [0], [5], [0], 2)
    assert bool(r.overflow[0])
    assert np.asarray(r.ids[0]).tolist() == [0, 1]  # lowest ids survive


def test_k2_scan_mixed_axes_one_batch():
    """Row and col scans of the same key in one batch agree with separate."""
    side = 100
    rng = np.random.default_rng(9)
    coords = [(rng.integers(0, side, 500), rng.integers(0, side, 500))]
    meta, f = _forest(coords, side)
    dense = dense_from_coords(coords, meta.side)[0]
    keys = np.array([17, 17, 42, 42], np.int32)
    axes = np.array([0, 1, 0, 1], np.int32)
    r = _run_all_backends(meta, f, np.zeros(4, np.int32), keys, axes, 64)
    for i in range(4):
        truth = scan_truth(dense, int(keys[i]), int(axes[i]))
        got = np.asarray(r.ids[i])[np.asarray(r.valid[i])]
        assert (got == truth).all()


# ---------------------------------------------------------------------------
# Wide levels: frontiers that fan out over many tiles of the arena
# ---------------------------------------------------------------------------

WIDE_SIDE = 1 << 12  # ks (4, 4, 4, 4, 4, 2, 2): five dense k = 4 levels


@pytest.fixture(scope="module")
def wide():
    """A side-2^12 forest whose scans fan out over tens of tiles a level
    (predicate 0: 40,000 cells), beside a sparse tree and an empty one."""
    rng = np.random.default_rng(21)
    coords = [(rng.integers(0, WIDE_SIDE, n), rng.integers(0, WIDE_SIDE, n))
              for n in (40_000, 300, 0)]
    meta, f = _forest(coords, WIDE_SIDE)
    return coords, meta, f


def _wide_lanes(q: int, live: int, seed: int):
    """``q`` lanes, ``live`` of them on the dense tree or the sparse or empty
    one, spread over the batch and ending on a live lane; the rest dead."""
    rng = np.random.default_rng(seed)
    preds = np.full(q, -1, np.int32)
    at = np.append(rng.choice(q - 1, live - 1, replace=False), q - 1)
    preds[at] = rng.choice([0, 0, 0, 1, 2], live)
    keys = rng.integers(0, WIDE_SIDE, q).astype(np.int32)
    axes = rng.integers(0, 2, q).astype(np.int32)
    return preds, keys, axes


def _line_truth(coords, pred: int, key: int, axis: int) -> np.ndarray:
    rows, cols = coords[pred]
    hit = (rows == key) if axis == 0 else (cols == key)
    return np.unique((cols if axis == 0 else rows)[hit]).astype(np.int32)


def level_tiles(meta, f, pred: int, key: int, axis: int) -> int:
    """The most (8, 128) tiles of ``t_words`` that one inner level of a
    lane's scan reads ranks from, by a numpy walk of its whole frontier."""
    tw, tr = np.asarray(f.t_words), np.asarray(f.t_rank)
    ob, ls = np.asarray(f.ones_before), np.asarray(f.level_start)
    ks, row = meta.ks, axis == 0
    digits, rem = [], key
    for sub in meta.subsides:
        digits.append(rem // sub)
        rem %= sub

    def bit(pos):
        return (int(tw[pred, pos >> 5]) >> (pos & 31)) & 1

    front = [p for p in (digits[0] * ks[0] + j if row else j * ks[0] + digits[0]
                         for j in range(ks[0])) if bit(p)]
    widest = 0
    for lvl in range(meta.n_levels - 2):
        widest = max(widest, len({pos >> 12 for pos in front}))  # word >> 7
        k, d, nxt = ks[lvl + 1], digits[lvl + 1], []
        for pos in front:
            w = pos >> 5
            rank = int(tr[pred, w]) + bin(
                int(tw[pred, w]) & ((1 << (pos & 31)) - 1)).count("1")
            cb0 = int(ls[pred, lvl + 1]) + (rank - int(ob[pred, lvl])) * k * k
            nxt += [c for c in (cb0 + (d * k + ch if row else ch * k + d)
                                for ch in range(k)) if bit(c)]
        front = nxt
    return widest


WIDE_CASES = {
    # one level's reads meet tens of tiles
    "many_tiles": dict(q=12, live=10, cap=1024),
    # cap 64 overflows mid-level
    "overflow": dict(q=12, live=10, cap=64),
    # two 1024-lane grid steps, dead lanes among live ones, cap below k0
    "two_blocks_cap_below_root": dict(q=1100, live=16, cap=2),
}


@pytest.mark.parametrize("case", sorted(WIDE_CASES))
def test_k2_scan_wide_levels(wide, case):
    """Scans whose levels fan out over many tiles of the arena: bit-exact
    against both jnp traversals and the numpy truth."""
    coords, meta, f = wide
    c = WIDE_CASES[case]
    cap = c["cap"]
    preds, keys, axes = _wide_lanes(c["q"], c["live"], seed=len(case))
    r = _run_all_backends(meta, f, preds, keys, axes, cap)
    for i in np.flatnonzero(preds >= 0):
        assert_scan_result(
            r.ids[i], r.valid[i], r.count[i], r.overflow[i],
            _line_truth(coords, preds[i], keys[i], axes[i]), cap,
            label=f"{case} lane {i}")
    assert not np.asarray(r.valid)[preds < 0].any()
    ovf = np.asarray(r.overflow)
    if case == "many_tiles":
        assert max(level_tiles(meta, f, int(p), int(k), int(x))
                   for p, k, x in zip(preds, keys, axes) if p == 0) >= 10
        assert not ovf.any()
    else:
        assert ovf.any()


@pytest.mark.parametrize("cap_y", [16, 1024])
def test_k2_scan_rebind_wide_levels(wide, cap_y):
    """The fused scan → rebind takes the same walk: bit-exact against the
    jnp composition on the wide forest, with and without Y overflow."""
    _, meta, f = wide
    rng = np.random.default_rng(cap_y)
    q = 4
    args = (np.array([0, 1, -1, 0]), rng.integers(0, WIDE_SIDE, q),
            rng.integers(0, 2, q), np.array([0, 0, 0, 1]),
            rng.integers(0, 2, q))
    o_pl = k2forest.scan_rebind_batch(meta, f, *args, 8, cap_y, "pallas")
    o_j = k2forest.scan_rebind_batch(meta, f, *args, 8, cap_y, "jnp")
    assert_results_identical(tuple(o_pl), tuple(o_j), "rebind pallas-vs-jnp")
    assert int(np.asarray(o_pl[2]).max()) > 1


@pytest.mark.parametrize("cap", [64, 1024])
def test_k2_range_wide_levels(wide, cap):
    """The full-matrix walk shares the scan's level loop: bit-exact against
    the jnp traversal and reference on the wide forest, overflowing on the
    dense tree at either cap and whole on the sparse tree at 1024."""
    coords, meta, f = wide
    preds = jnp.asarray([0, 1, -1, 2], jnp.int32)
    r = k2forest.range_scan_batch(meta, f, preds, cap, backend="pallas")
    r_jnp = k2forest.range_scan_batch(meta, f, preds, cap, backend="jnp")
    r_ref = ref.k2_range_ref(meta, preds, f.t_words, f.t_rank, f.l_words,
                             f.ones_before, f.level_start, cap=cap)
    assert_results_identical(tuple(r), tuple(r_jnp), "range pallas-vs-jnp")
    assert_results_identical(tuple(r), tuple(r_ref), "range pallas-vs-ref")
    count, ovf = np.asarray(r.count), np.asarray(r.overflow)
    sparse = len(set(zip(*coords[1])))
    assert ovf[0] and count[0] == cap
    assert ovf[1] == (cap < sparse) and count[1] == min(cap, sparse)
    assert count[2] == 0 and count[3] == 0 and not ovf[2:].any()


@pytest.mark.parametrize("mode", ["eager", "on_wait"])
def test_kernels_wait_on_every_dma(wide, mode, capsys):
    """Under the TPU interpreter, each kernel that reads arena tiles and
    writes records by DMA answers as the default interpreter does
    when a DMA lands only once it is waited on (a read before its wait
    would see a stale tile), and leaves no DMA outstanding at exit when
    each lands as it starts (the interpreter reports a semaphore left
    counting)."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import k2_range, k2_scan

    _, meta, f = wide
    arrs = (f.t_words, f.t_rank, f.l_words, f.ones_before, f.level_start)
    how = pltpu.InterpretParams(dma_execution_mode=mode)
    # a column of the dense tree (many tiles a level), a dead lane, and
    # a row of the sparse tree; cap 64 and 16 overflow mid-level
    p, k, x = (jnp.asarray(v, jnp.int32) for v in ([0, -1, 1], [77, 5, 300],
                                                   [1, 0, 0]))
    for run in (
        lambda i: k2_scan.k2_scan(meta, p, k, x, *arrs, cap=64, interpret=i),
        lambda i: k2_scan.k2_scan_rebind(meta, p, k, x, p * 0 + 1, x, *arrs,
                                         cap_x=2, cap_y=16, interpret=i),
        lambda i: k2_range.k2_range(meta, jnp.asarray([1, -1, 2]), *arrs,
                                    cap=16, interpret=i),
    ):
        assert_results_identical(tuple(run(how)), tuple(run(True)), mode)
    assert "at kernel exit" not in capsys.readouterr().out
