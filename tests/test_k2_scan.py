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
