"""Differential harness for the ``pred_gather`` ragged-gather kernels:
Pallas (interpret) vs the jnp refs (``ref.pred_gather_ref`` /
``ref.pred_gather_dac_ref``) vs ``predindex._gather_traced`` vs the
fixed-width baseline vs a numpy oracle — over real ``predindex.build``
stores so BOTH on-device layouts ("dac" multi-level chunks + flag bitmaps,
"fixed" byte-packed) are exercised on the same lists.

Degree shapes covered: degree-0 entities, singletons, random mid-degree
rows, a max-degree hub subject AND hub object, and (with ``n_preds`` large)
gaps > 255 so the DAC payload goes multi-level.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import predindex
from repro.kernels import ops, pred_gather, ref

from oracle import assert_scan_result, assert_results_identical

SUBJ = 48
OBJ = 16
R = SUBJ + OBJ  # entity rows in the shared SP/OP arena

LAYOUTS = ("dac", "fixed")


def _random_store(rng, n_preds: int, *, hub_degree: int | None = None):
    """Random per-subject sorted predicate lists -> a real BuiltPredIndex.

    Subject 1 is forced empty (degree 0) and subject 2 is a hub at
    ``hub_degree`` (default min(n_preds, 40)); every triple reuses object
    ids 1..OBJ so the OP half gets hub objects for free.
    """
    hub = min(n_preds, 40) if hub_degree is None else hub_degree
    triples = []
    for s in range(1, SUBJ + 1):
        if s == 1:
            continue  # degree-0 entity
        if s == 2:
            d = hub
        else:
            kind = rng.integers(0, 4)
            d = 0 if kind == 0 else int(rng.integers(1, min(n_preds, 18) + 1))
        if d == 0:
            continue
        preds = np.sort(rng.choice(n_preds, d, replace=False)) + 1
        objs = rng.integers(1, OBJ + 1, d)
        for p, o in zip(preds, objs):
            triples.append((s, int(p), int(o)))
    ids = np.asarray(triples, np.int64).reshape(-1, 3)
    return predindex.build(
        ids, n_subjects=SUBJ, n_objects=OBJ, n_preds=n_preds
    )


def _kernel_call(bi, layout, rows, cap):
    dev, meta = bi.select(layout)
    if layout == "dac":
        return pred_gather.pred_gather_dac(
            jnp.asarray(rows), dev.offsets, dev.words, dev.degs, dev.flags,
            dev.frank, levels=meta.levels,
            level_byte_start=meta.level_byte_start,
            flag_word_start=meta.flag_word_start, deg_width=meta.deg_width,
            rows_per_block=meta.rows_per_block, cap=cap,
            interpret=True,
        )
    return pred_gather.pred_gather(
        jnp.asarray(rows), dev.offsets, dev.words,
        bytes_per_pred=meta.bytes_per_pred, cap=cap,
        interpret=True,
    )


def _ref_call(bi, layout, rows, cap):
    dev, meta = bi.select(layout)
    if layout == "dac":
        return ref.pred_gather_dac_ref(
            rows, dev.offsets, dev.words, dev.degs, dev.flags, dev.frank,
            levels=meta.levels, level_byte_start=meta.level_byte_start,
            flag_word_start=meta.flag_word_start, deg_width=meta.deg_width,
            rows_per_block=meta.rows_per_block, cap=cap,
        )
    return ref.pred_gather_ref(
        rows, dev.offsets, dev.words, bytes_per_pred=meta.bytes_per_pred,
        cap=cap,
    )


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("n_preds", [40, 3000])  # 1-byte and 2-byte widths
@pytest.mark.parametrize("cap", [4, 32])
def test_pred_gather_kernel_vs_refs(n_preds, cap, layout):
    rng = np.random.default_rng(n_preds + cap)
    for rep in range(4):
        bi = _random_store(rng, n_preds)
        rows = rng.integers(0, R, 64).astype(np.int32)
        rows[:2] = (0, 1)  # force the degree-0 entity and the hub into view
        kout = _kernel_call(bi, layout, rows, cap)
        rout = _ref_call(bi, layout, rows, cap)
        tout = predindex._gather_traced(
            bi.select(layout)[1], bi.select(layout)[0], rows, cap
        )
        assert_results_identical(tuple(kout), tuple(rout), f"kernel-vs-ref[{rep}]")
        assert_results_identical(
            tuple(kout), tuple(tout), f"kernel-vs-traced[{rep}]"
        )
        ids, valid, count, ovf = (np.asarray(a) for a in kout)
        for i, r_ in enumerate(rows):
            truth = np.asarray(bi.host_list(int(r_)), np.int32)
            assert_scan_result(
                ids[i], valid[i], count[i], ovf[i], truth, cap,
                f"oracle[{rep},{i}]",
            )


@pytest.mark.parametrize("cap", [8, 64])
def test_pred_gather_dac_multi_level(cap):
    """Gaps > 255 (and > 65535): the DAC payload goes multi-level and the
    flag-bitmap rank walk is on the decode path."""
    rng = np.random.default_rng(99)
    bi = _random_store(rng, 70000, hub_degree=48)
    assert bi.meta.levels >= 2, bi.meta  # the whole point of this test
    rows = rng.integers(0, R, 64).astype(np.int32)
    rows[:2] = (0, 1)
    kout = _kernel_call(bi, "dac", rows, cap)
    rout = _ref_call(bi, "dac", rows, cap)
    fout = _kernel_call(bi, "fixed", rows, cap)
    assert_results_identical(tuple(kout), tuple(rout), "kernel-vs-ref")
    assert_results_identical(tuple(kout), tuple(fout), "dac-vs-fixed")
    ids, valid, count, ovf = (np.asarray(a) for a in kout)
    for i, r_ in enumerate(rows):
        truth = np.asarray(bi.host_list(int(r_)), np.int32)
        assert_scan_result(
            ids[i], valid[i], count[i], ovf[i], truth, cap, f"oracle[{i}]"
        )


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_layouts_bit_identical(backend):
    """The compressed layout is invisible to callers: gather_batch output
    over "dac" == over "fixed", on both traversal backends."""
    rng = np.random.default_rng(7)
    bi = _random_store(rng, 300)
    rows = rng.integers(0, R, 32).astype(np.int32)
    out = {}
    for layout in LAYOUTS:
        dev, meta = bi.select(layout)
        out[layout] = predindex.gather_batch(meta, dev, rows, 16, backend)
    assert_results_identical(
        tuple(out["dac"]), tuple(out["fixed"]), f"layout-flip[{backend}]"
    )


@pytest.mark.parametrize("layout", LAYOUTS)
def test_ops_entry_pads_and_clips(layout):
    """ops.pred_gather_index: non-multiple batch sizes + out-of-range rows."""
    rng = np.random.default_rng(0)
    bi = _random_store(rng, 40)
    dev, meta = bi.select(layout)
    rows = np.array([0, R - 1, 5, -3, R + 9], np.int32)  # odd length + OOR
    ids, valid, count, ovf = ops.pred_gather_index(meta, dev, rows, cap=8)
    assert ids.shape == (5, 8)
    clipped = np.clip(rows, 0, R - 1)
    for i, r_ in enumerate(clipped):
        truth = np.asarray(bi.host_list(int(r_)), np.int32)
        assert_scan_result(
            np.asarray(ids[i]), np.asarray(valid[i]), int(count[i]),
            bool(ovf[i]), truth, 8, f"row{i}",
        )


@pytest.mark.parametrize("layout", LAYOUTS)
def test_gather_batch_backend_parity(monkeypatch, layout):
    """predindex.gather_batch honors the env flag and both backends agree."""
    rng = np.random.default_rng(5)
    bi = _random_store(rng, 40)
    dev, meta = bi.select(layout)
    rows = rng.integers(0, R, 32).astype(np.int32)
    out = {}
    for be in ("jnp", "pallas"):
        monkeypatch.setenv("REPRO_SCAN_BACKEND", be)
        out[be] = predindex.gather_batch(meta, dev, rows, 16)
    assert_results_identical(tuple(out["jnp"]), tuple(out["pallas"]), "env-flip")


def test_measured_bits_near_analytic():
    """The DAC layout is real: measured device bits for the index land
    within 1.25x of the analytic DAC(b=8) figure plus the (cheap)
    compressed row-pointer side."""
    rng = np.random.default_rng(11)
    bi = _random_store(rng, 40)
    measured_payload = bi.stats.payload_bits
    # analytic counts 9 bits per chunk (8 + flag); measured stores 8-bit
    # chunks word-padded + word-aligned flag bitmaps + their rank blocks
    assert measured_payload <= 1.25 * bi.stats.dac_bits + 3 * 32
    # and the whole measured index is far below the fixed-width fallback
    total = bi.stats.payload_bits + bi.stats.offsets_bits
    fixed = bi.stats.fixed_payload_bits + bi.stats.fixed_offsets_bits
    assert total < fixed
