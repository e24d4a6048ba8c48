"""Compile the traversal kernels for a described TPU v5e, at real widths.

Nothing runs: ``jax.jit(...).lower(...).compile()`` against a v5e topology
described by the installed TPU compiler proves that Mosaic accepts each
kernel at the geonames Table-1 shapes (the forest, the SP/OP index and a
serve-step batch), which interpret mode cannot show.  The shapes come from
``rdf.generate_like("geonames", 9_415_253)``; the store itself is not built.
The topology is described inside a fixture, so collection never loads the
TPU library, and the tests skip where it cannot be described.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.bitvec import TILE_COLS, TILE_ROWS, round_up
from repro.core.k2tree import K2Meta, hybrid_ks
from repro.kernels import k2_range, k2_scan, pred_gather

# geonames, Table 1: 20 predicates, |O| = 3,031,664 sets the matrix side
N_PREDS = 20
META = K2Meta(hybrid_ks(3_031_664))
T_WORDS, L_WORDS = 868_940, 96_960  # largest predicate's T and L, in words
N_ROWS = 2_203_561 + 3_031_664  # SP rows then OP rows
SERVE_LANES = 256 * 15  # a 256-query batch: bounded lanes + 14 ?P slots each
CAP = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _s(sh, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _vec(sh, n, dtype=jnp.int32):
    """A 1-D index array as the store builds it: whole (8, 128) tiles."""
    return _s(sh, (round_up(n, TILE_ROWS * TILE_COLS),), dtype)


def _forest(sh):
    """The forest arena as ``build_forest`` lays it out: predicate rows and
    word columns rounded up to whole (8, 128) tiles."""
    rows = round_up(N_PREDS, TILE_ROWS)
    wt, wl = round_up(T_WORDS, TILE_COLS), round_up(L_WORDS, TILE_COLS)
    return (
        _s(sh, (rows, wt), jnp.uint32), _s(sh, (rows, wt)),
        _s(sh, (rows, wl), jnp.uint32),
        _s(sh, (rows, TILE_COLS)), _s(sh, (rows, TILE_COLS)),
    )


def _compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_k2_scan_compiles(one_chip):
    q = _s(one_chip, (SERVE_LANES,))
    _compiles(
        lambda p, k, a, *f: k2_scan.k2_scan(META, p, k, a, *f, cap=CAP),
        q, q, q, *_forest(one_chip),
    )


def test_k2_range_compiles(one_chip):
    _compiles(
        lambda p, *f: k2_range.k2_range(META, p, *f, cap=CAP),
        _s(one_chip, (N_PREDS,)), *_forest(one_chip),
    )


def test_k2_scan_rebind_compiles(one_chip):
    q = _s(one_chip, (256,))
    _compiles(
        lambda a, b, c, d, e, *f: k2_scan.k2_scan_rebind(
            META, a, b, c, d, e, *f, cap_x=64, cap_y=256,
        ),
        q, q, q, q, q, *_forest(one_chip),
    )


# the geonames index as built (max degree 14, one DAC level, 4-bit degrees)
@pytest.mark.parametrize("layout", ["dac", "fixed"])
def test_pred_gather_compiles(one_chip, layout):
    rows = _s(one_chip, (256,))
    words = _vec(one_chip, 4_287_440, jnp.uint32)
    if layout == "dac":
        one = _vec(one_chip, 1, jnp.uint32)
        _compiles(
            lambda r, anc, w, dg, fl, fr: pred_gather.pred_gather_dac(
                r, anc, w, dg, fl, fr, levels=1, level_byte_start=(0,),
                flag_word_start=(), deg_width=4, rows_per_block=32, cap=14,
            ),
            rows, _vec(one_chip, 163_601), words,
            _vec(one_chip, 654_404, jnp.uint32), one, _vec(one_chip, 1),
        )
    else:
        _compiles(
            lambda r, off, w: pred_gather.pred_gather(
                r, off, w, bytes_per_pred=1, cap=14,
            ),
            rows, _vec(one_chip, N_ROWS + 1), words,
        )


def test_pred_gather_dac_multilevel_compiles(one_chip):
    """A DAC index whose gaps need a second chunk level (flags + ranks)."""
    rows = _s(one_chip, (256,))
    _compiles(
        lambda r, anc, w, dg, fl, fr: pred_gather.pred_gather_dac(
            r, anc, w, dg, fl, fr, levels=2, level_byte_start=(0, 4_000_000),
            flag_word_start=(0,), deg_width=8, rows_per_block=16, cap=64,
        ),
        rows, _vec(one_chip, 327_202), _vec(one_chip, 1_100_000, jnp.uint32),
        _vec(one_chip, 1_308_808, jnp.uint32),
        _vec(one_chip, 125_000, jnp.uint32), _vec(one_chip, 125_000),
    )


def test_serve_step_scans_compile_under_their_names(one_chip):
    """The serve step over the geonames shapes, with the unbounded lanes:
    its two scan launches compile as ``k2_scan_bound`` (row and column
    lanes) and ``k2_scan_u`` (the u-candidate scans), the names a profile
    shows them under."""
    from repro.core import engine
    from repro.core.k2forest import K2Forest
    from repro.core.predindex import PredIndex, PredIndexMeta
    from repro.core.query import ExecConfig

    pmeta = PredIndexMeta(
        n_subjects=2_203_561, n_objects=3_031_664, n_preds=N_PREDS,
        bytes_per_pred=1, max_degree=14, layout="dac", levels=1,
        level_byte_start=(0,), flag_word_start=(), deg_width=4,
        rows_per_block=32,
    )
    step = engine.make_serve_step(
        META, CAP, backend=ExecConfig(backend="pallas", interpret=False),
        pmeta=pmeta,
    )
    q = _s(one_chip, (256,))
    index = PredIndex(
        offsets=_vec(one_chip, 163_601),
        words=_vec(one_chip, 4_287_440, jnp.uint32),
        degs=_vec(one_chip, 654_404, jnp.uint32),
        flags=_vec(one_chip, 1, jnp.uint32), frank=_vec(one_chip, 1),
    )
    text = step.lower(
        K2Forest(*_forest(one_chip), nnz=_s(one_chip, (N_PREDS,))),
        engine.ServeBatch(op=q, s=q, p=q, o=q), index,
    ).compile().as_text()
    assert "%k2_scan_bound." in text and "%k2_scan_u." in text
    assert "%k2_scan." not in text
