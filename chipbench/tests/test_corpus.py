"""The corpus generator matches ``rdf.generate``, and the reference answers
the serve-IR ops."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import corpus, traffic


@pytest.mark.parametrize("kw", [
    dict(n_triples=5000, n_subjects=400, n_preds=20, n_objects=600, seed=3),
    dict(n_triples=8000, n_subjects=700, n_preds=394, n_objects=900, seed=2**31 + 9),
    dict(n_triples=3000, n_subjects=300, n_preds=50, n_objects=200,
         preds_per_subject=6, so_frac=0.5, seed=1),
])
def test_generate_equals_rdf_generate(kw):
    from repro.data import rdf

    want = rdf.generate(**kw)
    got = corpus.generate(**kw)
    np.testing.assert_array_equal(got.ids, want.ids)
    assert (got.n_so, got.n_subjects, got.n_objects, got.n_preds) == (
        want.n_so, want.n_subjects, want.n_objects, want.n_preds)


def test_config_scale_matches_generate_like():
    """geonames' config is ``generate_like("geonames", 9,415,253)``'s scale."""
    from repro.data import rdf

    from chipbench import spec

    cfg = spec.load_cell("geonames.lookup.open").config
    d = rdf.PAPER_DATASETS["geonames"]
    assert (cfg["subjects"], cfg["preds"], cfg["objects"]) == (
        d["subjects"], d["preds"], d["objects"])


def test_reference_answers():
    ids = np.array([[1, 1, 5], [1, 2, 5], [1, 2, 7], [2, 1, 5]])
    ref = corpus.Reference(ids[::-1])
    assert ref.answer(traffic.OP_CHECK, 1, 2, 7) is True
    assert ref.answer(traffic.OP_CHECK, 2, 2, 7) is False
    assert ref.answer(traffic.OP_ROW, 1, 2, 0).tolist() == [5, 7]
    assert ref.answer(traffic.OP_COL, 0, 1, 5).tolist() == [1, 2]
    assert ref.answer(traffic.OP_S_ANY_O, 1, 0, 5).tolist() == [1, 2]
    got = ref.answer(traffic.OP_S_ANY_ANY, 1, 0, 0)
    assert {k: v.tolist() for k, v in got.items()} == {1: [5], 2: [5, 7]}
    got = ref.answer(traffic.OP_ANY_ANY_O, 0, 0, 5)
    assert {k: v.tolist() for k, v in got.items()} == {1: [1, 2], 2: [1]}


def test_reference_agrees_with_the_smoke_reference():
    """The copy answers as ``chip_smoke.Reference`` does on a corpus."""
    import sys

    from chipbench import spec

    sys.path.insert(0, str(spec.ROOT))
    import chip_smoke

    c = corpus.generate(3000, n_subjects=200, n_preds=12, n_objects=300, seed=4)
    mine, theirs = corpus.Reference(c.ids), chip_smoke.Reference(c.ids)
    rng = np.random.default_rng(0)
    for op in range(6):
        for s, p, o in c.ids[rng.integers(0, c.n_triples, 20)]:
            assert corpus.same(mine.answer(op, s, p, o), theirs.answer(op, s, p, o))


def test_same():
    assert corpus.same({1: np.array([5])}, {1: [5]})
    assert not corpus.same(True, np.array([1]))
    assert not corpus.same(np.array([5, 7]), np.array([5]))
    assert not corpus.same({1: np.array([5])}, {2: np.array([5])})
