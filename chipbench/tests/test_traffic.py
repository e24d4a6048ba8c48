"""The one generator: op shares, anchors, tenants and arrivals from a seed."""

from __future__ import annotations

import numpy as np

from chipbench import corpus, spec, traffic


def _ids():
    return corpus.generate(20000, n_subjects=2000, n_preds=20, n_objects=3000,
                           seed=5).ids


def test_draw_follows_the_mix():
    ids = _ids()
    keys = {tuple(r) for r in ids.tolist()}
    for cell in ("dbtune-9m.describe.closed", "geonames.lookup.open"):
        mix = spec.load_cell(cell).mix
        reqs = traffic.draw(ids, mix, 8000, np.random.default_rng(1))
        total = sum(mix["ops"].values())
        for name, weight in mix["ops"].items():
            got = np.mean(reqs[:, traffic.OP] == traffic.OPS[name])
            assert abs(got - weight / total) < 0.02, name
        unb = reqs[:, traffic.OP] >= traffic.OP_S_ANY_ANY
        assert np.all(reqs[unb, traffic.P] == 0)
        assert np.all(reqs[~unb, traffic.P] > 0)
        # anchors are triples of the corpus: the bound terms of each request
        s_o = {(s, o) for s, _, o in keys}
        for r in reqs[:200].tolist():
            if r[traffic.OP] < traffic.OP_S_ANY_ANY:
                assert (r[traffic.S], r[traffic.P], r[traffic.O]) in keys
            else:
                assert (r[traffic.S], r[traffic.O]) in s_o
        w = np.bincount(reqs[:, traffic.TENANT], minlength=mix["tenants"]) / len(reqs)
        assert np.all(np.diff(w) < 0.01) and w[0] > 2 * w[-1]


def test_anchor_rows_do_not_repeat():
    ids = _ids()
    mix = spec.load_cell("geonames.lookup.open").mix
    rng = np.random.default_rng(3)
    rows = ids[rng.choice(ids.shape[0], size=5000, replace=False)]
    assert len(np.unique(rows, axis=0)) == 5000
    reqs = traffic.draw(ids, mix, 5000, np.random.default_rng(3))
    assert len(np.unique(reqs[:, traffic.S:], axis=0)) == 5000


def test_same_seed_same_load():
    ids = _ids()
    mix = spec.load_cell("geonames.lookup.open").mix
    a = traffic.draw(ids, mix, 100, traffic.rngs(2**31 + 77)[1])
    b = traffic.draw(ids, mix, 100, traffic.rngs(2**31 + 77)[1])
    c = traffic.draw(ids, mix, 100, traffic.rngs(2**31 + 78)[1])
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_every_seed_offers_the_same_count():
    for seed in (1, 2, 2**33):
        due = traffic.poisson_arrivals(4800, 10, np.random.default_rng(seed))
        assert len(due) == 48000
        assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 10
        gaps = np.diff(due)
        assert abs(gaps.mean() - 1 / 4800) < 1e-5
        assert abs(gaps.std() / gaps.mean() - 1) < 0.05  # exponential gaps
