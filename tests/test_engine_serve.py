"""Serve-path semantics: ServeResult cap/overflow, pad_preds inertness,
check-lane masking in ``_serve_local`` — on both scan backends."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import engine as eng, k2triples
from repro.data import rdf


@pytest.fixture(scope="module")
def store():
    ds = rdf.generate(3000, n_subjects=120, n_preds=6, n_objects=150, seed=11)
    st = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    return st, ds


def _truth(ds):
    return set(map(tuple, ds.ids.tolist()))


def _batch(ops, s, p, o):
    return eng.ServeBatch(
        op=jnp.asarray(ops, jnp.int32), s=jnp.asarray(s, jnp.int32),
        p=jnp.asarray(p, jnp.int32), o=jnp.asarray(o, jnp.int32),
    )


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_serve_local_matches_truth(store, backend):
    st, ds = store
    T = _truth(ds)
    ids = ds.ids[:48]
    ops = np.arange(48) % 3
    q = _batch(ops, ids[:, 0], ids[:, 1], ids[:, 2])
    r = eng._serve_local(st.meta, st.forest, q, cap=256, backend=backend)
    hit, rids, valid = np.asarray(r.hit), np.asarray(r.ids), np.asarray(r.valid)
    for i, (s_, p_, o_) in enumerate(map(tuple, ids.tolist())):
        if ops[i] == eng.OP_CHECK:
            assert hit[i]  # the triple exists by construction
        elif ops[i] == eng.OP_ROW:
            exp = sorted(oo for (ss, pp, oo) in T if ss == s_ and pp == p_)
            assert rids[i][valid[i]].tolist() == exp, i
        else:
            exp = sorted(ss for (ss, pp, oo) in T if pp == p_ and oo == o_)
            assert rids[i][valid[i]].tolist() == exp, i


def test_check_lanes_masked(store):
    """op==OP_CHECK lanes report NO scan output; scan lanes report no hit."""
    st, ds = store
    ids = ds.ids[:16]
    q = _batch(np.zeros(16), ids[:, 0], ids[:, 1], ids[:, 2])  # all checks
    r = eng._serve_local(st.meta, st.forest, q, cap=64)
    assert np.asarray(r.hit).all()
    assert not np.asarray(r.valid).any()
    assert (np.asarray(r.ids) == 0).all()
    assert (np.asarray(r.count) == 0).all()
    assert not np.asarray(r.overflow).any()

    q2 = _batch(np.ones(16), ids[:, 0], ids[:, 1], ids[:, 2])  # all row scans
    r2 = eng._serve_local(st.meta, st.forest, q2, cap=64)
    assert not np.asarray(r2.hit).any()  # hit is a check-lane-only signal
    assert (np.asarray(r2.count) >= 1).all()  # (s,p) came from real triples


def test_serve_overflow_and_cap(store):
    """cap smaller than a row's result count: overflow flag + prefix ids."""
    st, ds = store
    T = _truth(ds)
    # the subject/pred pair with the most objects
    from collections import Counter

    (s_, p_), n = Counter((s, p) for s, p, o in T).most_common(1)[0]
    assert n >= 3
    exp = sorted(oo for (ss, pp, oo) in T if ss == s_ and pp == p_)
    cap = n - 1
    serve = eng.make_serve_step(st.meta, cap=cap)
    r = serve(st.forest, _batch([eng.OP_ROW], [s_], [p_], [0]))
    assert bool(np.asarray(r.overflow)[0])
    got = np.asarray(r.ids)[0][np.asarray(r.valid)[0]]
    assert int(np.asarray(r.count)[0]) == len(got) <= cap
    assert got.tolist() == exp[: len(got)]  # truncation keeps the prefix

    # overflow is CONSERVATIVE: cap == n can still latch it (intermediate
    # frontiers hold 1-nodes with no hit in the scanned line), but a roomy
    # cap must clear the flag and return the complete sorted answer
    serve2 = eng.make_serve_step(st.meta, cap=256)
    r2 = serve2(st.forest, _batch([eng.OP_ROW], [s_], [p_], [0]))
    assert not bool(np.asarray(r2.overflow)[0])
    assert np.asarray(r2.ids)[0][np.asarray(r2.valid)[0]].tolist() == exp


def test_pad_preds_inert(store):
    """Padded predicates are valid empty trees: zero results, and real
    predicates answer identically before/after padding."""
    st, ds = store
    f_pad = eng.pad_preds(st.forest, 8)
    assert f_pad.n_preds == 8
    ids = ds.ids[:24]
    ops = np.arange(24) % 3
    q = _batch(ops, ids[:, 0], ids[:, 1], ids[:, 2])
    r0 = eng._serve_local(st.meta, st.forest, q, cap=64)
    r1 = eng._serve_local(st.meta, f_pad, q, cap=64)
    for a, b in zip(r0, r1):
        assert (np.asarray(a) == np.asarray(b)).all()

    # queries routed AT a padded predicate return nothing on any op
    pad_p = st.forest.n_preds + 1  # 1-based id of the first padded tree
    qp = _batch([0, 1, 2], [1, 1, 0], [pad_p] * 3, [1, 0, 1])
    rp = eng._serve_local(st.meta, f_pad, qp, cap=64)
    assert not np.asarray(rp.hit).any()
    assert not np.asarray(rp.valid).any()
    assert (np.asarray(rp.count) == 0).all()
    assert not np.asarray(rp.overflow).any()


def test_pad_preds_noop_when_aligned(store):
    st, _ = store
    assert eng.pad_preds(st.forest, 3) is st.forest  # 6 % 3 == 0


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_serve_step_out_of_range_preds(store, backend):
    """A client's predicate or entity id outside the store answers empty on
    every bounded op of the serve step (with the SP/OP index in the
    program), on both backends; real lanes of the same batch are
    unaffected."""
    st, ds = store
    T = _truth(ds)
    index, pmeta = st.pred_index.select("dac")
    step = eng.make_serve_step(st.meta, cap=64, backend=backend, pmeta=pmeta)
    s_, p_, o_ = (int(x) for x in ds.ids[0])
    bad = [st.n_preds + 1, st.n_preds + 3, 9, 10**6, 0, -7]  # 1-based ids
    three = [eng.OP_CHECK, eng.OP_ROW, eng.OP_COL]
    ops, ss, ps, os_ = three * len(bad), [s_] * 18, [], [o_] * 18
    ps = [b for b in bad for _ in range(3)]
    for e in (0, -3, st.meta.side + 1, 10**6):  # entities past the matrix
        ops += three
        ss += [e, e, s_]
        ps += [p_] * 3
        os_ += [e, o_, e]
    q = _batch(ops + three, ss + [s_] * 3, ps + [p_] * 3, os_ + [o_] * 3)
    r = step(st.forest, q, index)
    n = len(ops)
    assert not np.asarray(r.hit)[:n].any()
    assert not np.asarray(r.valid)[:n].any()
    assert (np.asarray(r.count)[:n] == 0).all()
    assert not np.asarray(r.overflow)[:n].any()
    ids, valid = np.asarray(r.ids), np.asarray(r.valid)
    assert bool(np.asarray(r.hit)[n])
    assert ids[n + 1][valid[n + 1]].tolist() == sorted(
        oo for (ss, pp, oo) in T if ss == s_ and pp == p_)
    assert ids[n + 2][valid[n + 2]].tolist() == sorted(
        ss for (ss, pp, oo) in T if pp == p_ and oo == o_)
