"""Subprocess driver for multi-device tests (8 fake host devices).

Usage: python sharded_driver.py <case>
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh


def case_engine():
    """Predicate-sharded serve Plan (the compiled-plan API end to end):
    ``Engine.compile(ServeQ, ExecConfig(mesh=...))`` == truth."""
    from repro.core import engine as eng, k2triples
    from repro.core.query import ExecConfig, ServeQ
    from repro.data import rdf

    ds = rdf.generate(2000, n_subjects=100, n_preds=7, n_objects=120, seed=3)
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    T = set(map(tuple, ds.ids.tolist()))
    mesh = make_mesh((2, 4), ("data", "model"))
    E = eng.Engine(store)
    plan = E.compile(ServeQ(unbounded=False), ExecConfig.from_env(cap=256, mesh=mesh))
    rng = np.random.default_rng(0)
    B = 32
    ops = rng.integers(0, 3, B).astype(np.int32)
    ids = ds.ids[rng.integers(0, ds.n_triples, B)]
    q = eng.ServeBatch(
        op=jnp.asarray(ops), s=jnp.asarray(ids[:, 0], jnp.int32),
        p=jnp.asarray(ids[:, 1], jnp.int32), o=jnp.asarray(ids[:, 2], jnp.int32),
    )
    r = plan(q)
    hit, rids, valid = np.asarray(r.hit), np.asarray(r.ids), np.asarray(r.valid)
    for i in range(B):
        s_, p_, o_ = map(int, ids[i])
        if ops[i] == 0:
            assert hit[i] == ((s_, p_, o_) in T), i
        elif ops[i] == 1:
            assert rids[i][valid[i]].tolist() == sorted(
                oo for (ss, pp, oo) in T if ss == s_ and pp == p_
            ), i
        else:
            assert rids[i][valid[i]].tolist() == sorted(
                ss for (ss, pp, oo) in T if pp == p_ and oo == o_
            ), i
    # unbounded-predicate sweep (the paper's worst case, parallelized) —
    # kept on the reference entry point: it is the index-free fallback
    f_pad = eng.pad_preds(store.forest, 4)
    f_sh = eng.shard_forest(f_pad, mesh, "model")
    unb = eng.make_sharded_unbounded_scan(store.meta, mesh, cap=128)
    keys = jnp.asarray(ids[:8, 0], jnp.int32)
    axes = jnp.zeros((8,), jnp.int32)
    ids_u, valid_u, _ = (np.asarray(x) for x in unb(f_sh, keys, axes))
    for i in range(8):
        s_ = int(ids[i, 0])
        for pp in range(f_pad.n_preds):
            got = ids_u[i, pp][valid_u[i, pp]].tolist()
            exp = (
                sorted(oo for (ss, p2, oo) in T if ss == s_ and p2 == pp + 1)
                if pp < ds.n_preds else []
            )
            assert got == exp, (i, pp)
    # no arena-sized all-gathers in the compiled module
    txt = plan.compiled_text(q)
    assert txt.count("all-gather") == 0
    print("engine OK")


def case_engine_pruned():
    """Index-pruned unbounded serve IR on a predicate-sharded forest, via
    the compiled-plan API: sharded Plan == single-device Plan == truth."""
    from repro.core import engine as eng, k2triples
    from repro.core.query import ExecConfig, ServeQ
    from repro.data import rdf

    ds = rdf.generate(
        3000, n_subjects=90, n_preds=16, n_objects=110,
        preds_per_subject=4, seed=6,
    )
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    T = set(map(tuple, ds.ids.tolist()))
    mesh = make_mesh((2, 4), ("data", "model"))
    E = eng.Engine(store)
    plan_sh = E.compile(ServeQ(), ExecConfig.from_env(cap=128, mesh=mesh))
    plan_1d = E.compile(ServeQ(), ExecConfig.from_env(cap=128))
    rng = np.random.default_rng(1)
    B = 32
    ops = rng.integers(0, 6, B).astype(np.int32)
    ids = ds.ids[rng.integers(0, ds.n_triples, B)]
    q = eng.ServeBatch(
        op=jnp.asarray(ops), s=jnp.asarray(ids[:, 0], jnp.int32),
        p=jnp.asarray(np.where(ops >= 3, 0, ids[:, 1]), jnp.int32),
        o=jnp.asarray(ids[:, 2], jnp.int32),
    )
    r = plan_sh(q)
    ref = plan_1d(q)
    for name, a, b in zip(r._fields, r, ref):
        assert (np.asarray(a) == np.asarray(b)).all(), name
    # spot-check against truth: every unbounded pair lane
    up, ui, uv = (np.asarray(x) for x in (r.u_preds, r.u_ids, r.u_valid))
    for i in range(B):
        if ops[i] not in (3, 4):
            continue
        key = int(ids[i, 0] if ops[i] == 3 else ids[i, 2])
        got = {
            int(up[i, l]): ui[i, l][uv[i, l]].tolist()
            for l in range(up.shape[1]) if up[i, l] and uv[i, l].any()
        }
        exp = {}
        for (ss, pp, oo) in T:
            if ops[i] == 3 and ss == key:
                exp.setdefault(pp, []).append(oo)
            if ops[i] == 4 and oo == key:
                exp.setdefault(pp, []).append(ss)
        assert got == {k: sorted(v) for k, v in exp.items()}, i
    # a pattern plan on the same mesh config: lanes pad to the data axis
    # and decode from the psum'd u_* block
    from repro.core.query import TriplePatternQ

    s0 = int(ids[0, 0])
    got = E.compile(
        TriplePatternQ(s0, "?p", "?o"), ExecConfig.from_env(cap=128, mesh=mesh)
    )()
    exp = {}
    for (ss, pp, oo) in T:
        if ss == s0:
            exp.setdefault(pp, []).append(oo)
    assert {k: v.tolist() for k, v in got.items()} == {
        k: sorted(v) for k, v in exp.items()
    }
    # the pruned path reduces [B, u_width, cap]; the wire never carries
    # an arena- or P-sized gather
    txt = plan_sh.compiled_text(q)
    assert txt.count("all-gather") == 0
    print("engine_pruned OK")


def case_compress():
    """int8 EF all-reduce: shared scale is exact-sum; EF kills bias."""
    from repro.dist import compress

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g_all = rng.standard_normal((8, 256)).astype(np.float32)

    fn = jax.jit(
        jax.shard_map(
            lambda g, e: compress.compress_decompress_psum(g, e, "data"),
            mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
        )
    )
    exact = g_all.mean(axis=0)
    out, err = fn(jnp.asarray(g_all.reshape(-1)), jnp.zeros(8 * 256))
    e1 = np.abs(np.asarray(out).reshape(8, 256)[0] - exact).max()
    assert e1 < 0.05, e1
    errbuf = jnp.zeros((8 * 256,))
    acc = np.zeros(256)
    N = 20
    for _ in range(N):
        o, errbuf = fn(jnp.asarray(g_all.reshape(-1)), errbuf)
        acc += np.asarray(o).reshape(8, 256)[0]
    e2 = np.abs(acc / N - exact).max()
    assert e2 < e1 * 0.3, (e1, e2)
    print("compress OK")


def case_sortedset_union():
    """Sharded serve batch at 8 devices with non-uniform predicate load."""
    from repro.core import engine as eng, k2triples
    from repro.data import rdf

    from repro.core.query import ExecConfig, ServeQ

    ds = rdf.generate(4000, n_subjects=80, n_preds=16, n_objects=90, seed=9)
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    mesh = make_mesh((1, 8), ("data", "model"))
    T = set(map(tuple, ds.ids.tolist()))
    plan = eng.Engine(store).compile(
        ServeQ(unbounded=False), ExecConfig.from_env(cap=512, mesh=mesh)
    )
    ids = ds.ids[:64]
    q = eng.ServeBatch(
        op=jnp.full((64,), 1, jnp.int32), s=jnp.asarray(ids[:, 0], jnp.int32),
        p=jnp.asarray(ids[:, 1], jnp.int32), o=jnp.asarray(ids[:, 2], jnp.int32),
    )
    r = plan(q)
    rids, valid = np.asarray(r.ids), np.asarray(r.valid)
    for i in range(64):
        s_, p_, _ = map(int, ids[i])
        assert rids[i][valid[i]].tolist() == sorted(
            oo for (ss, pp, oo) in T if ss == s_ and pp == p_
        )
    print("sortedset_union OK")


def case_moe_shmap():
    """shard_map MoE == single-device reference MoE (same routing math)."""
    from repro.models import transformer as tf

    cfg = tf.TransformerCfg(
        name="m", n_layers=1, d_model=32, n_heads=4, n_kv_heads=4, d_head=8,
        d_ff=32, vocab=64, moe=tf.MoECfg(n_experts=8, top_k=2, d_ff_expert=32,
                                         capacity_factor=8.0),  # no drops: exact match
    )
    params = tf.init(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])  # single layer slice
    rng = np.random.default_rng(0)
    B, S, D = 4, 8, 32
    x = jnp.asarray(rng.standard_normal((B, S, D)), jnp.bfloat16)

    ref = tf.moe_ffn(cfg, lp, x.reshape(B * S, D)).reshape(B, S, D)

    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh:
        got = tf.moe_ffn_shmap(cfg, lp, x, mesh=mesh, dp_axes=("data",))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )
    # gradients flow through the shard_map path
    def loss(lp):
        with mesh:
            y = tf.moe_ffn_shmap(cfg, lp, x, mesh=mesh, dp_axes=("data",))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g = jax.grad(loss)(lp)
    assert float(jnp.abs(g["we1"]).sum()) > 0
    assert float(jnp.abs(g["router"]).sum()) > 0
    print("moe_shmap OK")


if __name__ == "__main__":
    case = sys.argv[1]
    {
        "engine": case_engine,
        "engine_pruned": case_engine_pruned,
        "compress": case_compress,
        "sortedset_union": case_sortedset_union,
        "moe_shmap": case_moe_shmap,
    }[case]()
