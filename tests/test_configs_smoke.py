"""REQUIRED per-arch smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + no NaNs.  One test per assigned architecture."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get
from repro.data import graphs as G, recsys as R
from repro.data.tokens import TokenStream
from repro.launch.programs import GNN_MODULES
from repro.models import transformer as tfm
from repro.models.recsys import xdeepfm

LM_ARCHS = [a for a, s in ARCHS.items() if s.family == "lm"]
GNN_ARCHS = [a for a, s in ARCHS.items() if s.family == "gnn"]


def test_registry_complete():
    fams = {}
    for a, s in ARCHS.items():
        fams.setdefault(s.family, []).append(a)
    assert len(fams["lm"]) == 5
    assert len(fams["gnn"]) == 4
    assert len(fams["recsys"]) == 1
    assert "k2triples" in fams["engine"]
    # 40 assigned cells
    n_cells = sum(len(s.shapes) for s in ARCHS.values() if s.family != "engine")
    assert n_cells == 40


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_smoke_train_step(arch_id):
    spec = get(arch_id)
    cfg = spec.smoke_cfg
    params = tfm.init(cfg, jax.random.PRNGKey(0))
    ts = TokenStream(cfg.vocab, 32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in ts.batch(2).items()}
    loss, grads = jax.value_and_grad(lambda p: tfm.loss_fn(cfg, p, batch))(params)
    assert np.isfinite(float(loss)), arch_id
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), arch_id
    # serve path: prefill emits logits of the right shape
    logits, cache = tfm.prefill(cfg, params, batch["tokens"])
    assert logits.shape == (2, cfg.vocab)
    assert not np.isnan(np.asarray(logits, np.float32)).any()


@pytest.mark.parametrize("arch_id", GNN_ARCHS)
def test_gnn_smoke_train_step(arch_id):
    spec = get(arch_id)
    mod = GNN_MODULES[arch_id]
    mol = G.molecule_batch(4, 8, 16, seed=1)
    mol = jax.tree.map(lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x, mol)
    cfg = spec.smoke_cfg
    if hasattr(cfg, "in_dim"):
        cfg = dataclasses.replace(cfg, in_dim=mol.node_feat.shape[1], out_dim=1)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    loss, grads = jax.value_and_grad(lambda p: mod.loss_fn(cfg, p, mol))(params)
    assert np.isfinite(float(loss)), arch_id
    out = mod.forward(cfg, params, mol)
    assert out.shape[0] == mol.node_feat.shape[0]
    assert np.isfinite(np.asarray(out, np.float32)).all(), arch_id


def test_recsys_smoke_train_step():
    spec = get("xdeepfm")
    cfg = spec.smoke_cfg
    params = xdeepfm.init(cfg, jax.random.PRNGKey(0))
    b = {k: jnp.asarray(v) for k, v in R.ctr_batch(32, cfg.n_fields, cfg.rows_per_field).items()}
    loss, grads = jax.value_and_grad(lambda p: xdeepfm.loss_fn(cfg, p, b))(params)
    assert np.isfinite(float(loss))
    logit = xdeepfm.forward(cfg, params, b["ids"])
    assert logit.shape == (32,)
    assert np.isfinite(np.asarray(logit)).all()


def test_engine_smoke_serve():
    from repro.core import engine as eng, k2triples
    from repro.data import rdf

    cfg = get("k2triples").smoke_cfg
    ds = rdf.generate(
        cfg.n_triples, n_subjects=cfg.n_subjects, n_preds=cfg.n_preds,
        n_objects=cfg.n_objects, seed=0,
    )
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    serve = eng.make_serve_step(store.meta, cap=cfg.cap)
    ids = ds.ids[:16]
    q = eng.ServeBatch(
        op=jnp.zeros(16, jnp.int32), s=jnp.asarray(ids[:, 0], jnp.int32),
        p=jnp.asarray(ids[:, 1], jnp.int32), o=jnp.asarray(ids[:, 2], jnp.int32),
    )
    r = serve(store.forest, q)
    assert np.asarray(r.hit).all()  # every existing triple found


@pytest.mark.parametrize(
    "arch_id,shape_id",
    [("tinyllama-1.1b", "train_4k"), ("egnn", "molecule"),
     ("xdeepfm", "serve_p99"), ("k2triples", "serve_64k")],
)
def test_program_builders_smoke_lower(arch_id, shape_id):
    """Program builders produce lowerable cells on a 1x1 mesh (smoke shapes)."""
    from repro.launch import programs

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    prog = programs.build(arch_id, shape_id, mesh, smoke=True)
    with mesh:
        lowered = jax.jit(prog.fn, in_shardings=prog.in_shardings).lower(*prog.in_specs)
        assert lowered is not None
