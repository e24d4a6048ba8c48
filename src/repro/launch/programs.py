"""Program builders: one (arch × shape × mesh) cell -> a lowerable program.

``build(arch_id, shape_id, mesh)`` returns a ``Program`` whose ``fn`` +
``in_specs`` (ShapeDtypeStructs) + ``in_shardings`` feed straight into

    jax.jit(fn, in_shardings=...).lower(*in_specs).compile()

Nothing is allocated — params, optimizer state, caches and batches are all
abstract.  The same builders back the real train/serve drivers (which
``init`` + ``device_put`` concrete arrays instead).

Cell kinds per family:
  lm:      train (grad+optimizer), prefill, decode (32k & 500k KV)
  gnn:     train on the 4 graph shapes (sampled blocks for minibatch_lg)
  recsys:  train / forward / bulk / retrieval
  engine:  sharded SPARQL serve batches (the paper's program)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import base as cb
from repro.dist import sharding as shd
from repro.launch import mesh as meshlib
from repro.models import transformer as tfm
from repro.models.gnn import common as gnn_common
from repro.models.gnn import egnn, equiformer_v2, graphcast, mace
from repro.models.recsys import xdeepfm
from repro.train import optim
from repro.train.trainer import make_train_step


class Program(NamedTuple):
    name: str
    fn: Callable
    in_specs: tuple  # ShapeDtypeStructs (pytrees)
    in_shardings: tuple
    donate: tuple[int, ...] = ()
    # analytic model flops for §Roofline's MODEL_FLOPS/HLO_FLOPS ratio
    model_flops: float = 0.0


def _opt(arch: cb.ArchSpec):
    return optim.adafactor(1e-3) if arch.optimizer == "adafactor" else optim.adamw(3e-4)


def _dtype(arch: cb.ArchSpec):
    return jnp.bfloat16 if arch.param_dtype == "bfloat16" else jnp.float32


def _tree_shardings_none_ok(mesh, specs, axes, rules=None):
    def one(s, names):
        if names is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, shd.spec_for(mesh, tuple(names), s.shape, rules))

    return jax.tree.map(
        one, specs, axes, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    )


# ---------------------------------------------------------------------------
# LM programs
# ---------------------------------------------------------------------------


def lm_train_flops(cfg: tfm.TransformerCfg, tokens: int) -> float:
    """6·N_active·D (+ attention quadratic term) — the §Roofline numerator."""
    base = 6.0 * cfg.n_active_params * tokens
    # causal attention: 2·(2·S·S/2·H·dh)·B fwd ≈ 6·S·H·dh per token bwd-incl
    return base


def build_lm(arch: cb.ArchSpec, shape: cb.ShapeSpec, mesh: Mesh, *, smoke=False) -> Program:
    cfg: tfm.TransformerCfg = arch.smoke_cfg if smoke else arch.cfg
    dims = shape.dims
    B, S = dims["global_batch"], dims["seq_len"]
    if smoke:
        B, S = 2, 64
    dp = meshlib.dp_axes(mesh)
    rules = dict(shape.rules_override)
    dt = _dtype(arch)

    pspecs = tfm.param_specs(cfg, dt)
    paxes = tfm.logical_axes(cfg)
    psh = _tree_shardings_none_ok(mesh, pspecs, paxes, rules)
    # sequence-parallel residual stream: [B, S, D] -> (dp, 'model', None)
    constrain = shd.constrain_fn(mesh, ("batch", "seq_sp", None), rules)
    # expert-parallel MoE: per-shard routing under shard_map (no global sort)
    moe_ctx = {"mesh": mesh, "dp_axes": dp} if cfg.moe else None

    if shape.kind == "train":
        opt = _opt(arch)
        ospecs = jax.eval_shape(opt.init, pspecs)
        oaxes = opt.state_logical_axes(paxes)
        osh = _tree_shardings_none_ok(mesh, ospecs, oaxes, rules)
        bspec = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        bsh = {
            "tokens": NamedSharding(mesh, P(dp, None)),
            "labels": NamedSharding(mesh, P(dp, None)),
        }
        constrain_logits = shd.constrain_fn(mesh, ("batch", None, "vocab"), rules)
        loss = lambda p, b: tfm.loss_fn(
            cfg, p, b, constrain=constrain, constrain_logits=constrain_logits,
            moe_ctx=moe_ctx,
        )
        step = make_train_step(loss, opt)
        return Program(
            name=f"{arch.arch_id}:{shape.shape_id}",
            fn=step,
            in_specs=(pspecs, ospecs, bspec),
            in_shardings=(psh, osh, bsh),
            donate=(0, 1),
            model_flops=lm_train_flops(cfg, B * S),
        )

    if shape.kind == "prefill":
        bspec = jax.ShapeDtypeStruct((B, S), jnp.int32)
        bsh = NamedSharding(mesh, P(dp, None))
        fn = lambda p, t: tfm.prefill(cfg, p, t, constrain=constrain, moe_ctx=moe_ctx)
        return Program(
            name=f"{arch.arch_id}:{shape.shape_id}",
            fn=fn,
            in_specs=(pspecs, bspec),
            in_shardings=(psh, bsh),
            model_flops=2.0 * cfg.n_active_params * B * S,
        )

    # decode: one token against a KV cache of S
    cache_spec = tfm.KVCache.specs(cfg, B, S)
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    cache_sh = {
        k: NamedSharding(
            mesh, shd.spec_for(mesh, kv_axes, v.shape, {**shd.DEFAULT_RULES, **rules})
        )
        for k, v in cache_spec.items()
    }
    tok_spec = jax.ShapeDtypeStruct((B,), jnp.int32)
    len_spec = jax.ShapeDtypeStruct((B,), jnp.int32)
    bsh = NamedSharding(mesh, shd.spec_for(mesh, ("batch",), (B,), rules))
    fn = lambda p, c, t, ln: tfm.decode_step(cfg, p, c, t, ln)
    return Program(
        name=f"{arch.arch_id}:{shape.shape_id}",
        fn=fn,
        in_specs=(pspecs, cache_spec, tok_spec, len_spec),
        in_shardings=(psh, cache_sh, bsh, bsh),
        donate=(1,),
        model_flops=2.0 * cfg.n_active_params * B
        + 4.0 * B * S * cfg.n_layers * cfg.n_kv_heads * cfg.d_head,
    )


# ---------------------------------------------------------------------------
# GNN programs
# ---------------------------------------------------------------------------

GNN_MODULES = {
    "mace": mace,
    "graphcast": graphcast,
    "egnn": egnn,
    "equiformer-v2": equiformer_v2,
}

GNN_RULES = {
    # node/edge arrays data-parallel; channel dims TP over 'model'
    "nodes": ("pod", "data"),
    "edges": ("pod", "data"),
}


def _gnn_sizes(shape: cb.ShapeSpec, smoke: bool):
    d = shape.dims
    if shape.shape_id == "minibatch_lg":
        seeds = 16 if smoke else d["batch_nodes"]
        f = d["fanouts"]
        n = seeds * int(np.prod([x + 1 for x in f]))
        e, m = 0, seeds
        for x in f:
            m *= x
            e += m
        return n, e, d["d_feat"], d["n_classes"], 1
    if shape.shape_id == "molecule":
        b = 8 if smoke else d["batch"]
        return b * d["n_nodes"], b * d["n_edges"], 8, 0, b
    n, e = (256, 1024) if smoke else (d["n_nodes"], d["n_edges"])
    return n, e, d["d_feat"], d["n_classes"], 1


def _pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def build_gnn(arch: cb.ArchSpec, shape: cb.ShapeSpec, mesh: Mesh, *, smoke=False) -> Program:
    mod = GNN_MODULES[arch.arch_id]
    n, e, d_feat, n_classes, n_graphs = _gnn_sizes(shape, smoke)
    dp_size = int(np.prod([mesh.shape[a] for a in meshlib.dp_axes(mesh)]))
    n = _pad_to(n, dp_size)
    e = _pad_to(e, dp_size * mesh.shape["model"])

    cfg = arch.smoke_cfg if smoke else arch.cfg
    out_dim = n_classes if n_classes else 1
    cfg = dataclasses.replace(cfg, out_dim=out_dim, **(
        {"in_dim": d_feat} if hasattr(cfg, "in_dim") else {}
    ))
    # edge-chunked message passing for the huge-edge shapes (bounds the
    # per-layer [E_loc, C, dim] working set; see equiformer_v2.forward)
    if hasattr(cfg, "edge_chunks") and not smoke and e >= 10_000_000:
        cfg = dataclasses.replace(cfg, edge_chunks=128)
    # full-batch giant graphs: remat RE-GATHERS the halo in the backward
    # (5x collective, no memory win — measured); turn it off there
    if shape.shape_id == "ogb_products" and not smoke:
        cfg = dataclasses.replace(cfg, remat=False)

    pspecs = mod.param_specs(cfg)
    # GNN params are small relative to activations: replicate
    psh = jax.tree.map(
        lambda s: NamedSharding(mesh, P()), pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )

    gb = gnn_common.GraphBatch(
        node_feat=jax.ShapeDtypeStruct((n, d_feat), jnp.float32),
        positions=jax.ShapeDtypeStruct((n, 3), jnp.float32),
        species=jax.ShapeDtypeStruct((n,), jnp.int32),
        edge_src=jax.ShapeDtypeStruct((e,), jnp.int32),
        edge_dst=jax.ShapeDtypeStruct((e,), jnp.int32),
        edge_feat=jax.ShapeDtypeStruct((e, 4), jnp.float32),
        node_mask=jax.ShapeDtypeStruct((n,), jnp.bool_),
        edge_mask=jax.ShapeDtypeStruct((e,), jnp.bool_),
        labels=jax.ShapeDtypeStruct((n,), jnp.int32),
        graph_ids=jax.ShapeDtypeStruct((n,), jnp.int32),
        graph_y=jax.ShapeDtypeStruct((n_graphs,), jnp.float32),
    )
    dp = meshlib.dp_axes(mesh)
    nsh = NamedSharding(mesh, P(dp))
    esh = NamedSharding(mesh, P(dp))
    gsh = gnn_common.GraphBatch(
        node_feat=nsh, positions=nsh, species=nsh,
        edge_src=esh, edge_dst=esh, edge_feat=esh,
        node_mask=nsh, edge_mask=esh, labels=nsh,
        graph_ids=nsh, graph_y=NamedSharding(mesh, P()),
    )

    opt = _opt(arch)
    ospecs = jax.eval_shape(opt.init, pspecs)
    osh = jax.tree.map(lambda s: NamedSharding(mesh, P()), ospecs,
                       is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    loss = lambda p, b: mod.loss_fn(cfg, p, b)
    step = make_train_step(loss, opt)
    # model flops: classify weights by whether they apply per-edge or
    # per-node, then 2·size·count fwd, ×3 for fwd+bwd
    EDGE_KEYS = ("edge_mlp", "phi_e", "phi_x", "w0", "w1_r", "w1_i", "w2_r",
                 "w2_i", "attn", "radial")
    per_edge = per_node = 0
    for kp, w in jax.tree_util.tree_flatten_with_path(pspecs)[0]:
        if len(w.shape) < 2:
            continue
        path = jax.tree_util.keystr(kp)
        sz = w.shape[-2] * w.shape[-1]
        if any(k in path for k in EDGE_KEYS):
            per_edge += sz
        else:
            per_node += sz
    return Program(
        name=f"{arch.arch_id}:{shape.shape_id}",
        fn=step,
        in_specs=(pspecs, ospecs, gb),
        in_shardings=(psh, osh, gsh),
        donate=(0, 1),
        model_flops=3.0 * 2.0 * (e * per_edge + n * per_node),
    )


# ---------------------------------------------------------------------------
# recsys programs
# ---------------------------------------------------------------------------


def build_recsys(arch: cb.ArchSpec, shape: cb.ShapeSpec, mesh: Mesh, *, smoke=False) -> Program:
    cfg: xdeepfm.XDeepFMCfg = arch.smoke_cfg if smoke else arch.cfg
    dp = meshlib.dp_axes(mesh)
    pspecs = xdeepfm.param_specs(cfg)
    paxes = {
        "tables": ("fields", "rows", None),
        "linear": ("fields", "rows"),
        "cin": [(None, None, None) for _ in cfg.cin_layers],
        "cin_out": (None, None),
        "dnn": {
            "w": [(None, None) for _ in range(len(cfg.mlp_dims) + 1)],
            "b": [(None,) for _ in range(len(cfg.mlp_dims) + 1)],
        },
        "bias": (),
    }
    psh = _tree_shardings_none_ok(mesh, pspecs, paxes)
    B = 64 if smoke else shape.dims["batch"]

    if shape.kind == "retrieval":
        nc = 4096 if smoke else shape.dims["n_candidates"]
        uspec = jax.ShapeDtypeStruct((cfg.n_fields,), jnp.int32)
        cspec = jax.ShapeDtypeStruct((nc,), jnp.int32)
        fn = lambda p, u, c: xdeepfm.retrieval_score(cfg, p, u, c)
        return Program(
            name=f"{arch.arch_id}:{shape.shape_id}", fn=fn,
            in_specs=(pspecs, uspec, cspec),
            in_shardings=(psh, NamedSharding(mesh, P()), NamedSharding(mesh, P(dp))),
            model_flops=2.0 * nc * cfg.embed_dim,
        )

    ids_spec = jax.ShapeDtypeStruct((B, cfg.n_fields), jnp.int32)
    ids_sh = NamedSharding(mesh, P(dp, None))
    flops_fwd = 2.0 * B * (
        cfg.n_fields * cfg.embed_dim  # lookups
        + sum(
            h * hp * cfg.n_fields * cfg.embed_dim
            for h, hp in zip(cfg.cin_layers, (cfg.n_fields, *cfg.cin_layers[:-1]))
        )
        + cfg.n_fields * cfg.embed_dim * cfg.mlp_dims[0]
        + sum(a * b for a, b in zip(cfg.mlp_dims, (*cfg.mlp_dims[1:], 1)))
    )

    if shape.kind == "forward":
        fn = lambda p, ids: xdeepfm.forward(cfg, p, ids)
        return Program(
            name=f"{arch.arch_id}:{shape.shape_id}", fn=fn,
            in_specs=(pspecs, ids_spec), in_shardings=(psh, ids_sh),
            model_flops=flops_fwd,
        )

    lbl_spec = jax.ShapeDtypeStruct((B,), jnp.int32)
    opt = _opt(arch)
    ospecs = jax.eval_shape(opt.init, pspecs)
    oaxes = opt.state_logical_axes(paxes)
    osh = _tree_shardings_none_ok(mesh, ospecs, oaxes)
    loss = lambda p, b: xdeepfm.loss_fn(cfg, p, b)
    step = make_train_step(loss, opt)
    return Program(
        name=f"{arch.arch_id}:{shape.shape_id}", fn=step,
        in_specs=(pspecs, ospecs, {"ids": ids_spec, "labels": lbl_spec}),
        in_shardings=(psh, osh, {"ids": ids_sh, "labels": NamedSharding(mesh, P(dp))}),
        donate=(0, 1),
        model_flops=3.0 * flops_fwd,
    )


# ---------------------------------------------------------------------------
# engine (k²-triples) programs — the paper's serving path
# ---------------------------------------------------------------------------


def _engine_forest_specs(cfg, mesh: Mesh):
    """Static arena shapes for the dry run (no store build, no allocation).

    Arena widths follow the paper's measured ~5 bits/triple at dbpedia
    sparsity (Table 2: 0.864 GB / 232 M triples ≈ 32 bits/triple incl.
    dictionary; structure-only ≈ 5) with a 4× safety factor, padded to the
    mesh.  The REAL store builder produces exact shapes; serving programs
    are re-lowered per store shape bucket in production.
    """
    from repro.core.k2tree import K2Meta, hybrid_ks

    P_pad = _pad_to(cfg.n_preds, mesh.shape["model"])
    extent = max(cfg.n_subjects, cfg.n_objects)
    meta = K2Meta(hybrid_ks(extent))
    H = meta.n_levels
    bits_per_tree = max(4096, 20 * cfg.n_triples // cfg.n_preds)
    wt = (bits_per_tree * 3 // 4 + 31) // 32
    wl = (bits_per_tree // 4 + 31) // 32
    from repro.core.bitvec import TILE_COLS, TILE_ROWS, round_up
    from repro.core.k2forest import K2Forest

    # the layout of engine.shard_forest: whole (8, 128) tiles per shard
    mp = mesh.shape["model"]
    rows = mp * round_up(P_pad // mp, TILE_ROWS)
    wt, wl = round_up(wt, TILE_COLS), round_up(wl, TILE_COLS)
    hc = round_up(H, TILE_COLS)
    return meta, K2Forest(
        t_words=jax.ShapeDtypeStruct((rows, wt), jnp.uint32),
        t_rank=jax.ShapeDtypeStruct((rows, wt), jnp.int32),
        l_words=jax.ShapeDtypeStruct((rows, wl), jnp.uint32),
        ones_before=jax.ShapeDtypeStruct((rows, hc), jnp.int32),
        level_start=jax.ShapeDtypeStruct((rows, hc), jnp.int32),
        nnz=jax.ShapeDtypeStruct((P_pad,), jnp.int32),
    )


def build_engine(arch: cb.ArchSpec, shape: cb.ShapeSpec, mesh: Mesh, *, smoke=False) -> Program:
    from repro.core import engine as eng

    cfg = arch.smoke_cfg if smoke else arch.cfg
    meta, fspecs = _engine_forest_specs(cfg, mesh)
    dp = meshlib.dp_axes(mesh)
    B = 256 if smoke else shape.dims["batch"]
    fsh = jax.tree.map(
        lambda s: NamedSharding(mesh, P("model")), fspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    qsh = NamedSharding(mesh, P(dp))

    if shape.dims.get("unbounded"):
        fn = eng.make_sharded_unbounded_scan(meta, mesh, cfg.cap, data_axes=dp)
        specs = (
            fspecs,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        )
        return Program(
            name=f"{arch.arch_id}:{shape.shape_id}", fn=fn,
            in_specs=specs, in_shardings=(fsh, qsh, qsh),
            model_flops=2.0 * B * cfg.n_preds * cfg.cap * 4,
        )

    fn = eng.make_sharded_serve_step(meta, mesh, cfg.cap, data_axes=dp)
    q = eng.ServeBatch(
        op=jax.ShapeDtypeStruct((B,), jnp.int32),
        s=jax.ShapeDtypeStruct((B,), jnp.int32),
        p=jax.ShapeDtypeStruct((B,), jnp.int32),
        o=jax.ShapeDtypeStruct((B,), jnp.int32),
    )
    qsh_t = eng.ServeBatch(op=qsh, s=qsh, p=qsh, o=qsh)
    return Program(
        name=f"{arch.arch_id}:{shape.shape_id}", fn=fn,
        in_specs=(fspecs, q), in_shardings=(fsh, qsh_t),
        model_flops=2.0 * B * cfg.cap * meta.n_levels * 2,
    )


# ---------------------------------------------------------------------------


def build(arch_id: str, shape_id: str, mesh: Mesh, *, smoke: bool = False) -> Program:
    arch = cb.get(arch_id)
    shape = arch.shape(shape_id)
    if shape.skip:
        raise ValueError(f"{arch_id}:{shape_id} skipped: {shape.skip}")
    builder = {
        "lm": build_lm,
        "gnn": build_gnn,
        "recsys": build_recsys,
        "engine": build_engine,
    }[arch.family]
    return builder(arch, shape, mesh, smoke=smoke)


def all_cells(include_engine: bool = True):
    for arch_id, arch in cb.ARCHS.items():
        if arch.family == "engine" and not include_engine:
            continue
        for s in arch.shapes:
            if not s.skip:
                yield arch_id, s.shape_id
