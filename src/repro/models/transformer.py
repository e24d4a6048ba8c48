"""Decoder-only transformer family covering the five assigned LM archs.

One config dataclass expresses: dense GQA (tinyllama, command-r-plus),
local/global alternating attention + logit softcaps (gemma2), and top-k MoE
(kimi-k2, olmoe).  Params are stacked over layers ([L, ...] leaves) and the
forward pass is a ``lax.scan`` with per-layer remat — compile time and HLO
size stay O(1) in depth, which matters at 61 layers × 512 devices.

Sharding is expressed as LOGICAL axis names on every param leaf
(``logical_axes``); ``repro.dist.sharding`` maps them onto the production
mesh (TP over 'model', FSDP over 'data', DP over 'pod'×'data', sequence-
parallel residual stream over 'model').
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L

Params = Any


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class TransformerCfg:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float = 10_000.0
    moe: MoECfg | None = None
    window: int | None = None  # sliding window for local layers
    local_every: int = 2  # gemma2: alternate local/global when window set
    attn_softcap: float | None = None
    final_softcap: float | None = None
    parallel_residual: bool = False  # command-r style
    tie_embeddings: bool = False
    remat: bool = True
    # attention chunking (flash-style); tuned per shape by the launcher
    chunk_q: int = 512
    chunk_kv: int = 1024

    @property
    def n_params(self) -> int:
        """Total parameter count (dense equivalent; MoE counts all experts)."""
        D, H, Kv, dh, F, V, Lz = (
            self.d_model, self.n_heads, self.n_kv_heads, self.d_head,
            self.d_ff, self.vocab, self.n_layers,
        )
        attn = D * H * dh + 2 * D * Kv * dh + H * dh * D
        if self.moe:
            ffn = D * self.moe.n_experts + 3 * self.moe.n_experts * D * self.moe.d_ff_expert
        else:
            ffn = 3 * D * F
        emb = V * D * (1 if self.tie_embeddings else 2)
        return Lz * (attn + ffn + 2 * D) + emb + D

    @property
    def n_active_params(self) -> int:
        """Per-token active params (MoE: top-k experts only)."""
        if not self.moe:
            return self.n_params
        D, Lz = self.d_model, self.n_layers
        full_ffn = 3 * self.moe.n_experts * D * self.moe.d_ff_expert
        act_ffn = 3 * self.moe.top_k * D * self.moe.d_ff_expert
        return self.n_params - Lz * (full_ffn - act_ffn)


# ---------------------------------------------------------------------------
# params: shapes, logical axes, init
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: TransformerCfg) -> dict[str, tuple[tuple[int, ...], tuple[str | None, ...]]]:
    D, H, Kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s: dict[str, tuple[tuple[int, ...], tuple[str | None, ...]]] = {
        "attn_norm": ((D,), ("embed",)),
        "wq": ((D, H, dh), ("embed", "heads", "head_dim")),
        "wk": ((D, Kv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ((D, Kv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ((H, dh, D), ("heads", "head_dim", "embed_out")),
        "ffn_norm": ((D,), ("embed",)),
    }
    if cfg.moe:
        E, Fe = cfg.moe.n_experts, cfg.moe.d_ff_expert
        s |= {
            "router": ((D, E), ("embed", None)),
            "we1": ((E, D, Fe), ("experts", "embed", "ffn")),
            "we3": ((E, D, Fe), ("experts", "embed", "ffn")),
            "we2": ((E, Fe, D), ("experts", "ffn", "embed_out")),
        }
    else:
        F = cfg.d_ff
        s |= {
            "w1": ((D, F), ("embed", "ffn")),
            "w3": ((D, F), ("embed", "ffn")),
            "w2": ((F, D), ("ffn", "embed_out")),
        }
    return s


def param_specs(cfg: TransformerCfg, dtype=jnp.float32):
    """ShapeDtypeStructs for every param (no allocation — dry-run path)."""
    Lz = cfg.n_layers
    lay = {
        k: jax.ShapeDtypeStruct((Lz, *shape), dtype)
        for k, (shape, _) in _layer_shapes(cfg).items()
    }
    p = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), dtype),
        "layers": lay,
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab), dtype)
    return p


def logical_axes(cfg: TransformerCfg):
    """Same pytree as params, leaves = logical axis-name tuples."""
    lay = {k: ("layers", *ax) for k, (_, ax) in _layer_shapes(cfg).items()}
    p = {
        "embed": ("vocab", "embed"),
        "layers": lay,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = ("embed", "vocab")
    return p


def init(cfg: TransformerCfg, key: jax.Array, dtype=jnp.float32) -> Params:
    specs = param_specs(cfg, dtype)
    flat, treedef = jax.tree.flatten(specs)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, s in zip(keys, flat):
        if len(s.shape) <= 1 or s.shape[-1] == 1:
            out.append(jnp.zeros(s.shape, dtype))
        else:
            fan_in = int(s.shape[-2]) if len(s.shape) >= 2 else int(s.shape[-1])
            out.append(
                (jax.random.normal(k, s.shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)
            )
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# MoE FFN: top-k, capacity-based sort dispatch (dense shapes, shardable)
# ---------------------------------------------------------------------------


def _moe_dispatch_indices(gates: jax.Array, E: int, K: int, C: int, e0=0, e_count=None):
    """Sort-based capacity routing -> gather/scatter INDEX tensors only.

    Returns (idx [E_loc·C] token index per slot, wgt [E_loc·C] combine weight,
    valid [E_loc·C]).  No [T·K, D] activation temp is ever built — dispatch
    is a [E_loc·C, D] gather, combine a scatter-add of the same size.
    ``e0/e_count`` restrict to a local expert range (shard_map path).
    """
    T = gates.shape[0]
    e_count = e_count or E
    topv, topi = jax.lax.top_k(gates, K)  # [T, K]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    exp = topi.reshape(-1).astype(jnp.int32) - e0
    wgt = topv.reshape(-1)
    local = (exp >= 0) & (exp < e_count)
    exp = jnp.where(local, exp, e_count)  # foreign experts sort to the tail
    order = jnp.argsort(exp)  # stable: groups by expert, arrival order kept
    exp_s, tok_s, w_s = exp[order], tok[order], wgt[order]
    start = jnp.searchsorted(exp_s, jnp.arange(e_count, dtype=jnp.int32))
    rank = jnp.arange(T * K, dtype=jnp.int32) - start[exp_s]
    keep = (rank < C) & (exp_s < e_count)
    slot = jnp.where(keep, exp_s * C + rank, e_count * C)  # overflow -> dropped

    z = e_count * C + 1
    idx = jnp.zeros((z,), jnp.int32).at[slot].set(tok_s, mode="drop")[:-1]
    wslot = jnp.zeros((z,), jnp.float32).at[slot].set(w_s, mode="drop")[:-1]
    valid = jnp.zeros((z,), jnp.bool_).at[slot].set(keep, mode="drop")[:-1]
    return idx, wslot, valid


def _moe_expert_compute(lp, x2, idx, wslot, valid, E_loc: int, C: int):
    """Gather -> per-expert gated MLP -> weighted scatter-add."""
    T, D = x2.shape
    xe = jnp.take(x2, idx, axis=0) * valid[:, None].astype(x2.dtype)
    xe = xe.reshape(E_loc, C, D)
    h = jnp.einsum("ecd,edf->ecf", xe, lp["we1"].astype(x2.dtype))
    g = jnp.einsum("ecd,edf->ecf", xe, lp["we3"].astype(x2.dtype))
    y = jnp.einsum(
        "ecf,efd->ecd", jax.nn.silu(h) * g, lp["we2"].astype(x2.dtype)
    ).reshape(E_loc * C, D)
    contrib = y * (wslot * valid).astype(x2.dtype)[:, None]
    return jnp.zeros((T, D), x2.dtype).at[idx].add(contrib, mode="drop")


def moe_capacity(cfg: TransformerCfg, T: int) -> int:
    m = cfg.moe
    C = max(8, int(math.ceil(m.capacity_factor * T * m.top_k / m.n_experts)))
    return min(C, T)


def moe_ffn(cfg: TransformerCfg, lp: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """x: [T, D] -> [T, D].  Single-shard reference path (smoke/CPU)."""
    m = cfg.moe
    T, D = x.shape
    C = moe_capacity(cfg, T)
    gates = jax.nn.softmax(
        (x @ lp["router"].astype(x.dtype)).astype(jnp.float32), axis=-1
    )
    idx, wslot, valid = _moe_dispatch_indices(gates, m.n_experts, m.top_k, C)
    return _moe_expert_compute(lp, x, idx, wslot, valid, m.n_experts, C)


def moe_ffn_shmap(cfg: TransformerCfg, lp, x3, *, mesh, dp_axes, model_axis="model"):
    """Expert-parallel MoE under shard_map: per-shard routing, no global sort.

    Tokens are data-parallel (replicated across the model axis after the
    sequence-parallel all-gather); experts shard over 'model'.  Every model
    shard routes its LOCAL tokens to its LOCAL experts and a psum combines —
    the only cross-shard traffic is the [T_loc, D] partial-output reduce,
    identical to a Megatron TP-FFN all-reduce.  Token order never leaves the
    shard, so the argsort is shard-local (GSPMD would emit a global sort).
    """
    m = cfg.moe
    B, S, D = x3.shape
    mp = mesh.shape[model_axis]
    E_loc = m.n_experts // mp
    from jax.sharding import PartitionSpec as P  # local import: keep models jax-pure

    dp = tuple(a for a in dp_axes if a in mesh.shape)
    x_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None), None, None)
    lp_specs = {
        "router": P(), "we1": P(model_axis), "we3": P(model_axis),
        "we2": P(model_axis),
    }

    # remat INSIDE the body: shard_map residuals are opaque to an outer
    # checkpoint policy — without this the [E_loc, C, F] expert activations
    # get saved per layer (gigabytes; confirmed in the dry-run HLO).
    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def inner(xl, lpl):
        Bl = xl.shape[0]
        T = Bl * S
        C = moe_capacity(cfg, T)
        x2 = xl.reshape(T, D)
        gates = jax.nn.softmax(
            (x2 @ lpl["router"].astype(x2.dtype)).astype(jnp.float32), axis=-1
        )
        e0 = jax.lax.axis_index(model_axis) * E_loc
        idx, wslot, valid = _moe_dispatch_indices(
            gates, m.n_experts, m.top_k, C, e0=e0, e_count=E_loc
        )
        out = _moe_expert_compute(lpl, x2, idx, wslot, valid, E_loc, C)
        out = jax.lax.psum(out, model_axis)
        return out.reshape(Bl, S, D)

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(x_spec, {k: lp_specs[k] for k in ("router", "we1", "we3", "we2")}),
        out_specs=x_spec,
    )
    lp_used = {k: lp[k] for k in ("router", "we1", "we3", "we2")}
    return fn(x3, lp_used)


# ---------------------------------------------------------------------------
# layer + full forward (scan over stacked layers)
# ---------------------------------------------------------------------------


def _attention(cfg, lp, x, positions, *, is_local, kv=None, lengths=None):
    """Full-sequence attention (train/prefill). Returns (out, (k, v))."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"].astype(x.dtype))
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    win_on = cfg.window is not None
    out_g = L.chunked_attention(
        q, k, v, causal=True, window=None, attn_softcap=cfg.attn_softcap,
        chunk_q=cfg.chunk_q, chunk_kv=cfg.chunk_kv,
    )
    if win_on:
        out_l = L.chunked_attention(
            q, k, v, causal=True, window=cfg.window, attn_softcap=cfg.attn_softcap,
            chunk_q=cfg.chunk_q, chunk_kv=cfg.chunk_kv,
        )
        out = jnp.where(is_local, out_l, out_g)
    else:
        out = out_g
    out = jnp.einsum("bshk,hkd->bsd", out, lp["wo"].astype(x.dtype))
    return out, (k, v)


def _ffn(cfg, lp, x, moe_ctx=None):
    B, S, D = x.shape
    if cfg.moe:
        if moe_ctx is not None:
            return moe_ffn_shmap(cfg, lp, x, **moe_ctx)
        return moe_ffn(cfg, lp, x.reshape(B * S, D)).reshape(B, S, D)
    return L.swiglu(x, lp["w1"], lp["w3"], lp["w2"])


def _layer(cfg, lp, x, positions, is_local, constrain, moe_ctx=None):
    h = L.rms_norm(x, lp["attn_norm"])
    attn, _ = _attention(cfg, lp, h, positions, is_local=is_local)
    if cfg.parallel_residual:
        f = _ffn(cfg, lp, h, moe_ctx)
        x = constrain(x + attn + f)
    else:
        x = constrain(x + attn)
        h2 = L.rms_norm(x, lp["ffn_norm"])
        x = constrain(x + _ffn(cfg, lp, h2, moe_ctx))
    return x


def forward(
    cfg: TransformerCfg,
    params: Params,
    tokens: jax.Array,  # int32[B, S]
    *,
    constrain=lambda x: x,  # sharding-constraint hook from the launcher
    moe_ctx: dict | None = None,  # mesh/axes for the shard_map MoE path
) -> jax.Array:
    """Token ids -> final hidden states [B, S, D] (bf16)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
    x = constrain(x)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    # gemma2-style: odd layers local when a window is configured
    local_flags = (
        (jnp.arange(cfg.n_layers) % cfg.local_every) != (cfg.local_every - 1)
        if cfg.window is not None
        else jnp.zeros((cfg.n_layers,), jnp.bool_)
    )

    def body(x, inp):
        lp, is_local = inp
        fn = partial(_layer, cfg, constrain=constrain, moe_ctx=moe_ctx)
        if cfg.remat:
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable
            )
        return fn(lp, x, positions, is_local), None

    x, _ = jax.lax.scan(body, x, (params["layers"], local_flags))
    return L.rms_norm(x, params["final_norm"])


def unembed_logits(cfg, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum(
        "bsd,dv->bsv", h, w.astype(h.dtype), preferred_element_type=jnp.float32
    )
    return L.softcap(logits, cfg.final_softcap)


def loss_fn(
    cfg: TransformerCfg, params: Params, batch: dict, *, constrain=lambda x: x,
    constrain_logits=lambda x: x, moe_ctx: dict | None = None,
) -> jax.Array:
    """Next-token cross-entropy, computed without a [B,S,V] f32 dump.

    The vocab dim shards over 'model'; log-sum-exp and the label gather are
    vocab-local + an all-reduce that GSPMD emits from the sharding.
    """
    tokens, labels = batch["tokens"], batch["labels"]
    h = forward(cfg, params, tokens, constrain=constrain, moe_ctx=moe_ctx)
    logits = constrain_logits(unembed_logits(cfg, params, h))  # [B,S,V] f32, V-sharded
    lmax = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - lmax), axis=-1)) + lmax[..., 0]
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = labels >= 0
    nll = jnp.where(mask, lse - lab, 0.0)
    return nll.sum() / jnp.maximum(mask.sum(), 1)


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with KV cache
# ---------------------------------------------------------------------------


class KVCache:
    """Layout helper: k/v stacked over layers, [L, B, S, Kv, dh] bf16."""

    @staticmethod
    def specs(cfg: TransformerCfg, batch: int, max_seq: int):
        sh = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.d_head)
        return {
            "k": jax.ShapeDtypeStruct(sh, jnp.bfloat16),
            "v": jax.ShapeDtypeStruct(sh, jnp.bfloat16),
        }

    @staticmethod
    def zeros(cfg: TransformerCfg, batch: int, max_seq: int):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), KVCache.specs(cfg, batch, max_seq)
        )


def prefill(cfg: TransformerCfg, params: Params, tokens, *, constrain=lambda x: x,
            moe_ctx: dict | None = None):
    """Process a prompt; returns (last-position logits, kv cache [L,B,S,Kv,dh])."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
    x = constrain(x)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    local_flags = (
        (jnp.arange(cfg.n_layers) % cfg.local_every) != (cfg.local_every - 1)
        if cfg.window is not None
        else jnp.zeros((cfg.n_layers,), jnp.bool_)
    )

    def body(x, inp):
        lp, is_local = inp

        def step(lp, x):
            h = L.rms_norm(x, lp["attn_norm"])
            attn, (k, v) = _attention(cfg, lp, h, positions, is_local=is_local)
            if cfg.parallel_residual:
                x = constrain(x + attn + _ffn(cfg, lp, h, moe_ctx))
            else:
                x = constrain(x + attn)
                x = constrain(x + _ffn(cfg, lp, L.rms_norm(x, lp["ffn_norm"]), moe_ctx))
            return x, (k, v)

        fn = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable) if cfg.remat else step
        x, kv = fn(lp, x)
        return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], local_flags))
    h = L.rms_norm(x, params["final_norm"])
    logits = unembed_logits(cfg, params, h[:, -1:, :])[:, 0]
    return logits, {"k": ks, "v": vs}


def decode_step(
    cfg: TransformerCfg,
    params: Params,
    cache: dict,
    tokens_new: jax.Array,  # int32[B] — one token per sequence
    lengths: jax.Array,  # int32[B] current cache fill (new token position)
    *,
    constrain=lambda x: x,
):
    """One autoregressive step against a [L,B,S,Kv,dh] cache. Linear in S."""
    B = tokens_new.shape[0]
    x = jnp.take(params["embed"], tokens_new, axis=0).astype(jnp.bfloat16)  # [B, D]
    pos = lengths.astype(jnp.int32)  # [B]
    local_flags = (
        (jnp.arange(cfg.n_layers) % cfg.local_every) != (cfg.local_every - 1)
        if cfg.window is not None
        else jnp.zeros((cfg.n_layers,), jnp.bool_)
    )

    def body(x, inp):
        lp, is_local, kc, vc = inp
        h = L.rms_norm(x, lp["attn_norm"])  # [B, D]
        q = jnp.einsum("bd,dhk->bhk", h, lp["wq"].astype(h.dtype))
        k = jnp.einsum("bd,dhk->bhk", h, lp["wk"].astype(h.dtype))
        v = jnp.einsum("bd,dhk->bhk", h, lp["wv"].astype(h.dtype))
        q = L.rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        k = L.rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        # write new k/v at pos (one-hot masked update keeps S shardable)
        S = kc.shape[1]
        onehot = (jnp.arange(S)[None, :] == pos[:, None]).astype(kc.dtype)
        kc = kc * (1 - onehot[..., None, None]) + onehot[..., None, None] * k[:, None]
        vc = vc * (1 - onehot[..., None, None]) + onehot[..., None, None] * v[:, None]
        attn = L.decode_attention(
            q, kc, vc, length=pos + 1, window=cfg.window,
            is_local=is_local if cfg.window is not None else None,
            attn_softcap=cfg.attn_softcap,
        )
        attn = jnp.einsum("bhk,hkd->bd", attn, lp["wo"].astype(h.dtype))
        if cfg.parallel_residual:
            x = x + attn + _ffn(cfg, lp, h[:, None, :])[:, 0]
        else:
            x = x + attn
            x = x + _ffn(cfg, lp, L.rms_norm(x, lp["ffn_norm"])[:, None, :])[:, 0]
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], local_flags, cache["k"], cache["v"])
    )
    h = L.rms_norm(x, params["final_norm"])
    logits = unembed_logits(cfg, params, h[:, None, :])[:, 0]
    return logits, {"k": ks, "v": vs}
