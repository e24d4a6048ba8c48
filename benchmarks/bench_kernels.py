"""Kernel microbenches: Pallas (interpret) vs pure-jnp reference.

On this CPU container interpret-mode timings measure the Python interpreter,
not the TPU — so the REPORTED metric is (a) correctness deltas and (b) the
jnp-reference throughput, plus the analytic VMEM/roofline characteristics of
each kernel's blocking (what you'd check before burning TPU time).
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import k2forest, k2tree
from repro.core.k2tree import K2Meta, hybrid_ks
from repro.kernels import ref

from repro.launch.mesh import V5E, peaks

# the analytic floors printed below are for one TPU v5e chip
PEAK = peaks(V5E)


def _t(fn, *a, n=5):
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def run():
    rng = np.random.default_rng(0)
    rows = []

    # popcount: ref throughput + analytic TPU roofline occupancy
    w = jnp.asarray(rng.integers(0, 2**32, (4096, 512), dtype=np.uint32))
    t = _t(jax.jit(ref.popcount_ref), w)
    nbytes = w.size * 4 * 2
    rows.append(("popcount", t * 1e3, f"{nbytes/t/1e9:.1f} GB/s cpu; "
                 f"v5e mem-bound floor {nbytes/PEAK.hbm_bw*1e6:.1f} us"))

    # k2_check: batched point queries
    meta = K2Meta(hybrid_ks(100_000))
    r = rng.integers(0, 100_000, 100_000)
    c = rng.integers(0, 100_000, 100_000)
    tree = k2tree.build(r, c, meta)
    q = 65_536
    qr = jnp.asarray(rng.integers(0, 100_000, q), jnp.int32)
    qc = jnp.asarray(rng.integers(0, 100_000, q), jnp.int32)
    f = jax.jit(lambda qr, qc: ref.k2_check_ref(
        meta, qr, qc, tree.t.words, tree.t.rank_blocks, tree.l.words,
        tree.ones_before, tree.level_start))
    t = _t(f, qr, qc)
    rows.append(("k2_check", t * 1e3,
                 f"{q/t/1e6:.1f} Mqueries/s cpu ({meta.n_levels} levels, "
                 f"arena {int(tree.t.words.size+tree.l.words.size)*4/1024:.0f} KiB -> VMEM-resident)"))

    # k2_scan: batched mixed row/col scans over a forest (the serve hot path)
    scan_side = 20_000
    smeta = K2Meta(hybrid_ks(scan_side))
    coords = []
    for _ in range(8):
        n = 40_000
        coords.append((rng.integers(0, scan_side, n), rng.integers(0, scan_side, n)))
    forest, _ = k2forest.build_forest(coords, smeta)
    sq = 2048
    cap = 128
    sp = jnp.asarray(rng.integers(0, 8, sq), jnp.int32)
    sk = jnp.asarray(rng.integers(0, scan_side, sq), jnp.int32)
    sa = jnp.asarray(rng.integers(0, 2, sq), jnp.int32)
    f_jnp = jax.jit(lambda p, k, a: k2forest.scan_batch_mixed(
        smeta, forest, p, k, a, cap, backend="jnp").ids)
    t = _t(f_jnp, sp, sk, sa, n=3)
    rows.append(("k2_scan(jnp-ref)", t * 1e3,
                 f"{sq/t/1e3:.1f} Kscans/s cpu ({smeta.n_levels} levels, cap {cap})"))
    f_pl = jax.jit(lambda p, k, a: k2forest.scan_batch_mixed(
        smeta, forest, p, k, a, cap, backend="pallas").ids)
    t_pl = _t(f_pl, sp, sk, sa, n=3)
    arena_kib = int(forest.t_words.size + forest.l_words.size) * 4 / 1024
    rows.append(("k2_scan(pallas-interp)", t_pl * 1e3,
                 f"{sq/t_pl/1e3:.1f} Kscans/s cpu; forest arena "
                 f"{arena_kib:.0f} KiB, HBM-resident; "
                 f"agrees bit-exact with jnp ref (tests/test_k2_scan.py)"))

    # pred_gather: SP/OP candidate-predicate gather (pruned unbounded path)
    from repro.core import predindex

    gids = np.stack([
        rng.integers(1, scan_side + 1, 120_000),
        rng.integers(1, 65, 120_000),
        rng.integers(1, scan_side + 1, 120_000),
    ], axis=1)
    bi = predindex.build(
        gids, n_subjects=scan_side, n_objects=scan_side, n_preds=64
    )
    grows = jnp.asarray(rng.integers(0, scan_side, sq), jnp.int32)
    for be, label in (("jnp", "jnp-ref"), ("pallas", "pallas-interp")):
        f_g = jax.jit(lambda r, be=be: predindex.gather_batch(
            bi.meta, bi.device, r, bi.meta.max_degree, be).ids)
        t_g = _t(f_g, grows, n=3)
        rows.append((f"pred_gather({label})", t_g * 1e3,
                     f"{sq/t_g/1e3:.1f} Kgathers/s cpu (max degree "
                     f"{bi.meta.max_degree}, {bi.meta.bytes_per_pred} B/entry, "
                     f"index {bi.stats.payload_bits/8/1024:.0f} KiB)"))

    # k2_range: batched (?S,P,?O) pair enumeration (dataset-dump path)
    rcap = 512
    rq = jnp.asarray(rng.integers(0, 8, 64), jnp.int32)
    f_rj = jax.jit(lambda p: k2forest.range_scan_batch(
        smeta, forest, p, rcap, backend="jnp").rows)
    t = _t(f_rj, rq, n=3)
    rows.append(("k2_range(jnp-ref)", t * 1e3,
                 f"{rq.size/t:.0f} trees/s cpu (cap {rcap}, Morton order)"))
    f_rp = jax.jit(lambda p: k2forest.range_scan_batch(
        smeta, forest, p, rcap, backend="pallas").rows)
    t_rp = _t(f_rp, rq, n=3)
    rows.append(("k2_range(pallas-interp)", t_rp * 1e3,
                 f"{rq.size/t_rp:.0f} trees/s cpu; agrees bit-exact with jnp "
                 f"ref (tests/test_k2_range.py)"))

    # k2_scan_rebind: fused X-scan + re-bind (join categories D-F)
    jq, jcx, jcy = 16, 64, 32
    jp1 = jnp.asarray(rng.integers(0, 8, jq), jnp.int32)
    jk1 = jnp.asarray(rng.integers(0, scan_side, jq), jnp.int32)
    ja1 = jnp.asarray(rng.integers(0, 2, jq), jnp.int32)
    jp2 = jnp.asarray(rng.integers(0, 8, jq), jnp.int32)
    ja2 = jnp.asarray(rng.integers(0, 2, jq), jnp.int32)
    f_bj = jax.jit(lambda *a: k2forest.scan_rebind_batch(
        smeta, forest, *a, jcx, jcy, "jnp")[4])
    t = _t(f_bj, jp1, jk1, ja1, jp2, ja2, n=3)
    rows.append(("k2_scan_rebind(jnp-ref)", t * 1e3,
                 f"{jq/t:.0f} joins/s cpu (cap_x {jcx}, cap_y {jcy})"))
    f_bp = jax.jit(lambda *a: k2forest.scan_rebind_batch(
        smeta, forest, *a, jcx, jcy, "pallas")[4])
    t_bp = _t(f_bp, jp1, jk1, ja1, jp2, ja2, n=3)
    rows.append(("k2_scan_rebind(pallas-interp)", t_bp * 1e3,
                 f"{jq/t_bp:.0f} joins/s cpu; fused scan->rebind, no host "
                 f"round-trip; bit-exact vs jnp (tests/test_joins_kernel.py)"))

    # sorted_intersect
    a = jnp.asarray(np.sort(rng.choice(10**7, 2**16, replace=False)).astype(np.int32))
    b = jnp.asarray(np.sort(rng.choice(10**7, 2**18, replace=False)).astype(np.int32))
    f = jax.jit(ref.sorted_intersect_mask_ref)
    t = _t(f, a, b)
    rows.append(("sorted_intersect", t * 1e3, f"{a.size/t/1e6:.1f} Mlanes/s cpu"))

    # block_spmm: masked vs dense flops at 25% occupancy
    M = K = 1024; D = 512
    mask = (rng.random((M // 128, K // 128)) < 0.25).astype(np.int32)
    A = jnp.asarray((rng.random((M, K)) < 0.05).astype(np.float32))
    X = jnp.asarray(rng.standard_normal((K, D)).astype(np.float32))
    f = jax.jit(lambda m, a, x: ref.block_spmm_ref(m, a, x))
    t = _t(f, jnp.asarray(mask), A, X)
    dense_flops = 2 * M * K * D
    skipped = 1 - mask.mean()
    rows.append(("block_spmm", t * 1e3,
                 f"{dense_flops/t/1e9:.1f} GFLOP/s cpu dense-equiv; mask skips "
                 f"{skipped*100:.0f}% of tiles -> v5e compute floor "
                 f"{dense_flops*(1-skipped)/PEAK.flops_bf16*1e6:.1f} us"))
    return rows


def main(csv=print):
    csv("# kernel microbenches (cpu reference timings + tpu analytic floors)")
    csv("kernel,ms_per_call,derived")
    for name, ms, d in run():
        csv(f"{name},{ms:.3f},{d}")


if __name__ == "__main__":
    main()
