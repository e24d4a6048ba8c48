"""Smoke run of the multi-tenant serve path on a TPU, checked against numpy.

    python chip_smoke.py [--seed N] [--queries 512]
    python chip_smoke.py --four-chips

Builds the paper's geonames corpus at its Table-1 scale (9,415,253 triples
requested, 20 predicates) from ``--seed``, builds the k²-triples store with
its SP/OP DAC predicate index, and serves one skewed multi-tenant trace
through ``ServeBroker`` exactly as ``launch/serve.py`` wires it: all six
serve-IR ops, ~5% SPARQL-shaped ``SelectQ``, 8 Zipf(1.1) tenants,
``max_batch=256``, ``cap=1024`` with cap growth on.

On one chip a kernel differential runs first: every traversal kernel,
compiled, against the XLA (``jnp``) traversal on small random forests and
indexes, with caps that overflow, dead lanes and predicate ids past the
arena.  Then the trace is served three times in this process: with the
compiled Pallas kernels (``backend="pallas", interpret=False``), with the
XLA traversal (``backend="jnp"``), and with the compiled kernels again from
a cap of ``CAP / 16``, so that the broker's cap growth fires and re-serves
the overflowing lanes up to ``CAP``.  Every lane answer of every run must
equal a plain numpy reference built from the generated triples and the
other runs' answers; ``SelectQ`` answers must agree across the runs.
``--four-chips`` instead serves the trace once through the
predicate-sharded serve step (forest sharded by predicate over a
``serve_mesh_shape(4)`` mesh, index replicated) and checks it against the
same reference.

Any failure exits non-zero.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed before it are smoke timings, not benchmark numbers.  With no
TPU visible the script exits 2 before building anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

GEONAMES_TRIPLES = 9_415_253
N_TENANTS = 8
ZIPF_A = 1.1
MAX_BATCH = 256
CAP = 1024
GROW_CAP = CAP // 16  # four doublings (the broker's default budget) reach CAP
SELECT_FRAC = 0.05


class Reference:
    """Answers of the serve-IR ops from the raw (s, p, o) id triples."""

    def __init__(self, ids: np.ndarray):
        s, p, o = ids[:, 0], ids[:, 1], ids[:, 2]
        self.by_s = ids[np.lexsort((o, p, s))]
        self.by_o = ids[np.lexsort((s, p, o))]

    @staticmethod
    def _slice(arr, col, key):
        c = arr[:, col]
        return arr[np.searchsorted(c, key, "left"): np.searchsorted(c, key, "right")]

    def answer(self, op: int, s: int, p: int, o: int):
        from repro.core import engine as eng

        if op in (eng.OP_CHECK, eng.OP_ROW, eng.OP_S_ANY_ANY, eng.OP_S_ANY_O):
            rows = self._slice(self.by_s, 0, s)
            if op == eng.OP_CHECK:
                return bool(((rows[:, 1] == p) & (rows[:, 2] == o)).any())
            if op == eng.OP_ROW:
                return rows[rows[:, 1] == p, 2]
            if op == eng.OP_S_ANY_O:
                return np.unique(rows[rows[:, 2] == o, 1])
            return {int(q): rows[rows[:, 1] == q, 2] for q in np.unique(rows[:, 1])}
        rows = self._slice(self.by_o, 2, o)
        if op == eng.OP_COL:
            return rows[rows[:, 1] == p, 0]
        return {int(q): rows[rows[:, 1] == q, 0] for q in np.unique(rows[:, 1])}


def same(a, b) -> bool:
    """Equality of two decoded answers (bool, id array, {pred: ids}, or a
    SelectQ's {column: array})."""
    if isinstance(a, dict) or isinstance(b, dict):
        return (isinstance(a, dict) and isinstance(b, dict)
                and a.keys() == b.keys()
                and all(same(a[k], b[k]) for k in a))
    flags = [isinstance(x, (bool, np.bool_)) for x in (a, b)]
    if any(flags):
        return all(flags) and bool(a) == bool(b)
    return np.array_equal(np.asarray(a), np.asarray(b))


def device_bytes(arrays) -> int:
    return int(sum(a.size * a.dtype.itemsize for a in arrays))


def check_spread(store, n_dev: int) -> None:
    """The served arena must be spread over the mesh, not left on the first
    device: the live ``t_words`` sharded over ``n_dev`` devices holds an
    equal block of predicates on each of them."""
    import jax

    t = store.forest.t_words
    found = [
        a for a in jax.live_arrays()
        if a.ndim == 2 and a.shape[1] == t.shape[1] and a.dtype == t.dtype
        and len(a.sharding.device_set) == n_dev
    ]
    if not found:
        raise SystemExit(f"no forest sharded over {n_dev} devices is live")
    for a in found:
        shards = a.addressable_shards
        rows = sorted((s.device.id, s.data.shape[0]) for s in shards)
        print(f"sharded t_words {a.shape}: (device, rows) {rows}")
        if (len({d for d, _ in rows}) != n_dev
                or any(r != a.shape[0] // n_dev for _, r in rows)):
            raise SystemExit("the forest is not spread evenly over the mesh")


def kernel_differential(*, seed: int, sides=(300, 5000), scan_caps=(4, 64, 1024),
                        range_caps=(16, 4096), n_lanes: int = 300,
                        interpret: bool = False) -> int:
    """Each traversal kernel, compiled, against the XLA traversal on small
    random forests and indexes; returns the number of comparisons.  Lanes
    include dead ones and predicate ids past the arena, and the small caps
    overflow.  ``interpret`` is for rehearsing this off the chip."""
    import jax.numpy as jnp

    from repro.core import k2forest, predindex
    from repro.core.k2tree import K2Meta, hybrid_ks
    from repro.core.query import ExecConfig
    from repro.kernels import ops

    pl = ExecConfig(backend="pallas", interpret=interpret)
    xla = ExecConfig(backend="jnp", interpret=False)
    rng = np.random.default_rng(seed)
    n = 0

    def agree(name, a, b):
        nonlocal n
        for i, (x, y) in enumerate(zip(a, b)):
            x, y = np.asarray(x), np.asarray(y)
            if x.shape != y.shape or not np.array_equal(x, y):
                raise SystemExit(f"kernel differential: {name}, output {i}: "
                                 "compiled Pallas and jnp disagree")
        n += 1

    for side, n_preds, nnz in zip(sides, (3, 5), (2000, 40000)):
        meta = K2Meta(hybrid_ks(side))
        f, _ = k2forest.build_forest(
            [(rng.integers(0, side, nnz), rng.integers(0, side, nnz))
             for _ in range(n_preds)], meta)
        rows = f.t_words.shape[0]
        preds = rng.integers(-1, n_preds, n_lanes)
        preds[:4] = (n_preds, rows, rows + 3, 10**6)  # past the forest
        keys = rng.integers(0, side, n_lanes)
        axes = rng.integers(0, 2, n_lanes)
        for cap in scan_caps:
            agree(f"k2_scan side={side} cap={cap}",
                  k2forest.scan_batch_mixed(meta, f, preds, keys, axes, cap, pl),
                  k2forest.scan_batch_mixed(meta, f, preds, keys, axes, cap, xla))
        # the kernel's own guard, handed rows past the arena directly
        past = jnp.asarray([rows, rows + 8, 10**6, -1], jnp.int32)
        _, valid, count, ovf = ops.k2_scan_forest(
            meta, f, past, keys[:4], axes[:4], cap=16, interpret=interpret)
        if np.asarray(valid).any() or np.asarray(ovf).any():
            raise SystemExit("k2_scan answered for a row past the arena")
        rp = np.concatenate([np.arange(n_preds), [-1, n_preds, 10**6]])
        for cap in range_caps:
            agree(f"k2_range side={side} cap={cap}",
                  k2forest.range_scan_batch(meta, f, rp, cap, pl),
                  k2forest.range_scan_batch(meta, f, rp, cap, xla))
        args = (rng.integers(-1, n_preds + 1, 8), rng.integers(0, side, 8),
                rng.integers(0, 2, 8), rng.integers(0, n_preds + 1, 8),
                rng.integers(0, 2, 8))
        agree(f"k2_scan_rebind side={side}",
              k2forest.scan_rebind_batch(meta, f, *args, 16, 8, pl),
              k2forest.scan_rebind_batch(meta, f, *args, 16, 8, xla))

    # n_preds 3000 makes gaps above 255: a second DAC chunk level
    ids = np.stack([rng.integers(1, 3000, 60000), rng.integers(1, 3001, 60000),
                    rng.integers(1, 3000, 60000)], 1)
    bi = predindex.build(ids, n_subjects=3000, n_objects=3000, n_preds=3000)
    rows = rng.integers(0, 6000, 700)
    for layout in ("dac", "fixed"):
        dev, pm = bi.select(layout)
        agree(f"pred_gather {layout} levels={pm.levels}",
              predindex.gather_batch(pm, dev, rows, pm.max_degree, pl),
              predindex.gather_batch(pm, dev, rows, pm.max_degree, xla))
    return n


def smoke(*, seed: int, n_queries: int, four_chips: bool) -> int:
    """Build, serve and check; returns the number of lanes checked."""
    import jax

    from repro.core import k2triples
    from repro.core.query import SelectQ
    from repro.data import rdf
    from repro.launch import serve

    t0 = time.perf_counter()
    ds = rdf.generate_like("geonames", GEONAMES_TRIPLES, seed=seed)
    t1 = time.perf_counter()
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    jax.block_until_ready(store.forest)
    t2 = time.perf_counter()
    index, pmeta = store.pred_index.select("dac")
    fb, ib = device_bytes(store.forest), device_bytes(index)
    print(f"store: {store.n_triples} triples, {store.n_preds} preds, "
          f"{store.stats.total_bits / store.n_triples:.2f} bits/triple "
          f"(trees), forest {fb} device bytes "
          f"({fb * 8 / store.n_triples:.1f} bits/triple padded), "
          f"SP/OP index ({pmeta.layout}) {ib} device bytes; "
          f"generated in {t1 - t0:.1f}s, built in {t2 - t1:.1f}s")

    trace = serve.make_trace(
        ds, n_queries, N_TENANTS, zipf_a=ZIPF_A, select_frac=SELECT_FRAC,
        seed=seed + 1,
    )
    ref = Reference(ds.ids)
    runs = ([("pallas", True, CAP)] if four_chips else
            [("pallas", False, CAP), ("jnp", False, CAP),
             ("pallas", False, GROW_CAP)])
    results = []
    for backend, sharded, cap in runs:
        answers: dict = {}
        row = serve.run_bench(
            ds=ds, store=store, trace=trace, backend=backend, sharded=sharded,
            n_queries=n_queries, n_tenants=N_TENANTS, zipf_a=ZIPF_A, cap=cap,
            max_batch=MAX_BATCH, select_frac=SELECT_FRAC, seed=seed,
            quiet=True, answers=answers,
        )
        print(f"run backend={row['backend']} interpret={row['interpret']} "
              f"mode={row['mode']} mesh={row['mesh']} cap={cap}: warmup "
              f"(compiles) "
              f"{row['warmup_s']:.1f}s; smoke timings, not benchmark "
              f"numbers: {row['qps']:.1f} queries/s, p50 {row['p50_ms']} ms, "
              f"{row['batches']} batches, {row['cap_growth_events']} cap "
              f"growths")
        if backend == "pallas" and row["interpret"]:
            raise SystemExit("the Pallas run was interpreted, not compiled")
        if cap < CAP and not row["cap_growth_events"]:
            raise SystemExit(f"no cap growth from cap {cap}: the growth "
                             "path was not exercised")
        if len(answers) != len(trace):
            raise SystemExit(f"{len(answers)} answers for {len(trace)} queries")
        results.append(answers)
        if sharded:
            check_spread(store, len(jax.devices()))

    lanes = selects = 0
    for i, item in enumerate(trace):
        got = [r[i] for r in results]
        if any(not same(g, got[0]) for g in got[1:]):
            raise SystemExit(f"query {i} {item[1:]}: backends disagree")
        if isinstance(item[1], SelectQ):
            selects += 1
            continue
        _, op, s, p, o = item
        want = ref.answer(op, s, p, o)
        if not same(got[0], want):
            raise SystemExit(
                f"query {i} (op {op}, s {s}, p {p}, o {o}): got {got[0]!r}, "
                f"numpy reference {want!r}")
        lanes += 1
    print(f"checked {lanes} lanes against numpy"
          + ("" if four_chips else " and across all three runs")
          + f"; {selects} SelectQ answers"
          + ("" if four_chips else " equal across the runs"))
    return lanes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="serve through the predicate-sharded step over four chips",
    )
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    want = 4 if args.four_chips else 1
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX sees {devs[0].platform!r} devices", file=sys.stderr)
        return 2
    if len(devs) < want:
        print(f"need {want} TPU chips, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    print(f"device: {devs[0].device_kind} x {len(devs)}")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    if not args.four_chips:
        t0 = time.perf_counter()
        n = kernel_differential(seed=args.seed)
        print(f"kernel differential: {n} compiled-Pallas vs jnp comparisons "
              f"equal ({time.perf_counter() - t0:.1f}s)")
    smoke(seed=args.seed, n_queries=args.queries, four_chips=args.four_chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
