"""Benchmark harness: one module per paper table + kernel microbenches.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--json [OUT.json]]

Emits CSV blocks per table (the EXPERIMENTS.md §Paper-validation source;
see EXPERIMENTS.md at the repo root for how to read each block, including
the SP/OP index-overhead columns).  ``--json`` additionally writes every
table as machine-readable JSON — with no path it lands at
``BENCH_results.json`` in the repo root, the committed perf-trajectory
file (``BENCH_*.json``) that CI also uploads as an artifact.

Every backend comparison is driven by explicit ``ExecConfig`` objects
(see ``bench_patterns.BACKEND_CFGS`` / ``bench_joins.run``); the harness
never mutates ``REPRO_SCAN_BACKEND``.

Each JSON lands with a ``provenance`` header (git SHA, UTC timestamp,
jax version, backend, device kind/count — ``repro.obs.provenance``) so
the committed perf trajectory is self-describing.  ``--trace`` /
``--metrics`` switch the observability layer on around the sweep and
write its Chrome-trace / metrics exports next to the results.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

DEFAULT_JSON = str(pathlib.Path(__file__).resolve().parent.parent / "BENCH_results.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller datasets")
    ap.add_argument(
        "--json", metavar="PATH", nargs="?", const=DEFAULT_JSON, default=None,
        help="also write all tables as JSON (default path: BENCH_results.json "
        "at the repo root)",
    )
    ap.add_argument(
        "--trace", nargs="?", const="bench_trace.json", default=None,
        metavar="PATH",
        help="trace the sweep; write Chrome trace_event JSON",
    )
    ap.add_argument(
        "--metrics", nargs="?", const="bench_metrics.json", default=None,
        metavar="PATH",
        help="write the obs metrics snapshot + Prometheus text as JSON",
    )
    args = ap.parse_args()

    from repro import obs
    from repro.launch.cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        bench_compression, bench_dynamic, bench_joins, bench_kernels,
        bench_patterns, bench_queries, bench_serve,
    )

    tracer = metrics = None
    if args.trace is not None or args.metrics is not None:
        from repro.core.query import ObsConfig

        tracer, metrics = obs.enable(ObsConfig())

    results: dict = {"fast": bool(args.fast), "provenance": obs.provenance()}
    t0 = time.time()
    print("=" * 72)
    print("# Table 2 analogue: compression (bits/triple, ID space)")
    print(bench_compression.CSV_HEADER)
    comp = (
        bench_compression.run(n_triples=30_000, datasets=("geonames", "dbtune"))
        if args.fast
        else bench_compression.run()
    )
    for r in comp:
        print(bench_compression.format_row(r))
    results["compression"] = comp

    print("=" * 72)
    print("# Table 3 analogue: ms/pattern (k2 vs vertical tables)")
    print(bench_patterns.CSV_HEADER)
    pats = (
        bench_patterns.run(n_triples=30_000, n_preds=16, n_queries=20)
        if args.fast
        else bench_patterns.run()
    )
    for k, (a, b) in pats.items():
        print(bench_patterns.format_row(k, a, b))
    results["patterns"] = {
        k: {"k2_ms": a, "vertical_ms": (None if b != b else b)}
        for k, (a, b) in pats.items()
    }

    print("# Pruned unbounded-?P (k2-triples+ SP/OP index) vs all-preds sweep")
    prows, pinfo = (
        bench_patterns.run_pruned(n_triples=20_000, n_queries=32)
        if args.fast
        else bench_patterns.run_pruned()
    )
    print(bench_patterns.format_pruned_info(pinfo))
    print(bench_patterns.PRUNED_CSV_HEADER)
    for r in prows:
        print(bench_patterns.format_pruned_row(r))
    results["patterns_pruned"] = {"info": pinfo, "rows": prows}

    print("=" * 72)
    print("# Table 4 analogue: ms/query by join category x scan backend")
    print("category,ms_per_query")
    joins = (
        bench_joins.run(n_triples=20_000, n_preds=12, n_each=5)
        if args.fast
        else bench_joins.run()
    )
    for k, v in joins.items():
        print(f"{k},{v:.2f}")
    results["joins"] = joins

    print("=" * 72)
    print("# Serving: streaming multi-tenant broker (Zipf trace, mixed ops)")
    print(bench_serve.CSV_HEADER)
    srows = bench_serve.run(fast=args.fast)
    for r in srows:
        print(bench_serve.format_row(r))
    results["serving"] = srows

    print("=" * 72)
    print("# Dynamic store: churn (insert qps, read tails vs delta "
          "fraction, compaction pause)")
    print(bench_dynamic.CSV_HEADER)
    dyn_res = bench_dynamic.run(fast=args.fast)
    for line in bench_dynamic.format_rows(dyn_res):
        print(line)
    results["dynamic"] = dyn_res

    print("=" * 72)
    print("# Query planner: cost-ordered vs greedy vs worst join orders")
    print(bench_queries.CSV_HEADER)
    qrows = bench_queries.run(fast=args.fast)
    for r in qrows:
        print(bench_queries.format_row(r))
    results["queries"] = qrows

    print("=" * 72)
    print("# kernel microbenches (cpu ref timings + TPU roofline analytics)")
    print("kernel,ms,notes")
    kern = bench_kernels.run()
    for name, ms, note in kern:
        print(f"{name},{ms:.3f},{note}")
    results["kernels"] = [
        {"kernel": n, "ms": ms, "notes": note} for n, ms, note in kern
    ]

    print("=" * 72)
    results["total_s"] = time.time() - t0
    print(f"# total {results['total_s']:.0f}s")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(results, fh, indent=2, default=float)
        print(f"# wrote {args.json}")
    if tracer is not None and args.trace is not None:
        with open(args.trace, "w") as fh:
            json.dump(tracer.to_chrome(metadata=results["provenance"]), fh)
        print(f"# wrote {args.trace} ({tracer.dropped} spans dropped)")
    if metrics is not None and args.metrics is not None:
        with open(args.metrics, "w") as fh:
            json.dump(
                {
                    "provenance": results["provenance"],
                    "metrics": metrics.snapshot(),
                    "prometheus": metrics.to_prometheus(),
                },
                fh, indent=2, default=float,
            )
        print(f"# wrote {args.metrics}")
    if tracer is not None or metrics is not None:
        obs.disable()


if __name__ == "__main__":
    main()
