"""Multi-tenant serving benchmark: drive the streaming broker with a
skewed tenant trace and report sustained queries/sec + per-QUERY tail
latency, single-device and predicate-sharded.

    python -m repro.launch.serve --triples 100000 --tenants 8 --queries 4096
    python -m repro.launch.serve --fast --sharded --json serve_rows.json

The harness builds a store, compiles ONE base ``ServeQ`` plan through
:class:`repro.launch.broker.ServeBroker`, replays a Zipf-skewed
multi-tenant trace of mixed serve-IR ops through per-tenant async
streams, and reports the broker's structured stats.  Latency is measured
per query (submit -> decoded result), never per batch, and tail
percentiles follow ``tail_percentile``'s sample-count guard — a p99 is
only printed when 100+ samples support it.

All execution knobs ride an explicit ``ExecConfig`` (env flags folded in
once via ``ExecConfig.from_env``); ``--sharded`` factors the serve mesh
from the ACTUAL device count (``mesh.serve_mesh_shape`` — every device
used or a loud failure) and refuses to run when only one device is
visible rather than silently degrading to single-device numbers.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np

from repro import obs
from repro.launch.broker import (
    CoalescePolicy, ServeBroker, TenantPolicy, tail_percentile,
)
from repro.launch.cache import use_compile_cache

# mixed-op trace composition: production traffic is mostly point lookups
# and bounded scans, with a thin unbounded-?P tail (the paper's worst case)
_OP_WEIGHTS = {
    0: 0.30,  # OP_CHECK
    1: 0.25,  # OP_ROW
    2: 0.25,  # OP_COL
    3: 0.08,  # OP_S_ANY_ANY
    4: 0.07,  # OP_ANY_ANY_O
    5: 0.05,  # OP_S_ANY_O
}


def zipf_weights(n_tenants: int, a: float) -> np.ndarray:
    """Normalized Zipf(a) tenant weights: tenant 0 is the heaviest."""
    w = 1.0 / np.arange(1, n_tenants + 1, dtype=np.float64) ** a
    return w / w.sum()


def make_trace(
    ds, n_queries: int, n_tenants: int, *, zipf_a: float = 1.1,
    unbounded: bool = True, select_frac: float = 0.0, seed: int = 0,
) -> list[tuple]:
    """A skewed multi-tenant trace: ``(tenant, op, s, p, o)`` lane rows,
    plus ``(tenant, SelectQ)`` rows for a ``select_frac`` fraction of the
    trace (SPARQL-shaped queries anchored on real subjects: a bounded
    WHERE scan with an OPTIONAL second predicate, ordered and limited).

    Tenants are Zipf(a)-weighted; ops follow ``_OP_WEIGHTS`` (bounded-only
    when ``unbounded=False``); ids come from real triples so every query
    has a non-empty answer shape to decode.
    """
    from repro.core.query import SelectQ, TriplePatternQ

    rng = np.random.default_rng(seed)
    ops_pool = [op for op in _OP_WEIGHTS if unbounded or op < 3]
    p_ops = np.array([_OP_WEIGHTS[op] for op in ops_pool])
    p_ops = p_ops / p_ops.sum()
    ops = rng.choice(ops_pool, size=n_queries, p=p_ops)
    tenants = rng.choice(n_tenants, size=n_queries, p=zipf_weights(n_tenants, zipf_a))
    rows = ds.ids[rng.integers(0, ds.n_triples, n_queries)]
    is_select = rng.random(n_queries) < select_frac
    trace: list[tuple] = []
    for i in range(n_queries):
        s, p, o = map(int, rows[i])
        tenant = f"tenant-{tenants[i]}"
        if is_select[i]:
            p2 = int(rng.integers(1, ds.n_preds + 1))
            trace.append((tenant, SelectQ(
                where=(TriplePatternQ(s, p, "?o"),),
                optional=((TriplePatternQ(s, p2, "?x"),),),
                order_by=("?o",),
                limit=16,
            )))
            continue
        if ops[i] >= 3:
            p = 0  # unbounded-?P ops leave the predicate free
        trace.append((tenant, int(ops[i]), s, p, o))
    return trace


async def _replay(broker: ServeBroker, trace, answers: dict | None = None) -> int:
    """Replay the trace as one async stream per tenant (per-tenant FIFO),
    counting decoded results.  Rows are ``(tenant, op, s, p, o)`` lanes
    or ``(tenant, SelectQ)`` full-shape queries — ``broker.stream``
    accepts both item shapes.  With ``answers``, each decoded result is
    stored there under its trace index."""
    per_tenant: dict[str, list] = {}
    for i, (tenant, *rest) in enumerate(trace):
        per_tenant.setdefault(tenant, []).append(
            (i, rest[0] if len(rest) == 1 else tuple(rest))
        )

    async def one(tenant, items):
        n = 0
        async for res in broker.stream(tenant, [q for _, q in items]):
            if answers is not None:
                answers[items[n][0]] = res
            n += 1
        return n

    counts = await asyncio.gather(
        *(one(t, items) for t, items in per_tenant.items())
    )
    return sum(counts)


def run_bench(
    *,
    n_triples: int = 100_000,
    n_preds: int = 64,
    n_tenants: int = 8,
    n_queries: int = 4096,
    zipf_a: float = 1.1,
    cap: int = 1024,
    max_batch: int = 256,
    deadline_ms: float = 2.0,
    backend: str | None = None,
    donate: bool | None = None,
    sharded: bool = False,
    unbounded: bool = True,
    select_frac: float = 0.0,
    warmup: int = 64,
    seed: int = 0,
    quiet: bool = False,
    trace_path: str | None = None,
    metrics_path: str | None = None,
    obs_on: bool = False,
    ds=None,
    store=None,
    trace: list | None = None,
    answers: dict | None = None,
) -> dict:
    """Build a store, serve a skewed multi-tenant trace through the
    broker, and return one machine-readable serving row.

    ``ds`` / ``store`` pass a prebuilt dataset and the store built from
    it (``n_triples`` / ``n_preds`` are then unused), ``trace`` a
    prebuilt ``make_trace`` trace; ``answers``, when given, receives
    every decoded result of the measured replay keyed by trace index, so
    a caller can check them.

    ``trace_path`` / ``metrics_path`` / ``obs_on`` switch the
    observability layer on for the measured window (trace and metrics
    are cleared at the warmup boundary, together with the broker's own
    stats, so exports describe exactly the run the row reports):
    ``trace_path`` gets the Chrome ``trace_event`` JSON, ``metrics_path``
    the metrics snapshot + per-plan cost profiles + Prometheus text."""
    import jax

    from repro.core import engine as eng, k2triples
    from repro.core.query import ExecConfig
    from repro.data import rdf
    from repro.launch import mesh as meshlib

    if ds is None:
        ds = rdf.generate(
            n_triples,
            n_subjects=max(64, n_triples // 12),
            n_preds=n_preds,
            n_objects=max(64, n_triples // 8),
            preds_per_subject=min(6, n_preds),
            seed=seed,
        )
    t0 = time.time()
    if store is None:
        store = k2triples.from_id_triples(
            ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
            n_objects=ds.n_objects, n_preds=ds.n_preds,
        )
    if not quiet:
        print(
            f"store: {store.n_triples} triples, {store.n_preds} preds, "
            f"side {store.meta.side}, "
            f"{store.stats.total_bits/8/1024:.1f} KiB structure "
            f"({store.stats.total_bits/max(store.n_triples,1):.2f} bits/triple), "
            f"built in {time.time()-t0:.1f}s"
        )

    n_dev = len(jax.devices())
    overrides: dict = {"cap": cap}
    if backend is not None:
        overrides["backend"] = backend
    if donate is not None:
        overrides["donate_batch"] = donate
    mesh_shape = None
    if sharded:
        if n_dev < 2:
            raise ValueError(
                "--sharded requested but only one device is visible; "
                "refusing to silently serve unsharded (run on a multi-"
                "device backend, or fake hosts with XLA_FLAGS="
                "--xla_force_host_platform_device_count=N)"
            )
        mesh_shape = meshlib.serve_mesh_shape(n_dev)
        overrides["mesh"] = meshlib.make_mesh(mesh_shape, ("data", "model"))
        if not quiet:
            print(f"sharded over mesh {{'data': {mesh_shape[0]}, 'model': {mesh_shape[1]}}}")
    cfg = ExecConfig.from_env(**overrides)

    engine = eng.Engine(store)
    if trace is None:
        trace = make_trace(
            ds, n_queries, n_tenants, zipf_a=zipf_a, unbounded=unbounded,
            select_frac=select_frac, seed=seed + 1,
        )
    n_queries = len(trace)
    # bound per-tenant windows so ~two coalesced batches stay outstanding:
    # the pipeline keeps both buffers fed while latency still means
    # "time through the broker", not "time parked in an unbounded queue"
    depth = max(16, (2 * max_batch) // max(n_tenants, 1))

    obs_enabled = obs_on or trace_path is not None or metrics_path is not None
    tracer = metrics = None
    if obs_enabled:
        from repro.core.query import ObsConfig

        tracer, metrics = obs.enable(ObsConfig(trace=True, metrics=True))

    async def main():
        broker = ServeBroker(
            engine, cfg, unbounded=unbounded,
            coalesce=CoalescePolicy(
                max_batch=max_batch, max_delay_s=deadline_ms * 1e-3
            ),
            tenant_policy=TenantPolicy(queue_depth=depth),
        )
        async with broker:
            # warmup: compile the serve program + prime every op type
            tw = time.perf_counter()
            await _replay(broker, trace[: min(warmup, len(trace))])
            warm = time.perf_counter() - tw
            broker.reset_stats()
            if tracer is not None:
                tracer.clear()
            if metrics is not None:
                metrics.reset()
            t0 = time.perf_counter()
            n_done = await _replay(broker, trace, answers)
            wall = time.perf_counter() - t0
        return broker, broker.stats(), n_done, wall, warm

    try:
        broker, stats, n_done, wall, warm = asyncio.run(main())
        if obs_enabled:
            _export_obs(
                broker, engine, tracer, metrics,
                trace_path=trace_path, metrics_path=metrics_path,
                quiet=quiet,
            )
    finally:
        if obs_enabled:
            obs.disable()
    assert n_done == n_queries, (n_done, n_queries)
    row = {
        "mode": "sharded" if sharded else "single",
        "mesh": list(mesh_shape) if mesh_shape else None,
        "devices": n_dev,
        "backend": cfg.backend,
        "interpret": cfg.resolved().interpret,
        "triples": store.n_triples,
        "preds": store.n_preds,
        "tenants": n_tenants,
        "zipf_a": zipf_a,
        "unbounded": unbounded,
        "queries": n_queries,
        "select_frac": select_frac,
        "selects": stats["selects"],
        "cap": cap,
        "max_batch": max_batch,
        "donate": cfg.donate_batch and cfg.mesh is None,
        "pred_index_layout": cfg.pred_index_layout,
        "deadline_ms": deadline_ms,
        "warmup_s": warm,
        "wall_s": wall,
        "qps": n_queries / wall,
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "coalesce_factor": stats["coalesce_factor"],
        "batches": stats["batches"],
        "shed": stats["shed"],
        "cap_growth_events": stats["cap_growth_events"],
        "queue_peak": stats["queue_peak"],
        "obs": obs_enabled,
        "per_tenant": stats["tenants"],
    }
    if not quiet:
        print(format_row(row))
    return row


def _export_obs(broker, engine, tracer, metrics, *, trace_path, metrics_path,
                quiet):
    """Write the run's observability exports: Chrome trace JSON and a
    metrics document (broker + obs registries, plan-cache stats, per-plan
    cost profiles, Prometheus text exposition)."""
    if trace_path is not None and tracer is not None:
        with open(trace_path, "w") as fh:
            json.dump(tracer.to_chrome(metadata=obs.provenance()), fh)
        if not quiet:
            print(f"# wrote {trace_path} ({tracer.dropped} spans dropped)")
    if metrics_path is not None:
        doc = {
            "provenance": obs.provenance(),
            "broker": broker.metrics.snapshot(),
            "obs": metrics.snapshot() if metrics is not None else {},
            "plan_cache": engine.plan_cache_stats,
            "cost_profiles": broker.cost_profiles(),
            "prometheus": (
                broker.metrics.to_prometheus()
                + (metrics.to_prometheus() if metrics is not None else "")
            ),
        }
        with open(metrics_path, "w") as fh:
            json.dump(doc, fh, indent=2, default=float)
        if not quiet:
            print(f"# wrote {metrics_path}")


def format_row(row: dict) -> str:
    def pct(v):
        return f"{v:.2f} ms" if v is not None else "n/a (insufficient samples)"

    return (
        f"{row['mode']} x {row['backend']}: {row['queries']} queries, "
        f"{row['tenants']} tenants (zipf {row['zipf_a']}): "
        f"{row['qps']:,.0f} queries/s sustained, per-query p50 {pct(row['p50_ms'])}, "
        f"p99 {pct(row['p99_ms'])}, coalesce x{row['coalesce_factor']:.1f} "
        f"({row['batches']} batches), {row['cap_growth_events']} cap growths, "
        f"{row['shed']} shed"
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--triples", type=int, default=100_000)
    ap.add_argument("--preds", type=int, default=64)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--zipf", type=float, default=1.1, help="tenant skew exponent")
    ap.add_argument("--queries", type=int, default=4096, help="trace length")
    ap.add_argument("--batch", type=int, default=256, help="coalesce max_batch")
    ap.add_argument(
        "--deadline-ms", type=float, default=2.0,
        help="coalesce deadline for the oldest pending query",
    )
    ap.add_argument("--cap", type=int, default=1024)
    ap.add_argument(
        "--backend", default=None, choices=("pallas", "jnp"),
        help="scan backend override (default: ExecConfig.from_env)",
    )
    ap.add_argument("--sharded", action="store_true", help="shard over local devices")
    ap.add_argument(
        "--no-donate", action="store_true",
        help="disable per-batch buffer donation (the before/after knob)",
    )
    ap.add_argument(
        "--bounded-only", action="store_true",
        help="trace without unbounded-?P ops (compiles the u_* block out)",
    )
    ap.add_argument(
        "--select-frac", type=float, default=0.0,
        help="fraction of the trace served as SPARQL-shaped SelectQ "
             "queries (OPTIONAL + ORDER/LIMIT) instead of raw lanes",
    )
    ap.add_argument("--fast", action="store_true", help="tiny smoke-test trace")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--json", metavar="PATH", default=None,
        help="write the serving rows as JSON ({'serving': [...]})",
    )
    ap.add_argument(
        "--trace", nargs="?", const="serve_trace.json", default=None,
        metavar="PATH",
        help="enable tracing; write Chrome trace_event JSON "
             "(default PATH: serve_trace.json — load it in Perfetto)",
    )
    ap.add_argument(
        "--metrics", nargs="?", const="serve_metrics.json", default=None,
        metavar="PATH",
        help="enable metrics; write snapshot + cost profiles + Prometheus "
             "text (default PATH: serve_metrics.json)",
    )
    ap.add_argument(
        "--obs-overhead", action="store_true",
        help="run the bench twice (observability off, then on) and report "
             "the p50/qps overhead of tracing",
    )
    args = ap.parse_args(argv)
    use_compile_cache()

    kw = dict(
        n_triples=args.triples, n_preds=args.preds, n_tenants=args.tenants,
        n_queries=args.queries, zipf_a=args.zipf, cap=args.cap,
        max_batch=args.batch, deadline_ms=args.deadline_ms,
        backend=args.backend, sharded=args.sharded,
        donate=(False if args.no_donate else None),
        unbounded=not args.bounded_only, select_frac=args.select_frac,
        seed=args.seed,
    )
    if args.fast:
        kw.update(
            n_triples=20_000, n_preds=16, n_queries=256, max_batch=64,
            cap=256, warmup=32,
        )
    try:
        if args.obs_overhead:
            rows = [run_bench(**kw)]
            rows.append(run_bench(
                **kw, obs_on=True,
                trace_path=args.trace, metrics_path=args.metrics,
            ))
            off, on = rows
            print(format_overhead(off, on))
        else:
            rows = [run_bench(
                **kw, trace_path=args.trace, metrics_path=args.metrics,
            )]
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"serving": rows}, fh, indent=2, default=float)
        print(f"# wrote {args.json}")


def format_overhead(off: dict, on: dict) -> str:
    """One-line tracing-overhead report from an off/on run pair."""
    parts = [f"obs overhead: qps {off['qps']:,.0f} -> {on['qps']:,.0f} "
             f"({(off['qps'] - on['qps']) / off['qps'] * 100:+.1f}%)"]
    if off["p50_ms"] is not None and on["p50_ms"] is not None:
        parts.append(
            f"p50 {off['p50_ms']:.3f} -> {on['p50_ms']:.3f} ms "
            f"({(on['p50_ms'] - off['p50_ms']) / off['p50_ms'] * 100:+.1f}%)"
        )
    return ", ".join(parts)


if __name__ == "__main__":
    main()
