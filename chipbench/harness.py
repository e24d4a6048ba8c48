"""One run of one cell: set up, measure a window, check every answer, report.

Set-up builds the corpus from the seed, builds the store with
``k2triples.from_id_triples`` (compiled Pallas backend, DAC SP/OP index),
starts a ``ServeBroker`` with the config's serving settings, and warms it up
with a burst of the cell's own traffic, which compiles (or loads from the
cache) the one program the cell serves through.  The window then drives the
broker for ``seconds`` with the cell's loop.  Once it has closed, the
device's peak memory is read, the program's state is freed, and every
answer is compared with the numpy reference.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import corpus as corpus_mod
from chipbench import loops, traffic
from chipbench import tracereduce as tr

GRACE_S = 60.0  # how long an answer due in the window is awaited after it


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Watch:
    """What happens in this process while ``active`` (the window): JAX
    traces and backend compiles, which should be none, and garbage-collector
    pauses, which stall the broker and the load generator alike."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        self.gc_t0 = 0.0
        self.gc_pauses = {0: [], 1: [], 2: []}
        jax.monitoring.register_event_duration_secs_listener(self._on_jax)
        gc.callbacks.append(self._on_gc)

    def _on_jax(self, event, duration, **_kw):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _on_gc(self, phase, info):
        if phase == "start":
            self.gc_t0 = time.perf_counter()
        elif self.active:
            self.gc_pauses[info["generation"]].append(time.perf_counter() - self.gc_t0)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_jax)
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> str:
        gcs = ", ".join(
            f"gen {g}: {len(p)}, longest {max(p, default=0) * 1e3:.3f} ms"
            for g, p in self.gc_pauses.items())
        return (f"compiles in window: {self.counts['compiles']} backend compiles, "
                f"{self.counts['traces']} traces; gc pauses: {gcs}")


@dataclasses.dataclass
class Run:
    """What the metric readers read (see ``metrics/``)."""

    cell: object
    seed: int
    setup_s: float
    window_s: float
    loop: str
    latency_s: np.ndarray  # per request of the window; window + grace where none came
    answered_in_window: int
    n_triples: int
    live_device_bytes: int
    spans: list | None = None  # host spans of the window (``repro.obs``)
    device: dict | None = None  # the reduced profiler trace (``tracereduce``)


def live_device_bytes(device) -> int:
    """Bytes of every live array on ``device``: the store and whatever
    else is resident once nothing is in flight."""
    import jax

    gc.collect()
    sizes = []
    for a in jax.live_arrays():
        if a.is_deleted():
            continue
        for shard in a.addressable_shards:
            if shard.device == device:
                sizes.append((shard.data.nbytes, f"{a.dtype}{list(a.shape)}"))
    sizes.sort(reverse=True)
    say(f"live on the device: {len(sizes)} arrays, {sum(b for b, _ in sizes)} "
        "bytes; largest: " + ", ".join(f"{n} {b}" for b, n in sizes[:8]))
    return sum(b for b, _ in sizes)


def build(cell, seed: int):
    """The corpus and the store of ``cell``'s config at ``seed``."""
    import jax

    from repro.core import k2triples

    t0 = time.perf_counter()
    corpus = corpus_mod.from_config(cell.config, seed)
    t1 = time.perf_counter()
    store = k2triples.from_id_triples(
        corpus.ids, n_so=corpus.n_so, n_subjects=corpus.n_subjects,
        n_objects=corpus.n_objects, n_preds=corpus.n_preds)
    jax.block_until_ready(store.forest)
    t2 = time.perf_counter()
    say(f"corpus: {corpus.n_triples} triples, {corpus.n_preds} preds, "
        f"generated in {t1 - t0:.3f} s; store built in {t2 - t1:.3f} s "
        f"({store.stats.total_bits / store.n_triples:.2f} tree bits/triple)")
    return corpus, store


def make_broker(cell, store):
    from repro.core import engine as eng
    from repro.core.query import ExecConfig
    from repro.launch.broker import CoalescePolicy, ServeBroker, TenantPolicy

    sv = cell.config["serving"]
    return ServeBroker(
        eng.Engine(store),
        ExecConfig(backend="pallas", cap=sv["cap"]),
        unbounded=cell.unbounded,
        coalesce=CoalescePolicy(max_batch=sv["max_batch"],
                                max_delay_s=sv["deadline_ms"] * 1e-3,
                                max_inflight=sv["max_inflight"]),
        tenant_policy=TenantPolicy(queue_depth=sv["queue_depth"],
                                   max_cap_doublings=sv["max_cap_doublings"]),
    )


def workload(cell, corpus, seed: int, seconds: float):
    """The warm-up burst and the window's load, all drawn from ``seed``:
    ``(warm, ("open", reqs, due))`` or ``(warm, ("closed", next_request, k))``."""
    warm_rng, win_rng, tenant_rng = traffic.rngs(seed)
    warm = traffic.draw(corpus.ids, cell.mix, cell.loop["warmup_requests"],
                        warm_rng)
    if cell.loop["loop"] == "open":
        due = traffic.poisson_arrivals(cell.loop["rate_per_s"], seconds, win_rng)
        return warm, ("open", traffic.draw(corpus.ids, cell.mix, len(due),
                                           win_rng), due)
    tenant_rngs = [np.random.default_rng(s)
                   for s in tenant_rng.bit_generator.seed_seq.spawn(
                       cell.mix["tenants"])]
    chunks: dict = {}

    def next_request(t: int):
        if not chunks.get(t):
            chunks[t] = list(traffic.draw(corpus.ids, cell.mix, 1024,
                                          tenant_rngs[t], tenant=t))[::-1]
        return chunks[t].pop()

    return warm, ("closed", next_request, cell.loop["outstanding_per_tenant"])


async def serve(cell, broker, corpus, seed: int, seconds: float, *,
                trace: bool, t_start: float, watch: Watch,
                trace_dir: str | None):
    """Warm up, then drive the window; returns what the run logged."""
    from repro import obs
    from repro.core.query import ObsConfig

    import jax

    names = traffic.tenant_names(cell.mix)
    warm, (kind, a, b) = workload(cell, corpus, seed, seconds)
    out: dict = {}
    async with broker:
        t = time.perf_counter()
        wlog = await loops.burst(broker, names, warm)
        out["warm_failed"] = bad = int(np.sum(wlog.failed))
        say(f"warm-up: {len(warm)} requests in {time.perf_counter() - t:.3f} s, "
            f"{bad} failed, {broker.stats()['batches']} batches")
        del wlog
        broker.reset_stats()
        out["live"] = live_device_bytes(jax.devices()[0])
        tracer = None
        if trace:
            tracer, _ = obs.enable(ObsConfig(trace=True, metrics=False,
                                             trace_capacity=1 << 21))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with tr.sync_mark():
                pass

        def on_start():
            out["setup_s"] = time.perf_counter() - t_start
            watch.active = True

        def on_close():
            watch.active = False
            if trace:
                with tr.sync_mark():
                    pass
                jax.profiler.stop_trace()
                out["spans"] = tracer.events()
                obs.disable()

        if kind == "open":
            log = await loops.open_loop(broker, names, a, b, seconds,
                                        grace=GRACE_S, on_start=on_start,
                                        on_close=on_close)
        else:
            log = await loops.closed_loop(broker, names, a, b,
                                          seconds, grace=GRACE_S,
                                          on_start=on_start, on_close=on_close)
        out["stats"] = broker.stats()
    return log, out


def check(corpus, log, warm_failed: int = 0) -> dict:
    """Every answer against the reference: the numbers ``correct`` needs.
    ``failed`` counts the requests the broker never admitted within the
    grace, failed or cancelled, in the window and in the warm-up."""
    ref = corpus_mod.Reference(corpus.ids)
    wrong = checked = 0
    first_wrong = None
    for i, ans in enumerate(log.answers):
        if ans is None:
            continue
        r = log.reqs[i]
        want = ref.answer(int(r[traffic.OP]), int(r[traffic.S]),
                          int(r[traffic.P]), int(r[traffic.O]))
        checked += 1
        if not corpus_mod.same(ans, want):
            wrong += 1
            if first_wrong is None:
                first_wrong = (i, [int(x) for x in r], ans, want)
    a = log.arrays()
    missing = int(np.sum(~np.isnan(a["sent"]) & ~a["failed"] & np.isnan(a["done"])))
    fifo = sum(int(np.sum(np.diff(order) < 0)) for order in log.order.values())
    if first_wrong is not None:
        i, r, got, want = first_wrong
        say(f"first wrong answer: request {i} (tenant, op, s, p, o) = {r}: "
            f"got {got!r}, reference {want!r}")
    return {"wrong": wrong, "missing": missing,
            "failed": int(np.sum(a["failed"])) + warm_failed,
            "fifo_breaks": fifo, "checked": checked}


LIMITS = {"wrong": ("at most", 0), "missing": ("at most", 0),
          "failed": ("at most", 0), "fifo_breaks": ("at most", 0),
          "checked": ("at least", 1)}


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= v if how == "at most" else numbers[k] >= v
               for k, (how, v) in LIMITS.items())


def measure(cell, seed: int, seconds: float, trace: bool, t_start: float,
            built=None) -> dict:
    """One whole run; returns the result object (the contract's last line).
    ``built`` is ``build(cell, seed)``'s corpus and store, made by the caller
    to serve several cells of one config (the control's readings)."""
    import jax

    watch = Watch()
    corpus, store = build(cell, seed) if built is None else built
    broker = make_broker(cell, store)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        log, out = asyncio.run(serve(cell, broker, corpus, seed, seconds,
                                     trace=trace, t_start=t_start,
                                     watch=watch, trace_dir=trace_dir))
        dev = jax.devices()[0]
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        n_triples = store.n_triples
        del broker, store
        gc.collect()
        device = tr.load(trace_dir) if trace else None
    finally:
        watch.close()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    a = log.arrays()
    # a request that failed or never came missed every limit: it counts as
    # late by the whole window and the grace after it
    lat = np.where(a["failed"] | np.isnan(a["done"]), seconds + GRACE_S,
                   a["done"] - a["due"])
    answered = ~a["failed"] & ~np.isnan(a["done"])
    sent = a["sent"][~np.isnan(a["sent"])]
    late = sent - a["due"][~np.isnan(a["sent"])]
    stats = out["stats"]
    say(f"window: {len(log.reqs)} requests, {int(answered.sum())} answered, "
        f"{int(a['failed'].sum())} failed ({len(log.errors)} errors"
        + (f", first {log.errors[0]}" if log.errors else "") + "), "
        f"{stats['batches']} batches, coalesce x{stats['coalesce_factor']:.1f}, "
        f"{stats['cap_growth_events']} cap growths, {stats['shed']} shed; "
        f"{log.held} requests held back and resent, {log.refusals} refusals")
    say("latency: " + ", ".join(f"p{q} {np.percentile(lat, q) * 1e3:.3f} ms"
                                for q in (50, 95, 99)))
    if cell.loop["loop"] == "open" and len(late):
        say(f"generator lateness: p50 {np.percentile(late, 50) * 1e3:.3f} ms, "
            f"p99 {np.percentile(late, 99) * 1e3:.3f} ms, "
            f"max {late.max() * 1e3:.3f} ms")
    say(watch.summary())

    run = Run(cell=cell, seed=seed, setup_s=out["setup_s"], window_s=seconds,
              loop=cell.loop["loop"], latency_s=lat,
              answered_in_window=int(np.sum(answered & (a["done"] <= seconds))),
              n_triples=n_triples, live_device_bytes=out["live"],
              spans=out.get("spans"), device=device)
    t = time.perf_counter()
    numbers = check(corpus, log, out["warm_failed"])
    say(f"reference check: {numbers['checked']} answers in "
        f"{time.perf_counter() - t:.3f} s")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    result = {
        "correct": verdict(numbers),
        "attempted": len(log.reqs),
        "failed": int(np.sum(~answered)),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": mem},
    }
    if trace:
        result["device"]["busy_s"] = tr.busy_ns(device) * 1e-9
        result["device"]["window_s"] = device["window_ns"] * 1e-9
        result["breakdown"] = {"device_ops": tr.top_ops(device),
                               "idle_gaps": tr.idle_gaps(device, run.spans)}
    result["checks"] = {k: {"value": numbers[k], "limit": f"{how} {v}"}
                        for k, (how, v) in LIMITS.items()}
    for k, (how, v) in LIMITS.items():
        say(f"check {k}: {numbers[k]} (limit: {how} {v})")
    return result


def require_chips(n: int) -> None:
    """Exit non-zero, printing no result, unless JAX sees ``n`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"no TPU: JAX sees {devs[0].platform!r} devices")
        raise SystemExit(2)
    if len(devs) < n:
        say(f"the cell needs {n} TPU chips; JAX sees {len(devs)}")
        raise SystemExit(2)


def use_compile_cache() -> str:
    """The program's compile cache at its fixed in-checkout path, holding
    every program however quickly it compiled."""
    import jax

    from repro.launch.cache import use_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where
