"""The observability layer: tracer, metrics registry, cost profiles,
trace validation, and the two contracts that make it safe to ship:

  * **Disabled is free** — with observability off, plan calls and broker
    dispatch make ZERO tracer/obs-registry calls beyond the ``is None``
    branch at each site (spy-based tripwire, mirroring
    ``test_no_env_read_inside_plan_call``).  The broker's own always-on
    bookkeeping registry (plain ``Counter.inc`` behind ``stats()``) is
    the documented exemption: it replaced the old ad-hoc
    ``collections.Counter`` and is not part of the obs layer.
  * **Enabled is consistent** — a traced broker run still returns exact
    answers, its Chrome trace covers every query's lifetime (a ``query``
    span and its ``queue`` phase, linked by ``bid`` to the batch's
    stages), each batch's fetch stages meet end to end, and the metrics
    snapshot agrees with ``stats()``.
  * **Recording is lean** — a record allocates nothing that outlives it,
    so a traced window leaves the garbage collector nothing new to walk.
"""

import asyncio
import gc
import glob
import json
import os

import numpy as np
import pytest

import repro.obs as obs
from repro.core import engine as eng, k2triples
from repro.core.query import ExecConfig, ObsConfig, ServeQ
from repro.data import rdf
from repro.launch.broker import CoalescePolicy, ServeBroker, TenantPolicy
from repro.obs.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, log_buckets,
)
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.obs.validate import validate_chrome_trace


@pytest.fixture(autouse=True)
def _obs_off_after():
    """Observability is process-global state: never leak it across tests."""
    yield
    obs.disable()


@pytest.fixture(scope="module")
def store_and_truth():
    ds = rdf.generate(
        2500, n_subjects=50, n_preds=12, n_objects=70,
        preds_per_subject=3, seed=17,
    )
    store = k2triples.from_id_triples(
        ds.ids, n_so=ds.n_so, n_subjects=ds.n_subjects,
        n_objects=ds.n_objects, n_preds=ds.n_preds,
    )
    return store, set(map(tuple, ds.ids.tolist())), ds


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_log_buckets_shape():
    b = log_buckets(1e-3, 1e3, per_decade=1)
    assert b == (1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0, 1000.0)
    b3 = log_buckets(1.0, 10.0, per_decade=3)
    assert b3[0] == 1.0 and b3[-1] == 10.0 and len(b3) == 4
    assert list(b3) == sorted(b3)
    with pytest.raises(ValueError):
        log_buckets(10.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 10.0, per_decade=0)


def test_counter_gauge_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("x.count") is c  # create-or-return
    g = reg.gauge("x.level")
    g.set(2.5)
    assert g.value == 2.5
    reg.reset()
    assert c.value == 0 and g.value == 0.0  # objects stay valid
    with pytest.raises(TypeError):
        reg.gauge("x.count")  # typed: a name never changes kind


def test_histogram_buckets_and_percentile():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(560.5)
    snap = h._snapshot()
    assert snap["buckets"] == {"1.0": 1, "10.0": 2, "100.0": 1, "+Inf": 1}
    assert snap["min"] == 0.5 and snap["max"] == 500.0
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 10.0  # the median lands in the (1, 10] bucket
    assert h.percentile(100) == 500.0
    assert Histogram("e", (1.0,), reg._lock).percentile(50) is None
    reg.reset()
    assert h.count == 0 and h._snapshot()["buckets"] == {}


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("broker.batches").inc(3)
    reg.gauge("queue.depth").set(7)
    h = reg.histogram("lat.ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(20.0)
    text = reg.to_prometheus()
    assert "# TYPE broker_batches counter\nbroker_batches 3" in text
    assert "queue_depth 7" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text  # cumulative
    assert "lat_ms_count 2" in text


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_span_and_chrome_export():
    t = Tracer(capacity=64)
    with t.span("outer", cat="test", k=1):
        with t.span("inner"):
            pass
    t.instant("mark", note="hi")
    ev = t.events()
    assert [e["name"] for e in ev] == ["inner", "outer", "mark"]
    assert ev[1]["t0"] <= ev[0]["t0"] and ev[1]["t1"] >= ev[0]["t1"]

    ch = t.to_chrome(metadata={"run": "unit"})
    assert ch["otherData"]["run"] == "unit"
    assert validate_chrome_trace(ch) == []
    names = {e["name"] for e in ch["traceEvents"]}
    assert {"outer", "inner", "mark", "thread_name"} <= names


def test_tracer_error_annotation():
    t = Tracer(capacity=8)
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (ev,) = t.events()
    assert ev["args"]["error"] == "RuntimeError"


def test_tracer_retroactive_and_async():
    t = Tracer(capacity=64)
    n0 = t.now()
    t.add("batch", n0, n0 + 1000, tid="batch-slot-0", cat="broker", bid=0)
    t.add_async("query", 7, n0, n0 + 500, tenant="a")
    t.add_async("queue", 7, n0, n0 + 100)
    ch = t.to_chrome()
    assert validate_chrome_trace(ch) == []
    b_events = [e for e in ch["traceEvents"] if e.get("ph") == "b"]
    e_events = [e for e in ch["traceEvents"] if e.get("ph") == "e"]
    assert len(b_events) == len(e_events) == 2
    assert all(e["id"] == "7" for e in b_events)
    # string track ids surface as thread_name metadata
    meta = [e for e in ch["traceEvents"] if e.get("ph") == "M"]
    assert any(e["args"]["name"] == "batch-slot-0" for e in meta)


def test_tracer_ring_drops_oldest():
    t = Tracer(capacity=4)
    for i in range(10):
        t.add(f"s{i}", i, i + 1)
    assert t.dropped == 6
    assert [e["name"] for e in t.events()] == ["s6", "s7", "s8", "s9"]
    assert t.to_chrome()["droppedSpans"] == 6
    t.clear()
    assert t.dropped == 0 and t.events() == []


def test_noop_span_is_shared_and_inert():
    assert obs.span("anything", k=1) is NOOP_SPAN
    with NOOP_SPAN as s:
        assert s is NOOP_SPAN


# ---------------------------------------------------------------------------
# trace validation
# ---------------------------------------------------------------------------


def test_validate_rejects_malformed():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": []}) != []
    bad_dur = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": -1, "pid": 1, "tid": 1},
    ]}
    assert any("dur" in p for p in validate_chrome_trace(bad_dur))
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 1},
    ]}
    assert any("nest" in p for p in validate_chrome_trace(overlap))
    unbalanced = {"traceEvents": [
        {"name": "q", "ph": "b", "ts": 0, "cat": "query", "id": "1",
         "pid": 1, "tid": 0},
    ]}
    assert any("unmatched" in p for p in validate_chrome_trace(unbalanced))
    ok = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 2, "dur": 3, "pid": 1, "tid": 1},
    ]}
    assert validate_chrome_trace(ok) == []
    # --require-queries needs at least one query-cat async span
    assert any("query" in p
               for p in validate_chrome_trace(ok, require_queries=True))


# ---------------------------------------------------------------------------
# cost profiles
# ---------------------------------------------------------------------------


def test_cost_profile_of_compiled_plan(store_and_truth):
    store, _, _ = store_and_truth
    E = eng.Engine(store)
    plan = E.compile(ServeQ(unbounded=False), ExecConfig(backend="jnp", cap=64))
    prof = plan.cost_profile()
    assert prof["geometry"]["cap"] == 64
    assert prof["geometry"]["lanes"] == 8  # pow2-padded minimum
    assert prof["geometry"]["u_width"] == 0  # bounded plan: no u_* block
    assert prof.get("flops", 0) > 0
    assert prof.get("bytes_accessed", 0) > 0
    assert "memory" in prof and prof["memory"]["output_bytes"] > 0
    # cached per geometry: identical dict again, not a recompile
    assert plan.cost_profile() == prof
    # a pattern plan has no raw compiled surface to profile
    from repro.core.query import TriplePatternQ

    pat = E.compile(TriplePatternQ(1, 1, "?o"), ExecConfig(backend="jnp"))
    with pytest.raises(NotImplementedError):
        pat.cost_profile()


# ---------------------------------------------------------------------------
# the disabled-path tripwire
# ---------------------------------------------------------------------------


def _arm_tripwire(monkeypatch):
    """Make every obs-layer recording surface raise.  The broker's
    bookkeeping ``Counter.inc`` (its always-on ``stats()`` registry) is
    deliberately NOT armed — it replaced the ad-hoc stats dict and runs
    regardless of observability, like the stats dict always did."""

    def boom(name):
        def _(*a, **k):
            raise AssertionError(
                f"obs call {name} on the DISABLED path — instrumentation "
                "must be behind an `is None` guard"
            )
        return _

    for m in ("__init__", "begin", "end", "span", "add", "add_async",
              "instant", "_record"):
        monkeypatch.setattr(Tracer, m, boom(f"Tracer.{m}"))
    monkeypatch.setattr(Histogram, "observe", boom("Histogram.observe"))
    monkeypatch.setattr(Gauge, "set", boom("Gauge.set"))


def test_disabled_path_makes_no_obs_calls(monkeypatch, store_and_truth):
    """With observability off, compiled plan calls are obs-free."""
    store, T, ds = store_and_truth
    E = eng.Engine(store)
    plan = E.compile(ServeQ(unbounded=False), ExecConfig(backend="jnp", cap=256))
    qb = eng.ServeBatch(
        op=np.full(8, eng.OP_CHECK, np.int32), s=ds.ids[:8, 0].astype(np.int32),
        p=ds.ids[:8, 1].astype(np.int32), o=ds.ids[:8, 2].astype(np.int32),
    )
    plan(qb)  # prime compilation before arming the tripwire

    assert not obs.enabled()
    _arm_tripwire(monkeypatch)
    r = plan(qb)
    host = eng.host_result(plan.submit(qb), unbounded=False)
    assert eng.decode_lane(eng.OP_CHECK, host, 0) is True
    assert bool(np.asarray(r.hit)[0])
    E.compile(ServeQ(unbounded=False), ExecConfig(backend="jnp", cap=256))


def test_disabled_path_broker_dispatch(monkeypatch, store_and_truth):
    """With observability off, a full broker roundtrip — enqueue,
    coalesce, dispatch, deliver — is obs-free too (its bookkeeping
    counters excepted, see ``_arm_tripwire``)."""
    store, T, ds = store_and_truth
    E = eng.Engine(store)

    async def main():
        async with ServeBroker(
            E, ExecConfig(backend="jnp", cap=256), unbounded=False,
            coalesce=CoalescePolicy(max_batch=8, max_delay_s=0.002),
        ) as b:
            _arm_tripwire(monkeypatch)
            futs = [b.submit_nowait("t", eng.OP_CHECK, *map(int, ds.ids[i]))
                    for i in range(6)]
            return await asyncio.gather(*futs)

    assert not obs.enabled()
    got = asyncio.run(main())
    assert all(got)


# ---------------------------------------------------------------------------
# enabled end-to-end: broker run under tracing + metrics
# ---------------------------------------------------------------------------


def _direct_truth(T, queries):
    out = []
    for op, s, p, o in queries:
        if op == eng.OP_CHECK:
            out.append((s, p, o) in T)
        elif op == eng.OP_ROW:
            out.append(sorted(oo for (ss, pp, oo) in T if ss == s and pp == p))
        else:
            out.append(sorted(ss for (ss, pp, oo) in T if pp == p and oo == o))
    return out


def test_enabled_broker_trace_covers_every_query(store_and_truth):
    store, T, ds = store_and_truth
    E = eng.Engine(store)
    tracer, metrics = obs.enable(ObsConfig())
    queries = []
    rng = np.random.default_rng(3)
    for i in rng.integers(0, len(ds.ids), 24):
        s, p, o = map(int, ds.ids[i])
        queries.append((int(rng.integers(0, 3)), s, p, o))

    async def main():
        async with ServeBroker(
            E, ExecConfig(backend="jnp", cap=256), unbounded=False,
            coalesce=CoalescePolicy(max_batch=8, max_delay_s=0.001),
        ) as b:
            futs = [b.submit_nowait(f"t{i % 3}", *q)
                    for i, q in enumerate(queries)]
            got = await asyncio.gather(*futs)
            return b, got, b.stats()

    b, got, st = asyncio.run(main())

    # answers stay exact under tracing
    for g, want in zip(got, _direct_truth(T, queries)):
        assert (g if isinstance(g, bool) else sorted(g)) == want

    # the trace is schema-valid and covers every query's lifetime
    ch = tracer.to_chrome()
    assert validate_chrome_trace(ch, require_queries=True) == []
    per_query: dict = {}
    for e in ch["traceEvents"]:
        if e.get("ph") == "b":
            per_query.setdefault(e["id"], set()).add(e["name"])
    assert len(per_query) == len(queries)
    for qid, names in per_query.items():
        assert names == {"query", "queue"}, (qid, names)
    batch_spans = [e for e in ch["traceEvents"]
                   if e.get("ph") == "X" and e["name"] == "broker.batch"]
    assert len(batch_spans) == st["batches"]
    assert all(0 < e["args"]["occupancy"] <= 1 for e in batch_spans)
    # two records per query, a fixed number per batch, none per lane
    spans = tracer.events()
    per_batch = _stages_by_bid(spans)
    assert len(per_batch) == st["batches"]
    assert all(set(v) == set(BATCH_STAGES) for v in per_batch.values())
    assert not any(e["name"] == "plan.decode_lane" for e in spans)
    assert sum(e["kind"] == "async" for e in spans) == 2 * len(queries)
    _assert_fetch_stages_meet(per_batch, FETCH_STAGES)
    # broker.copy counts the bytes of the arrays the fetch brought over
    fetched = eng.host_result(
        b.base_plan.submit(b._encode([], b._pad_to)), unbounded=False)
    want = [a.nbytes for a in fetched if a.nbytes]
    assert len(want) == 5  # hit, ids, valid, count, overflow
    for stages in per_batch.values():
        copy = stages["broker.copy"]["args"]
        assert (copy["bytes"], copy["arrays"]) == (sum(want), len(want))

    # the obs metrics snapshot agrees with the broker's reported totals
    snap = metrics.snapshot()
    assert snap["broker.query_latency_ms"]["count"] == st["queries"]
    occ = snap["broker.batch_occupancy"]
    assert occ["count"] == st["batches"]
    book = b.metrics.snapshot()
    assert book["broker.batches"]["value"] == st["batches"]
    assert book["broker.lanes"]["value"] == st["lanes"]

    # per-plan compile-time cost profiles, base geometry included
    profiles = b.cost_profiles()
    assert profiles["base"]["geometry"]["cap"] == 256
    assert profiles["base"].get("flops", 0) > 0


# the batch stages of a static store, and the fetch's stages in order
BATCH_STAGES = (
    "broker.batch", "broker.coalesce", "broker.dispatch", "broker.inflight",
    "broker.handoff", "broker.device_wait", "broker.copy", "broker.resume",
    "broker.decode_deliver",
)
FETCH_STAGES = ("broker.handoff", "broker.device_wait", "broker.copy",
                "broker.resume")


def _stages_by_bid(spans) -> dict:
    out: dict = {}
    for e in spans:
        if e["kind"] == "X" and e["name"].startswith("broker."):
            stages = out.setdefault(e["args"]["bid"], {})
            assert e["name"] not in stages, e
            stages[e["name"]] = e
    return out


def _assert_fetch_stages_meet(per_batch, order):
    """Each batch's fetch stages meet end to end, on the batch's track,
    inside its ``broker.batch`` span."""
    for bid, stages in per_batch.items():
        seq = [stages[n] for n in order]
        for a, b in zip(seq, seq[1:]):
            assert a["t1"] == b["t0"], (bid, a["name"], b["name"])
        assert all(a["t0"] <= a["t1"] for a in seq)
        assert len({e["tid"] for e in stages.values()}) == 1
        batch = stages["broker.batch"]
        assert batch["t0"] <= seq[0]["t0"] and seq[-1]["t1"] <= batch["t1"]


def test_dynamic_store_fetch_has_a_merge_stage(store_and_truth):
    """With a delta to merge, the merge is a stage of its own between the
    copy and the resume; the stages still meet end to end."""
    from repro.core import delta

    store, T, ds = store_and_truth
    E = eng.Engine(store=delta.DynamicStore(store))
    tracer, _ = obs.enable(ObsConfig(metrics=False))
    s, p, o = map(int, ds.ids[0])

    async def main():
        async with ServeBroker(
            E, ExecConfig(backend="jnp", cap=256), unbounded=False,
            coalesce=CoalescePolicy(max_batch=8, max_delay_s=0.001),
        ) as b:
            b.submit_delete_nowait("t", s, p, o)
            return await asyncio.gather(
                b.submit_nowait("t", eng.OP_CHECK, s, p, o),
                b.submit_nowait("t", eng.OP_ROW, s, p, 0),
            ), b.stats()

    (hit, row), st = asyncio.run(main())
    assert hit is False and o not in set(row.tolist())
    per_batch = _stages_by_bid(tracer.events())
    assert len(per_batch) == st["batches"] >= 1
    _assert_fetch_stages_meet(
        per_batch, ("broker.handoff", "broker.device_wait", "broker.copy",
                    "broker.merge", "broker.resume"))


def test_device_annotations_reach_the_profile(store_and_truth, tmp_path):
    """With ``device_annotations=True`` the fetch thread's live stages are
    ``TraceAnnotation``s: a profile taken around a broker run holds
    ``broker.device_wait`` and ``broker.copy`` events (and the event
    loop's ``broker.dispatch``), which ``ProfileData`` reads."""
    import jax
    from jax.profiler import ProfileData

    store, T, ds = store_and_truth
    E = eng.Engine(store)
    obs.enable(ObsConfig(metrics=False, device_annotations=True))

    async def main():
        async with ServeBroker(
            E, ExecConfig(backend="jnp", cap=256), unbounded=False,
            coalesce=CoalescePolicy(max_batch=8, max_delay_s=0.001),
        ) as b:
            return await asyncio.gather(*(
                b.submit_nowait("t", eng.OP_CHECK, *map(int, ds.ids[i]))
                for i in range(12)))

    jax.profiler.start_trace(str(tmp_path))
    try:
        got = asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    assert all(got)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"broker.dispatch", "broker.device_wait", "broker.copy"} <= names


def test_tracer_records_allocate_nothing_that_lives_on():
    """Recording 100,000 spans with the collector off adds fewer than
    1,000 objects to ``gc.get_objects()``; ``events()`` still returns the
    record dicts the readers take."""
    t = Tracer(capacity=1 << 17)
    was = gc.isenabled()
    gc.collect()
    gc.disable()
    try:
        n0 = len(gc.get_objects())
        base = t.now()
        for i in range(25_000):
            t.add("broker.copy", base + i, base + i + 7, tid="batch-slot-1",
                  cat="broker", bid=i, bytes=1_310_720 + i, arrays=5)
            t.add_async("query", 10_000 + i, base, base + i,
                        tenant=f"t{i % 8}", op=1, lane=i % 256, bid=i)
            t.add_async("queue", 10_000 + i, base, base + i)
            live = t.begin("broker.device_wait", tid="batch-slot-1",
                           t0=base + i, bid=i)
            t.end(live, t1=base + i + 3)
        grown = len(gc.get_objects()) - n0
    finally:
        if was:
            gc.enable()
    assert grown < 1_000, grown
    assert t.dropped == 0
    ev = t.events()
    assert len(ev) == 100_000
    assert ev[-4:] == [
        {"kind": "X", "name": "broker.copy", "cat": "broker",
         "t0": base + 24_999, "t1": base + 25_006, "tid": "batch-slot-1",
         "args": {"bid": 24_999, "bytes": 1_310_720 + 24_999, "arrays": 5}},
        {"kind": "async", "name": "query", "cat": "query", "id": 34_999,
         "t0": base, "t1": base + 24_999, "tid": 0,
         "args": {"tenant": "t7", "op": 1, "lane": 24_999 % 256,
                  "bid": 24_999}},
        {"kind": "async", "name": "queue", "cat": "query", "id": 34_999,
         "t0": base, "t1": base + 24_999, "tid": 0, "args": {}},
        {"kind": "X", "name": "broker.device_wait", "cat": "",
         "t0": base + 24_999, "t1": base + 25_002, "tid": "batch-slot-1",
         "args": {"bid": 24_999}},
    ]
    assert all(type(e["t0"]) is int and type(e["t1"]) is int for e in ev[:8])


def test_tracer_keeps_wide_args_and_wraps():
    """A record with more arguments than the columns hold keeps them all;
    a wrapped ring exports the newest records, oldest first."""
    t = Tracer(capacity=3)
    wide = {f"k{i}": i for i in range(7)}
    t.add("wide", 1, 2, tid=5, **wide)
    t.instant("mark", tid=5, note="x")
    t.add("narrow", 3, 4, tid=5, a=1)
    t.add("last", 5, 6, tid=5)
    assert t.dropped == 1
    assert [e["name"] for e in t.events()] == ["mark", "narrow", "last"]
    t = Tracer(capacity=4)
    t.add("wide", 1, 2, tid=5, **wide)
    assert t.events() == [{"kind": "X", "name": "wide", "cat": "", "t0": 1,
                           "t1": 2, "tid": 5, "args": wide}]


def test_engine_compile_metrics_absorb_plan_cache_stats(store_and_truth):
    store, _, ds = store_and_truth
    E = eng.Engine(store)
    _, metrics = obs.enable(ObsConfig(trace=False, metrics=True))
    cfg = ExecConfig(backend="jnp", cap=128)
    q = ServeQ(unbounded=False)
    E.compile(q, cfg)
    E.compile(q, cfg)
    with pytest.raises(Exception):
        E.compile(q, cfg.replace(cap=64), admit=lambda k: False)
    snap = metrics.snapshot()
    assert snap["engine.plan_cache.misses"]["value"] == 1
    assert snap["engine.plan_cache.hits"]["value"] == 1
    assert snap["engine.plan_cache.denied"]["value"] == 1
    assert E.plan_cache_stats == {
        "hits": 1, "misses": 1, "denied": 1, "size": 1
    }
