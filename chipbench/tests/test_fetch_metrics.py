"""The readers of the broker's fetch stages and of the u-scan kernel, on span
and op lists small enough to reckon by hand, and on tiny traced windows on
the CPU."""

from __future__ import annotations

import dataclasses
import time
import types

import pytest

from chipbench import harness, spec
from chipbench.tests.tiny import tiny


def _x(name, t0, t1, **args):
    return {"kind": "X", "name": name, "cat": "broker", "t0": t0, "t1": t1,
            "tid": "batch-slot-0", "args": args}


def _run(spans=None, device=None):
    return types.SimpleNamespace(spans=spans, device=device)


def _batch(bid, t, lanes, copy_bytes, handoff, resume):
    """One batch's spans: its fetch stages meet end to end."""
    a, b = t + handoff, t + handoff + 40
    return [
        _x("broker.batch", t - 10, b + resume + 20, bid=bid, lanes=lanes,
           padded=256, occupancy=lanes / 256),
        _x("broker.handoff", t, a, bid=bid),
        _x("broker.device_wait", a, a + 30, bid=bid),
        _x("broker.copy", a + 30, b, bid=bid, bytes=copy_bytes, arrays=5),
        _x("broker.resume", b, b + resume, bid=bid),
    ]


def test_copy_ms_is_the_mean_copy():
    spans = _batch(0, 0, 256, 1_000, 5, 7) + _batch(1, 1000, 256, 1_000, 5, 7)
    spans[8]["t1"] += 2_000_000  # batch 1's copy takes 2 ms longer
    read = spec.reader("host.copy_ms.open")
    assert read(_run(spans)) == pytest.approx((10 + 2_000_010) / 2 * 1e-6)
    assert spec.reader("host.copy_ms.closed")(_run(spans)) == read(_run(spans))
    assert read(_run([])) is None and read(_run(None)) is None


def test_loop_wait_p99_joins_handoff_and_resume_by_batch():
    read = spec.reader("host.loop_wait_p99_ms.open")
    spans = []
    for bid in range(200):
        spans += _batch(bid, bid * 10_000, 16, 100, 1_000 * bid, 500)
    # p99 over 200 batches of handoff + resume = 1000 * bid + 500 ns
    want = (1_000 * (0.99 * 199) + 500) * 1e-6
    assert read(_run(spans)) == pytest.approx(want)
    # a batch whose handoff fell outside the trace is left out
    assert read(_run(spans[:1] + spans[2:])) == pytest.approx(
        (1_000 * (1 + 0.99 * 198) + 500) * 1e-6)
    # fewer than 100 batches carry no p99; the parent's spans carry none
    assert read(_run(spans[: 5 * 99])) is None
    assert read(_run([_x("broker.fetch", 0, 9, bid=0)])) is None


def test_fetch_kb_per_query_over_batches_with_both_spans():
    read = spec.reader("host.fetch_kb_per_query.closed")
    spans = (_batch(0, 0, 256, 1_311_488, 1, 1)
             + _batch(1, 100, 128, 1_311_488, 1, 1))
    assert read(_run(spans)) == pytest.approx(2 * 1_311_488 / 384 / 1e3)
    # a copy whose batch span never came (the window closed) is left out
    assert read(_run(spans[1:])) == pytest.approx(1_311_488 / 128 / 1e3)
    assert read(_run([s for s in spans if s["name"] != "broker.copy"])) is None
    assert read(_run(None)) is None


def test_scan_u_ms_reads_the_named_kernel_per_step():
    read = spec.reader("kernels.scan_u_ms.closed")
    trace = {"ops": [["k2_scan_u.3", 0, 5_000_000, "tpu_custom_call"],
                     ["k2_scan_bound.2", 5_000_000, 100_000, "tpu_custom_call"],
                     ["pred_gather_dac.1", 6_000_000, 200_000, "tpu_custom_call"],
                     ["k2_scan_u.3", 10_000_000, 7_000_000, "tpu_custom_call"],
                     ["fusion.1", 17_000_000, 1_000, ""]],
             "modules": [["jit_serve_step(1)", 0, 7_000_000],
                         ["jit_serve_step(1)", 10_000_000, 8_000_000]],
             "sync": [], "window_ns": 20_000_000}
    assert read(_run(device=trace)) == pytest.approx(6.0)
    # the parent's kernels share the name k2_scan: nothing to read
    old = dict(trace, ops=[["k2_scan.3" if o[0].startswith("k2_scan_u") else o[0],
                            *o[1:]] for o in trace["ops"]])
    assert read(_run(device=old)) is None
    assert read(_run(device=None)) is None


@pytest.mark.parametrize("name", ["geonames.lookup.open", "geonames.lookup.closed"])
def test_tiny_traced_window_reads_the_fetch_stages(name):
    cell = tiny(name)
    if cell.loop["loop"] == "open":  # enough batches for a p99 over them
        cell = dataclasses.replace(cell, loop=dict(cell.loop, rate_per_s=150))
    result = harness.measure(cell, 2**31 + 101, 1.5, True, time.perf_counter())
    assert result["correct"] is True
    m = result["metrics"]
    loop = cell.loop["loop"]
    assert m[f"host.copy_ms.{loop}"]["value"] > 0
    if loop == "open":
        assert m["host.loop_wait_p99_ms.open"]["value"] > 0
    else:
        # ids [16, 1024] int32 and valid [16, 1024] bool, hit, count and
        # overflow: 16 * (4096 + 1024 + 1 + 4 + 1) bytes a full batch
        assert m["host.fetch_kb_per_query.closed"]["value"] == pytest.approx(5.126)
