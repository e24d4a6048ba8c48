"""The reduction from a profiler trace, on a recorded excerpt of a chip run
and on a trace small enough to reckon by hand."""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from chipbench import tracereduce as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_closed_excerpt.json"


@pytest.fixture(scope="module")
def excerpt():
    return json.loads(FIXTURE.read_text())


def test_op_record_names_and_kernels():
    text = ('%k2_scan.1 = s32[256,16,128]{2,1,0} custom-call(s32[256]{0} %a), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.op_record(text, 5, 7) == ["k2_scan.1", 5, 7, "tpu_custom_call"]
    assert tr.op_record("%fusion.17 = s32[256]{0} fusion(%x)", 1, 2) == [
        "fusion.17", 1, 2, ""]


def test_hand_trace():
    t = {"ops": [["a", 0, 10, ""], ["k", 5, 10, "tpu_custom_call"],
                 ["b", 30, 5, ""], ["k", 50, 20, "tpu_custom_call"]],
         "modules": [["jit_serve_step(1)", 0, 35], ["jit_serve_step(1)", 50, 20],
                     ["jit_other(2)", 80, 1]],
         "sync": [[0, 1000], [100, 1100]], "window_ns": 100}
    assert tr.intervals(t["ops"]).tolist() == [[0, 15], [30, 35], [50, 70]]
    assert tr.busy_ns(t) == 40
    assert tr.pallas_ns(t) == 30
    assert tr.steps(t) == 2
    assert tr.top_ops(t, 2) == [["k", pytest.approx(30e-9)], ["a", pytest.approx(10e-9)]]
    assert tr.gaps(t).tolist() == [[15, 30], [35, 50]]
    assert tr.clock_offset(t) == 1000
    spans = [{"kind": "X", "name": "broker.fetch", "t0": 1010, "t1": 1029},
             {"kind": "X", "name": "broker.decode_deliver", "t0": 1029, "t1": 1060},
             {"kind": "X", "name": "broker.batch", "t0": 900, "t1": 1200}]
    assert tr.idle_gaps(t, spans) == [
        ["idle during broker.fetch", pytest.approx(15e-9)],
        ["idle during broker.decode_deliver", pytest.approx(15e-9)]]
    assert tr.idle_gaps(dict(t, sync=[]), spans)[0][0] == "idle (clocks not aligned)"
    assert tr.idle_gaps(t, [])[0][0] == "idle"


def test_excerpt_numbers(excerpt):
    """Six full 256-lane steps of ``geonames.lookup.closed``: the k2_scan
    kernel is nearly all of the busy time."""
    assert tr.steps(excerpt) == 6
    assert tr.busy_ns(excerpt) == 221269506
    assert tr.pallas_ns(excerpt) == 220117159
    assert excerpt["window_ns"] == 273772496
    top = tr.top_ops(excerpt, 3)
    assert top[0] == ["k2_scan.1", 0.220117159]
    assert [k for k, _ in top[1:]] == ["fusion.9", "fusion.1"]
    assert len(tr.gaps(excerpt)) == 405
    assert tr.clock_offset(excerpt) == 270130408521


def test_excerpt_busy_by_brute_force(excerpt):
    """The union, reckoned again on a 1 us grid."""
    lo = min(o[1] for o in excerpt["ops"])
    hi = max(o[1] + o[2] for o in excerpt["ops"])
    grid = np.zeros((hi - lo) // 1000 + 2, np.bool_)
    for _, s, d, _ in excerpt["ops"]:
        grid[(s - lo) // 1000: (s + d - lo) // 1000] = True
    assert abs(grid.sum() * 1000 - tr.busy_ns(excerpt)) < 0.01 * tr.busy_ns(excerpt)


def test_span_ns():
    spans = [{"kind": "async", "name": "queue", "t0": 0, "t1": 5},
             {"kind": "X", "name": "queue", "t0": 0, "t1": 7},
             {"kind": "async", "name": "queue", "t0": 2, "t1": 3}]
    assert tr.span_ns(spans, "queue", "async").tolist() == [5, 1]
    assert tr.span_ns(None, "queue").tolist() == []
