"""Whole runs of every cell at a tiny size on the CPU, with the Pallas
kernels interpreted: the real broker, the cell's loop, the reference check
and the metric readers.  ``harness.measure`` is called directly, past the
command's refusal of the CPU."""

from __future__ import annotations

import time

import pytest

from chipbench import harness, spec
from chipbench.tests.tiny import tiny


@pytest.mark.parametrize("name", spec.cell_names())
def test_tiny_window_agrees_with_the_reference(name):
    cell = tiny(name)
    result = harness.measure(cell, 2**31 + 17, 1.5, False, time.perf_counter())
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 10
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert checks["wrong"]["value"] == 0 and checks["checked"]["value"] == result["attempted"]
    assert checks["failed"]["value"] == 0
    got = set(result["metrics"])
    assert {"setup_s", "device_bits_per_triple"} <= got
    assert got <= {m.name for m in cell.end_to_end}
    assert result["device"]["platform"] == "cpu"


def test_tiny_traced_window_reads_the_spans():
    cell = tiny("geonames.lookup.closed")
    result = harness.measure(cell, 5, 1.5, True, time.perf_counter())
    assert result["correct"] is True
    m = result["metrics"]
    assert m["broker.occupancy_pct.closed"]["value"] == 100.0
    assert m["host.decode_ms.closed"]["value"] > 0
    # the CPU has no TPU plane: the device readers find nothing, and say so
    assert "kernels.pallas_ms.closed" not in m and "device.idle_pct.closed" not in m
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 0
