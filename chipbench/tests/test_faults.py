"""``correct`` comes out false under the control and under each fault a
served store can have, with the rest of a run as it is."""

from __future__ import annotations

import time

import pytest

from chipbench import faults, harness
from chipbench.tests.tiny import tiny


def _run(cell, seed=11):
    return harness.measure(cell, seed, 1.0, False, time.perf_counter())


def test_control_is_not_correct(monkeypatch):
    """The store served at a small cap with the overflow bit ignored.  At
    this size answers are short, so the control's cap is cut to 2."""
    monkeypatch.setattr(faults, "CONTROL_CAP", 2)
    cell = faults.control_cell(tiny("geonames.lookup.open"))
    assert cell.config["serving"]["cap"] == 2
    with faults.control():
        result = _run(cell)
    assert result["correct"] is False
    assert result["checks"]["wrong"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", ["geonames.lookup.open", "dbtune-9m.describe.closed"])
def test_fault_is_not_correct(fault, name):
    with faults.FAULTS[fault]():
        result = _run(tiny(name))
    assert result["correct"] is False
    assert result["checks"][faults.TRIPS[fault]]["value"] > 0


def test_faults_are_undone():
    from repro.core import engine as eng
    from repro.launch.broker import ServeBroker

    before = (eng.host_result, ServeBroker._encode, ServeBroker.submit_nowait)
    for f in list(faults.FAULTS.values()) + [faults.control]:
        with f():
            pass
    assert (eng.host_result, ServeBroker._encode, ServeBroker.submit_nowait) == before
    assert set(faults.TRIPS) == set(faults.FAULTS)
