"""Share of the traced window in which no operation ran on the device."""

from chipbench import tracereduce as tr


def read(run):
    if not run.device or not run.device["window_ns"] or not run.device["ops"]:
        return None
    return 100.0 * (1.0 - tr.busy_ns(run.device) / run.device["window_ns"])
