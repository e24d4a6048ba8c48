"""Device busy time (union of the device's op intervals) per execution of
the serve program, from the profiler trace."""

from chipbench import tracereduce as tr


def read(run):
    n = tr.steps(run.device) if run.device else 0
    return tr.busy_ns(run.device) * 1e-6 / n if n else None
