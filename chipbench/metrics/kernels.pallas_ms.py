"""Device time of the Pallas kernels (``k2_scan``, ``pred_gather``, every
``tpu_custom_call`` summed) per execution of the serve program, from the
profiler trace."""

from chipbench import tracereduce as tr


def read(run):
    n = tr.steps(run.device) if run.device else 0
    ns = tr.pallas_ns(run.device) if n else 0
    return ns * 1e-6 / n if ns else None
