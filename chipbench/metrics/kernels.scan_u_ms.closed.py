"""Device time of the u-candidate scans, the ops named ``k2_scan_u.N`` (the
launch the serve step names ``k2_scan_u``), per execution of the serve
program, from the profiler trace."""

from chipbench import tracereduce as tr

PREFIX = "k2_scan_u"


def read(run):
    n = tr.steps(run.device) if run.device else 0
    ns = sum(op[2] for op in run.device["ops"]
             if op[0].startswith(PREFIX)) if n else 0
    return ns * 1e-6 / n if ns else None
