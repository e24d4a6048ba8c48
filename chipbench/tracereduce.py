"""From a profiler trace to the device numbers of a run.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a small
dict of plain lists (the form the test fixture keeps):

- ``ops``: ``[name, start_ns, dur_ns, category]`` of every operation on the
  first TPU's "XLA Ops" line.  The trace names an op by its whole HLO text;
  the name kept is the instruction's (``k2_scan.1``, ``fusion.17``), and
  the category is ``tpu_custom_call`` for a Pallas kernel, else empty;
- ``modules``: ``[name, start_ns, dur_ns]`` of its "XLA Modules" line, one
  event per program execution;
- ``sync``: ``[trace_ns, perf_counter_ns]`` pairs, one per ``chipbench.sync``
  annotation on the host, to put host spans on the trace's clock;
- ``window_ns``: the traced window.

The reductions below take that dict.  Busy time is the union of the op
intervals; idle is the rest of the window.  The ops and the modules share
the host's timeline in the trace, and the sync marks put the host spans on
it too.
"""

from __future__ import annotations

import glob
import os

import numpy as np

SYNC = "chipbench.sync"
PALLAS = "tpu_custom_call"


def op_record(hlo_text: str, start_ns: int, dur_ns: int) -> list:
    """``[name, start_ns, dur_ns, category]`` of one "XLA Ops" event."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    category = PALLAS if f'custom_call_target="{PALLAS}"' in hlo_text else ""
    return [name, int(start_ns), int(dur_ns), category]


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"ops": [], "modules": [], "sync": []}
    lo, hi = np.inf, -np.inf
    device_seen = False
    for plane in pd.planes:
        is_tpu = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_tpu and not device_seen and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                for e in line.events:
                    out[key].append(
                        op_record(e.name, e.start_ns, e.duration_ns) if key == "ops"
                        else [e.name, int(e.start_ns), int(e.duration_ns)])
            for e in line.events:
                lo = min(lo, e.start_ns)
                hi = max(hi, e.start_ns + e.duration_ns)
                if e.name == SYNC:
                    stats = dict(e.stats)
                    if "perf_ns" in stats:
                        out["sync"].append([int(e.start_ns), int(stats["perf_ns"])])
        if is_tpu and (out["ops"] or out["modules"]):
            device_seen = True
    out["window_ns"] = int(hi - lo) if hi > lo else 0
    return out


def sync_mark():
    """A host annotation carrying this moment's ``perf_counter_ns``; the
    trace gives its own timestamp, and the pair aligns the two clocks."""
    import time

    import jax

    return jax.profiler.TraceAnnotation(SYNC, perf_ns=time.perf_counter_ns())


def intervals(events) -> np.ndarray:
    """``[[start, end], ...]`` merged: the union of the events' intervals."""
    if not events:
        return np.zeros((0, 2), np.int64)
    iv = np.array([[e[1], e[1] + e[2]] for e in events], np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [iv[0].copy()]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append(np.array([a, b]))
    return np.array(out, np.int64)


def busy_ns(trace: dict) -> int:
    iv = intervals(trace["ops"])
    return int((iv[:, 1] - iv[:, 0]).sum())


def pallas_ns(trace: dict) -> int:
    """Device time of the Pallas kernels."""
    return int(sum(op[2] for op in trace["ops"] if op[3] == PALLAS))


def steps(trace: dict, prefix: str = "jit_serve_step") -> int:
    """Executions of the serve program in the window."""
    return sum(1 for m in trace["modules"] if m[0].startswith(prefix))


def top_ops(trace: dict, n: int = 10) -> list:
    """``[[op name, seconds], ...]``: the ops that took most device time."""
    tot: dict = {}
    for op in trace["ops"]:
        tot[op[0]] = tot.get(op[0], 0) + op[2]
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def gaps(trace: dict) -> np.ndarray:
    """``[[start, end], ...]`` of the idle gaps between busy intervals."""
    iv = intervals(trace["ops"])
    if len(iv) < 2:
        return np.zeros((0, 2), np.int64)
    return np.stack([iv[:-1, 1], iv[1:, 0]], axis=1)


def clock_offset(trace: dict) -> int | None:
    """``perf_counter_ns - trace_ns``, or None with no sync mark."""
    if not trace["sync"]:
        return None
    return int(np.median([p - t for t, p in trace["sync"]]))


def idle_gaps(trace: dict, spans: list | None, n: int = 10) -> list:
    """``[[label, seconds], ...]``: the ``n`` longest idle gaps, each
    labelled with the broker stage that overlaps it most (``idle`` where no
    span covers it, ``idle (clocks not aligned)`` where the clocks cannot
    be aligned)."""
    g = gaps(trace)
    if not len(g):
        return []
    g = g[np.argsort(g[:, 0] - g[:, 1], kind="stable")[:n]]
    off = clock_offset(trace)
    stages = [e for e in (spans or []) if e.get("kind") == "X"
              and e["name"].startswith("broker.") and e["name"] != "broker.batch"]
    out = []
    for a, b in g:
        label = "idle"
        if off is None:
            label = "idle (clocks not aligned)"
        elif stages:
            a_p, b_p = a + off, b + off
            best, best_ov = None, 0
            for e in stages:
                ov = min(b_p, e["t1"]) - max(a_p, e["t0"])
                if ov > best_ov:
                    best, best_ov = e["name"], ov
            if best is not None:
                label = f"idle during {best}"
        out.append([label, (b - a) * 1e-9])
    return out


def span_ns(spans: list | None, name: str, kind: str = "X") -> np.ndarray:
    """Durations of the host spans called ``name`` (``kind`` "X" for the
    broker's batch stages, "async" for per-query phases)."""
    return np.array([e["t1"] - e["t0"] for e in spans or ()
                     if e.get("kind") == kind and e["name"] == name], np.int64)
