"""The benchmark's data, found by name.

``BENCHMARK.json`` at the root lists the cells (``workloads``) and metrics.
Each name leads to a file of its own under this directory:

- ``cells/<workload>.json``: the config, the mix and the loop of one cell
  (``{"loop": "open", "rate_per_s": r}`` or ``{"loop": "closed",
  "outstanding_per_tenant": k}``), plus ``warmup_requests``;
- ``configs/<config>.json``: one corpus and its serving settings;
- ``mixes/<mix>.json``: the op shares, tenants and anchor draw of a mix;
- ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` (a metric
  named ``<stem>.open`` or ``<stem>.closed`` may share ``metrics/<stem>.py``).

A later cell, corpus, mix or metric is added by adding such files and
entries; no code here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def load_benchmark(path: pathlib.Path = BENCHMARK) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path.relative_to(ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def traffic_name(cell: dict) -> str:
    """A cell's traffic is its mix under its loop: ``lookup.open``."""
    return f"{cell['mix']}.{cell['loop']}"


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str  # "end_to_end" | "per_layer"
    read: object  # read(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    loop: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]

    @property
    def unbounded(self) -> bool:
        """Whether the mix sends unbounded-?P ops (the u_* block)."""
        from chipbench.traffic import UNBOUNDED_OPS

        return any(op in UNBOUNDED_OPS for op, w in self.mix["ops"].items()
                   if w > 0)


def reader(name: str):
    """``metrics/<name>.py``'s ``read`` function.  A metric split by loop
    (``host.decode_ms.open``, ``host.decode_ms.closed``) whose halves read
    alike shares one file without the suffix (``host.decode_ms.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file() and name.rsplit(".", 1)[-1] in ("open", "closed"):
        path = HERE / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no metric reader metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: dict | None = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its files read."""
    bench = load_benchmark() if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json ({known})")
    cell = _load_json("cells", workload)
    if cell["config"] != entry["config"] or traffic_name(cell) != entry["traffic"]:
        raise ValueError(f"cells/{workload}.json names config "
                         f"{cell['config']!r} and traffic "
                         f"{traffic_name(cell)!r}; BENCHMARK.json says "
                         f"{entry['config']!r} and {entry['traffic']!r}")

    def metrics(kind):
        return tuple(Metric(m["name"], m["unit"], kind, reader(m["name"]))
                     for m in bench[kind] if _reports(m, workload))

    loop = {k: v for k, v in cell.items() if k not in ("config", "mix")}
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=cell["config"], config=_load_json("configs", cell["config"]),
        mix_name=cell["mix"], mix=_load_json("mixes", cell["mix"]), loop=loop,
        end_to_end=metrics("end_to_end"), per_layer=metrics("per_layer"),
    )


def cell_names(bench: dict | None = None) -> list[str]:
    bench = load_benchmark() if bench is None else bench
    return [w["name"] for w in bench["workloads"]]
