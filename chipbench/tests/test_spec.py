"""``BENCHMARK.json`` keeps to the benchmark's contract, and every cell is
found from its files."""

from __future__ import annotations

import json
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(bench)) <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (spec.ROOT / p).is_dir()
    assert 1 <= len(bench["command"]) <= 32
    assert all(_one_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert (spec.ROOT / bench["command"][1]).is_file()
    assert any(bench["command"][1].startswith(p + "/") for p in bench["paths"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    full = 2 + 14 * 24
    assert full * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"]) and _one_line(c["why"])
        assert c["file"].startswith("chipbench/") and c["file"] not in files
        files.add(c["file"])
        data = json.loads((spec.ROOT / c["file"]).read_text())
        assert data["source"] == c["source"] and data["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        changed = {k for k, v in data["published"].items() if data[k] != v}
        assert changed == set(c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert len({c["source"] for c in bench["configs"]}) == len(bench["configs"])


def test_dbtune_keeps_its_predicates():
    data = json.loads((spec.HERE / "configs" / "dbtune-9m.json").read_text())
    assert data["preds"] == data["published"]["preds"] == 394
    assert "preds" not in data["reduced"]


def test_workloads(bench):
    ws = bench["workloads"]
    assert 1 <= len(ws) <= 24
    assert len({w["name"] for w in ws}) == len(ws)
    assert len({(w["config"], w["traffic"]) for w in ws}) == len(ws)
    assert sum(w["chips"] == 4 for w in ws) <= max(1, len(ws) // 2)
    configs = {c["name"] for c in bench["configs"]}
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and _one_line(w["why"])


def test_metrics(bench):
    names = set()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _one_line(m["layer"])
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in cells
            assert "workloads" not in moved or w in moved["workloads"], (m["name"], w)
        layers.setdefault(m["layer"], m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["name"] not in names
        assert set(m.get("workloads", cells)) <= cells
        names.add(m["name"])
        assert callable(spec.reader(m["name"]))


def test_every_cell_reports_enough(bench):
    for name in spec.cell_names(bench):
        cell = spec.load_cell(name, bench)
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_cells_are_discovered_from_files(bench):
    """Each cell file is a workload of ``BENCHMARK.json`` and each workload
    has its cell file; loading one reads its config, mix and readers."""
    files = sorted(p.stem for p in (spec.HERE / "cells").glob("*.json"))
    assert files == sorted(spec.cell_names(bench))
    for name in files:
        cell = spec.load_cell(name, bench)
        assert cell.name == name and cell.chips == 1
        assert cell.config["name"] == cell.config_name
        assert cell.mix["name"] == cell.mix_name
        assert cell.loop["loop"] in ("open", "closed")
        if cell.loop["loop"] == "open":
            assert cell.loop["rate_per_s"] > 0
        else:
            assert cell.loop["outstanding_per_tenant"] > 0


def test_a_cell_is_added_by_files_alone(tmp_path, monkeypatch):
    """A new cell needs a cell file and an entry, and no code."""
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "geonames.describe.open", "config": "geonames",
                               "traffic": "describe.open", "chips": 1, "why": "x"})
    cells = tmp_path / "cells"
    cells.mkdir()
    (cells / "geonames.describe.open.json").write_text(json.dumps(
        {"config": "geonames", "mix": "describe", "loop": "open",
         "rate_per_s": 100, "warmup_requests": 64}))
    real = spec._load_json

    def load_json(kind, name):
        path = tmp_path / kind / f"{name}.json"
        return json.loads(path.read_text()) if path.is_file() else real(kind, name)

    monkeypatch.setattr(spec, "_load_json", load_json)
    cell = spec.load_cell("geonames.describe.open", bench)
    assert cell.unbounded and cell.config["preds"] == 20
    assert {m.name for m in cell.end_to_end} == {"device_bits_per_triple", "setup_s"}


def test_loop_split_metrics_share_a_reader():
    """``<stem>.open`` and ``<stem>.closed`` fall back to ``metrics/<stem>.py``;
    a name with neither suffix has no fallback."""
    shared = str(spec.HERE / "metrics" / "host.decode_ms.py")
    for name in ("host.decode_ms.open", "host.decode_ms.closed"):
        assert spec.reader(name).__code__.co_filename == shared
    with pytest.raises(FileNotFoundError):
        spec.reader("host.decode_ms.train")
