"""BENCHMARK.json and the cell files it names keep to the benchmark's
rules: names and units from the allowed characters, every file found by
name, every per-layer metric moving one end-to-end metric that each of its
cells reports."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
E2E = {m["name"] for m in SPEC["end_to_end"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def _text_ok(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def _cell_e2e(cell: str) -> set[str]:
    return {m["name"] for m in SPEC["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}


def test_top_level_shape():
    assert set(SPEC) == TOP_KEYS
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entry_keys_and_names(section, keys):
    entries = SPEC[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = set(e) - keys - ({"workloads"} if section in
                                 ("end_to_end", "per_layer") else set())
        assert set(e) >= keys and not extra, (e["name"], set(e) ^ keys)
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end":
                assert _text_ok(e[k]), (e["name"], k)
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")


def test_bounds_and_metric_sources():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    assert "setup_s" in E2E
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_configs_files():
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_found_by_name(cell):
    w = CELLS[cell]
    assert w["chips"] in (1, 4)
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert (BENCH / "configs" / f"{w['config']}.json").is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    spec = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    assert (BENCH / "drivers" / f"{spec['driver']}.py").is_file()
    assert spec["check"]["limits"]["decisions_differing"] == 0
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    planner = cfg.get("agent", {}).get("planner")
    if planner is not None:
        assert planner.isidentifier(), planner
        assert (BENCH / "reference" / f"{planner}.py").is_file()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_what_it_needs(cell):
    e2e = _cell_e2e(cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"]
             if cell in m.get("workloads", [cell])]
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], cell)


def test_per_layer_metrics_move_one_e2e_metric():
    layers: dict[str, str] = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in E2E
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", []):
            assert cell in CELLS
        layers.setdefault(m["layer"], m["layer"])
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(SPEC["workloads"])


def test_four_chip_share():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_traffic_files_are_data():
    for w in SPEC["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        assert NAME.match(t["process"]), t["process"]
        assert (BENCH / "lib" / "processes" / f"{t['process']}.py").is_file()
        assert t["arrivals"] > 0 and t["pool"] > 0 and t["load"] > 0
