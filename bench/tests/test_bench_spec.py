"""BENCHMARK.json resolves: every cell to its configuration, traffic mix,
driver and limits, every metric to its reader, and every name and unit
uses only the characters the contract allows."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = {x["name"]: x for x in SPEC["workloads"]}[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    conf = {c["name"]: c for c in SPEC["configs"]}[w["config"]]
    body = json.loads((ROOT / conf["file"]).read_text())
    assert body["reduced"] == conf["reduced"]
    assert body["source"] == conf["source"]
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    importlib.import_module(f"bench.drivers.{traffic['driver']}")
    importlib.import_module(f"bench.references.{body['reference']}")
    limits = json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                        .read_text())
    assert limits and all(v["limit"] is not None for v in limits.values())
    from bench.run import cell_metrics
    e2e = cell_metrics(SPEC, cell, per_layer=False)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert cell_metrics(SPEC, cell, per_layer=True)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if metric in SPEC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in [m["name"] for m in SPEC["end_to_end"]]
        assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").exists()
        from bench.run import load_reader
        assert callable(load_reader(metric["name"]))
    for w in metric.get("workloads", []):
        assert w in CELLS
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_files_under_paths_are_named_from_name_characters():
    for p in (ROOT / "bench").rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if "__pycache__" in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
