"""BENCHMARK.json against the benchmark's contract and its own files."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def under_paths(doc, *parts):
    return [p for p in (os.path.join(ROOT, d, *parts) for d in doc["paths"])
            if os.path.exists(p)]


def test_top_level_keys_and_limits(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert doc["command"] == ["python3", "-m", "chipbench.run"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in doc["paths"])
    # (2 + 14 x 24 cells) runs of run_seconds + 60, 2 x 90 a cell, 1200 spare
    rs = doc["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_cells_resolve_to_files(doc):
    configs = {c["name"]: c for c in doc["configs"]}
    assert 2 <= len(doc["workloads"]) <= 24
    pairs = set()
    for cell in doc["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        assert cell["config"] in configs
        assert under_paths(doc, "traffic", cell["traffic"] + ".json")
        pairs.add((cell["config"], cell["traffic"]))
    assert len(pairs) == len(doc["workloads"])
    four = [c for c in doc["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    used = {c["config"] for c in doc["workloads"]}
    for name, entry in configs.items():
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert name in used
        assert any(entry["file"].startswith(p + "/") for p in doc["paths"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            body = json.load(f)
        assert body["name"] == name and body["reduced"] == entry["reduced"]
        assert len(entry["source"]) <= 200
        for key in ("family", "assumed", "deployment", "guarantees",
                    "tolerances"):
            assert key in body, (name, key)


def test_names_and_units(doc):
    names = ([c["name"] for c in doc["configs"]]
             + [c["name"] for c in doc["workloads"]]
             + [c["traffic"] for c in doc["workloads"]]
             + [m["name"] for g in ("end_to_end", "per_layer") for m in doc[g]])
    for n in names:
        assert NAME.match(n), n
    metrics = doc["end_to_end"] + doc["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_metrics_cover_every_cell(doc):
    cells = [c["name"] for c in doc["workloads"]]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    layers = set()
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        layers.add(m["layer"])
        assert under_paths(doc, "layer_metrics", m["name"] + ".py"), m["name"]
        # a per-layer metric is reported only where the metric it moves is
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved_in, m["name"]
    for cell in cells:
        mine = [m for m in doc["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(cell in m.get("workloads", cells) for m in doc["per_layer"])
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
