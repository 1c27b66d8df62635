"""BENCHMARK.json against its contract, and every name against a file."""

import json
import os
import re

import pytest

from benchmarks.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for c in bench["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert 1 <= len(c["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_name_finds_its_file(bench):
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for c in bench["configs"]:
        cfg = harness.load_json("configs", c["name"] + ".json")
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for c in bench["workloads"]:
        traffic = harness.load_json("traffic", c["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            harness.BENCH, "drivers", traffic["driver"] + ".py"))
        assert harness.load_json("limits", c["name"] + ".json")
    for m in bench["per_layer"]:
        how = harness.load_json("layer_metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            harness.BENCH, "readers", how["reader"] + ".py"))
        assert set(m["workloads"]) <= cells
        assert how["moves"] == m["moves"] and how["cells"] == m["workloads"]
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:      # setup_s, another end-to-end, one per-layer
        assert any(cell in m.get("workloads", cells) and m["name"] != "setup_s"
                   for m in bench["end_to_end"])
        assert any(cell in m["workloads"] for m in bench["per_layer"])
