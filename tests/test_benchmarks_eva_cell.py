"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_eva_cell.py."""
import json
import types

from benchmarks.tests.test_eva_cell import *  # noqa: F401,F403
from benchmarks.tests import test_eva_cell as _cell

# what PR 42 (tracing) gave the cell by its issue, beside the 18 per-layer
# metrics the benchmark's file counts (a file this PR may not edit)
SINCE = {f"{name}.bytedocs" for name in (
    "decode_rounds_time_share", "round_ms_p50_chunk", "round_ms_p50_decode",
    "idle_under_operands_share", "idle_under_key_share",
    "idle_decode_rounds_share", "idle_under_sync_share",
    "operands_host_ms_p50", "key_host_ms_p50")}
# cells added since (PR 44): the benchmark's file counts six
CELLS_SINCE = {"longcat-serve-toolturns"}


def test_the_cell_is_the_issues(monkeypatch):
    """Every assert of the benchmark's test, its counts of 18 metrics and
    of 6 cells too, on ``BENCHMARK.json`` as it read when that test was
    written: the entries added since are held here by name — the metrics
    the cell's alone, the cells on one chip each."""
    def load(f):
        bench = json.load(f)
        since = [m for m in bench["per_layer"] if m["name"] in SINCE]
        assert {m["name"] for m in since} == SINCE
        assert all(m["workloads"] == [_cell.CELL] and
                   m["moves"] == "serve_tok_s" for m in since)
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in SINCE]
        later = [c for c in bench["workloads"] if c["name"] in CELLS_SINCE]
        assert {c["name"] for c in later} == CELLS_SINCE
        assert all(c["chips"] == 1 for c in later)
        bench["workloads"] = [c for c in bench["workloads"]
                              if c["name"] not in CELLS_SINCE]
        return bench
    monkeypatch.setattr(_cell, "json", types.SimpleNamespace(load=load))
    _cell.test_the_cell_is_the_issues()
