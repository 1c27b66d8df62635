"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_scmoe_cell.py."""
from benchmarks.tests.test_scmoe_cell import *  # noqa: F401,F403
