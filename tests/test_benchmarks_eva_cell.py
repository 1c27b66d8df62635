"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_eva_cell.py."""
from benchmarks.tests.test_eva_cell import *  # noqa: F401,F403
