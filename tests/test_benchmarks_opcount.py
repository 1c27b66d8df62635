"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_opcount.py."""
from benchmarks.tests.test_opcount import *  # noqa: F401,F403
