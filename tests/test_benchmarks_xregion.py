"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_xregion.py."""
from benchmarks.tests.test_xregion import *  # noqa: F401,F403
