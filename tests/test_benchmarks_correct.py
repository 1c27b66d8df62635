"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_correct.py."""
from benchmarks.tests.test_correct import *  # noqa: F401,F403
