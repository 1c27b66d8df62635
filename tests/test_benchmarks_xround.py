"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_xround.py."""
from benchmarks.tests.test_xround import *  # noqa: F401,F403
