"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_xplane.py."""
from benchmarks.tests.test_xplane import *  # noqa: F401,F403
