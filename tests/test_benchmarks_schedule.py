"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_schedule.py."""
from benchmarks.tests.test_schedule import *  # noqa: F401,F403
