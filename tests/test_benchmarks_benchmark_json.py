"""Tier-1 runs the benchmark's own tests: benchmarks/tests/test_benchmark_json.py."""
from benchmarks.tests.test_benchmark_json import *  # noqa: F401,F403
