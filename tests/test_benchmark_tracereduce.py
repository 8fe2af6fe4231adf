"""Tier-1 runs ``benchmark/tests/test_tracereduce.py``: the device-trace
reduction by hand and against a recorded v5e trace."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_tracereduce", globals())
