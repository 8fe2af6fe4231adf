"""Tier-1 runs ``benchmark/tests/test_progtrace.py``: the program-span
reduction on a made-up trace and against a recorded v5e trace."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_progtrace", globals())
