"""Tier-1 runs ``benchmark/tests/test_root_readers.py``: the two readers
of the root pass, on a made-up trace and against the registry."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_root_readers", globals())
