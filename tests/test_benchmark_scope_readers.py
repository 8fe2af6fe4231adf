"""Tier-1 runs ``benchmark/tests/test_scope_readers.py``: the five readers
of device time by the program's own scopes, on a made-up window and
op-scope table."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_scope_readers", globals())
