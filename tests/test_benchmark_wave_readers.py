"""Tier-1 runs ``benchmark/tests/test_wave_readers.py``: the reader of what
a wave pass's dot spends on a row, against the registry."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_wave_readers", globals())
