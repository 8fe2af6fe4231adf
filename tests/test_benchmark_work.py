"""Tier-1 runs ``benchmark/tests/test_work.py``: the required-work
function by hand, and the model-text reader that feeds it."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_work", globals())
