"""Tier-1 runs ``benchmark/tests/test_correct_dp.py`` (the data-parallel cell
``criteo_dp4.train``: ``run.measure`` through kind ``train_loop_dp`` over four
virtual devices under the cell's own limits, its planted faults and guards)."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_correct_dp", globals())
