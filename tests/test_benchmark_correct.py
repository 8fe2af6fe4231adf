"""Tier-1 runs ``benchmark/tests/test_correct.py``: ``run.measure``
through the normal entry points under the cell's own limits — a sound
run is ``correct``; the bf16 control and four planted faults are not."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_correct", globals())
