"""Tier-1 runs ``benchmark/tests/test_correct_rank.py``: ``run.measure``
through ``Dataset(group=)`` -> ``Booster`` -> ``update()`` under the
ranking cell's own limits — a sound run is ``correct``; the bf16 control,
the emulated faults and four planted faults are not."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_correct_rank", globals())
