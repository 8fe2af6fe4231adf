"""Tier-1 runs ``benchmark/tests/test_dp_readers.py`` (the data-parallel cell
``criteo_dp4.train``: its six readers on a made-up two-chip trace, the wire's required
bytes by hand, ``datagen_f32`` against ``datagen``)."""
from conftest import adopt_benchmark_tests

adopt_benchmark_tests("test_dp_readers", globals())
