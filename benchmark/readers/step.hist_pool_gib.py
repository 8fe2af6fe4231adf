"""Bytes of the grower's histogram pool, ``[num_leaves, F, B, 3]`` float32
(every leaf's histogram kept for the subtraction): the program's gauge
``mem/hist_pool_bytes`` (``ops/wave_grower.py``, from the pool's shape where the
grower is built). None where the program has no such gauge."""
import progtrace


def read(facts):
    pool = progtrace.registry_gauge("mem/hist_pool_bytes")
    return pool / 2**30 if pool else None
