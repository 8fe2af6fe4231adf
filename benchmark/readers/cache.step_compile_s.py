"""Seconds the backend compiled during the fused step's first dispatch: the
program's timer ``step_cache/backend_compile`` (``ops/step_cache.py``: jax's
``backend_compile_duration`` events inside the ``step_cache/compile`` span,
a fetch from the persistent cache counted as 0). 0.0 is a reading: the
persistent cache served the step."""
import progtrace


def read(facts):
    return progtrace.registry_timer("step_cache/backend_compile")
