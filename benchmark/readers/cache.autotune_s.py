"""Seconds in the kernel autotuner (``utils/timing.py``, ``autotune``). With
the tuning cache warm it does nothing and reads 0.0: that is a reading, and a
run that reads more has tuned again."""


def read(facts):
    return facts["autotune_s"]
