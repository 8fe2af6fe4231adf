"""Host clock around the first ``update()``: trace, lower, and the compile or
its fetch from the cache."""


def read(facts):
    return facts["first_step_s"]
