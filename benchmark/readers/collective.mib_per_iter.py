"""MiB ONE chip hands the histogram seam a tree: the program's counter
``comm/psum_bytes`` (``models/gbdt.py``: from the shapes of the summed blocks
and the passes of the trees the stop check has counted) over
``hist/trees_counted``. None where the program has no such counter."""


def read(facts):
    from lightgbm_tpu.obs import registry as obs
    c = dict(obs.default_registry().counter_items())
    n = int(c.get("hist/trees_counted", 0))
    if not n or "comm/psum_bytes" not in c:
        return None
    return c["comm/psum_bytes"] / n / 2.0 ** 20
