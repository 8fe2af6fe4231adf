"""Rows the histogram kernels put through their one-hot dot over the rows the
grown trees require (``work.py``: the root's rows and each split's smaller
child's): 1.0 is a program that dots exactly what LightGBM's histogram
subtraction needs, 4.1 one that dots every row in every wave pass.

The wave passes' share comes from the program's counters ``hist/rows_dotted``
and ``hist/trees_counted`` (``models/gbdt.py``: fed at the stop check, from two
scalars a tree that ride the download it already makes; the fused TPU kernel
counts its dotted tiles, ``ops/hist_wave.py``); the root pass dots every row
of every tree. The trees compared are the booster's first ``trees_counted``,
set-up's included: the counters run from the process's start. None where the
program has no such counter."""
import work


def read(facts):
    from lightgbm_tpu.obs import registry as obs
    counters = dict(obs.default_registry().counter_items())
    n = int(counters.get("hist/trees_counted", 0))
    trees = facts["trees"][:n]
    if not n or len(trees) < n or "hist/rows_dotted" not in counters:
        return None
    need = work.required(trees, facts["rows"], facts["features"],
                         facts["bins"])["row_reads"]
    return (counters["hist/rows_dotted"] + n * facts["rows"]) / need
