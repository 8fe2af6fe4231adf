"""Device-busy time outside the histogram kernels, an iteration: the traced
window's busy time less the ``hist`` group's self time (the traffic file's
``kernels.hist`` names), over the iterations traced. In a ranking cell this is
the pair gradient plus the XLA round the kernels (pool gather and scatter,
split scan, score update); PERF.md section 5 tells its parts apart. None where
the trace holds no ``hist`` kernel."""


def read(facts):
    tr = facts["trace"]
    if "hist" not in tr["kernel_s"] or not tr["busy_s"] or not facts["done"]:
        return None
    return 1e3 * (tr["busy_s"] - tr["kernel_s"]["hist"]) / facts["done"]
