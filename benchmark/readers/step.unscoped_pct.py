"""Busy time in operations that the program's op-scope table maps to no
``lgbm/`` scope, or does not know (another program's, the harness's
fence), over the traced window's busy time: the table's coverage, which
keeps the four ``step.*_ms_per_iter`` splits honest. ``scopesplit.py``
prints the largest such operations. None where the program publishes no
table."""
import scopesplit


def read(facts):
    sp = scopesplit.of(facts)
    if sp is None:
        return None
    return 100.0 * sp["by_scope"].get(None, 0.0) / sp["busy"]
