"""Device self time of the operations the program runs under
``lgbm/wave/bookkeep``, an iteration: electing each wave, the per-leaf
tables and the tree record. ``scopesplit.py`` joins the program's op-scope
table with the traced window; the mean chip where there are several. None
where the program publishes no table."""
import scopesplit


def read(facts):
    sp = scopesplit.of(facts)
    if sp is None:
        return None
    return 1e3 * scopesplit.part_seconds(sp, "bookkeep") / sp["done"]
