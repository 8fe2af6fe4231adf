"""Device self time of the operations the program runs under
``lgbm/wave/split_find`` (the best-split scan of the root and of every
wave's children) and ``lgbm/wave/split_sync`` (the feature- and
voting-parallel learners' gather of the shards' best splits), an
iteration. ``scopesplit.py`` joins the program's op-scope table with the
traced window; the mean chip where there are several. None where the
program publishes no table."""
import scopesplit


def read(facts):
    sp = scopesplit.of(facts)
    if sp is None:
        return None
    return 1e3 * scopesplit.part_seconds(sp, "split_find") / sp["done"]
