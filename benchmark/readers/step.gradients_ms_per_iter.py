"""Device self time of the operations the program runs under its
``lgbm/gradients`` scope, an iteration: the objective's gradient (the
pair gradient, ``lgbm/gradients/rank_pairs``, in a ranking cell) and the
grower's bagging and quantization of g and h. ``scopesplit.py`` joins the
program's op-scope table with the traced window; the mean chip where there
are several. None where the program publishes no table."""
import scopesplit


def read(facts):
    sp = scopesplit.of(facts)
    if sp is None:
        return None
    return 1e3 * scopesplit.part_seconds(sp, "gradients") / sp["done"]
