"""Device self time of the XLA operations the program runs under
``lgbm/root_hist`` and ``lgbm/wave/hist``, an iteration, the ``hist``
kernels' own time and the collectives' scopes (``lgbm/root_hist/psum``,
``lgbm/wave/hist_psum``) left out: the flush-by-slot epilogue, the pool's
gather, subtraction and scatter round the kernels. ``scopesplit.py`` joins
the program's op-scope table with the traced window; the mean chip where
there are several. None where the program publishes no table."""
import scopesplit


def read(facts):
    sp = scopesplit.of(facts)
    if sp is None:
        return None
    return 1e3 * scopesplit.part_seconds(sp, "hist_xla") / sp["done"]
