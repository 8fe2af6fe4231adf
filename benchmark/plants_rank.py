"""The faults a ranking cell can have, planted under the timed path through
``kinds/rank_loop.py``'s one seam ``make_system``; each takes the kind's module
and returns what goes in ``make_system``'s place. ``plants.py``'s
``unchanged`` and ``altered_leaf`` know nothing of the objective and are taken
as they are; its other two read a binary label and have their ranking forms
here, beside the fault only a ranking job can have:

* ``half_left_out``: every row is partitioned, so the leaf counts stay exact,
  but the odd blocks of rows carry weight 0 and are left out of every sum
  (lambdarank multiplies a row's lambda and hessian by its weight);
* ``tail_pairs_left_out``: every query's pairs beyond its first
  ``reference_rank.TAIL_KEPT`` documents are left out: the cheap way for a
  pair layout to be fast is to drop the long tail, and ``correct`` has to see
  it. Planted where the objective keeps its query tables
  (``LambdarankNDCG._pair_classes``: the labels a query a line, -1 where there
  is no document): documents from the 65th on are struck from their query.

``tests/test_correct_rank.py`` drives them at a size a test can hold;
``calibrate_rank.py --plant`` reads one at the cell's own size on the chip.
"""
from __future__ import annotations

import numpy as np

import plants
import reference
import reference_rank


def half_left_out(kind):
    class Half(kind.System):
        def __init__(self, params, X, fields, spans):
            import lightgbm_tpu as lgb
            n = len(fields["label"])
            w = ((np.arange(n) // reference.BLOCK) % 2 == 0).astype(np.float32)
            self.ds = lgb.Dataset(X, **fields, weight=w, params=dict(params))
            self.ds.construct()
            self.bst = lgb.Booster(dict(params), self.ds)
    return Half


def tail_pairs_left_out(kind):
    class Tail(kind.System):
        def __init__(self, params, X, fields, spans):
            from lightgbm_tpu.objectives.objective import LambdarankNDCG
            build = LambdarankNDCG._build_pair_layout

            def cut(obj, lab):
                build(obj, lab)
                for c in obj._pair_classes:
                    c["lab"][:, reference_rank.TAIL_KEPT:] = -1
            LambdarankNDCG._build_pair_layout = cut
            try:
                super().__init__(params, X, fields, spans)
            finally:
                LambdarankNDCG._build_pair_layout = build
    return Tail


ALL = {f.__name__: f for f in (plants.unchanged, plants.altered_leaf,
                               half_left_out, tail_pairs_left_out)}
