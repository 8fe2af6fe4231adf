"""The faults a one-chip training cell can have, planted under the timed path
through the kind's one seam ``make_system``. Each takes the kind's module and
returns what goes in ``make_system``'s place. ``tests/test_correct.py`` drives
them at a size a test can hold and sees ``correct`` come out false;
``calibrate.py --plant`` reads one at the cell's own size on the chip."""
from __future__ import annotations

import re

import numpy as np

import reference


def unchanged(kind):
    class Unchanged(kind.System):
        def update(self):
            stop = super().update()
            self.bst.rollback_one_iter()        # the step's work is undone
            return stop
    return Unchanged


def frozen_scores(kind):
    class Frozen(kind.System):
        """Trees are grown and written, but the scores never move."""

        def __init__(self, params, X, y, spans):
            super().__init__(params, X, y, spans)
            p = float(np.mean(y, dtype=np.float64))
            self._init = float(np.log(p / (1.0 - p)))

        def scores(self):
            import jax.numpy as jnp
            return jnp.full_like(super().scores(), self._init)
    return Frozen


def half_left_out(kind):
    class Half(kind.System):
        """Every row is partitioned, so the leaf counts stay exact, but the
        odd blocks of rows carry weight 0 and are left out of every sum."""

        def __init__(self, params, X, y, spans):
            import lightgbm_tpu as lgb
            w = ((np.arange(len(y)) // reference.BLOCK) % 2 == 0).astype(np.float32)
            self.ds = lgb.Dataset(X, label=y, weight=w, params=dict(params))
            self.ds.construct()
            self.bst = lgb.Booster(dict(params), self.ds)
    return Half


def altered_leaf(kind):
    class Altered(kind.System):
        def model_text(self):
            text = super().model_text()
            head, tree1 = text.split("\nTree=1\n", 1)
            m = re.search(r"^leaf_value=(.*)$", tree1, re.M)
            v = [float(x) for x in m.group(1).split()]
            i = int(np.argmax(np.abs(v)))
            v[i] *= 1.05
            tree1 = (tree1[:m.start(1)] + " ".join(repr(x) for x in v)
                     + tree1[m.end(1):])
            return head + "\nTree=1\n" + tree1
    return Altered


ALL = {f.__name__: f for f in (unchanged, frozen_scores, half_left_out,
                               altered_leaf)}
