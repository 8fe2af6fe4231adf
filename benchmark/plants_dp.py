"""The faults a DATA-PARALLEL training cell can have, planted under the timed
path through the kind's one seam ``make_system`` (``kinds/train_loop_dp.py``).
``tests/test_correct_dp.py`` drives them at a size a test can hold and sees
``correct`` come out false; ``calibrate_dp.py`` reads them at the cell's own
size on the chips.

``one_chip_left_out`` is the fault that belongs to the mechanism: the sum
across the chips misses one chip's histograms. It is planted through the
program's own seam for its collectives
(``lightgbm_tpu.parallel.learners.set_network_functions``: what an embedder
uses to wrap the reduce), so every row is still partitioned, every chip still
grows the same tree and the scalar totals are whole: only the ``[W, F, B, 3]``
blocks lose a quarter of the rows, as a chip that dropped out of a ring would
leave them. ``half_left_out`` is ``plants.py``'s fault on this kind's system.
"""
from __future__ import annotations

import numpy as np

import reference

LEFT_OUT_CHIP = 1


def one_chip_left_out(kind):
    class OneChipLeftOut(kind.System):
        """Chip ``LEFT_OUT_CHIP``'s histogram blocks are zeros in the sum."""

        def __init__(self, params, X, y, spans, **kw):
            import jax
            import jax.numpy as jnp
            from lightgbm_tpu.parallel import learners

            def reduce(value, psum):
                if jnp.ndim(value) < 3:          # a scalar total: whole
                    return psum(value)
                mine = jax.lax.axis_index(learners.AXIS) != LEFT_OUT_CHIP
                return psum(jnp.where(mine, value, jnp.zeros_like(value)))

            # read where the step is TRACED (the first update), so it stays
            # until close(); a booster built under it is not served by the
            # step registry (models/gbdt.py _step_cache_eligible)
            learners.set_network_functions(reduce_scatter_fn=reduce)
            super().__init__(params, X, y, spans, **kw)

        def close(self):
            from lightgbm_tpu.parallel import learners
            learners.set_network_functions()
            super().close()
    return OneChipLeftOut


def half_left_out(kind):
    class Half(kind.System):
        """Every row is partitioned, so the leaf counts stay exact, but the
        odd blocks of rows carry weight 0 and are left out of every sum."""

        def __init__(self, params, X, y, spans, **kw):
            w = ((np.arange(len(y)) // reference.BLOCK) % 2 == 0).astype(
                np.float32)
            super().__init__(params, X, y, spans, weight=w, **kw)
    return Half


ALL = {f.__name__: f for f in (one_chip_left_out, half_left_out)}
