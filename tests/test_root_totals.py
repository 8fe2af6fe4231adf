"""The totals a tree starts from and the counts it ends with, where float32
runs out (PERF.md, PR 36): ``wave_grower._stable_sum`` adds its block sums
pairwise, so a booster's first tree (every hessian the same number) gets a
root total that agrees with its histogram; and past 2^24 rows a leaf's count
is an integer count of the rows' leaf ids, on one chip too."""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.wave_grower import _SUM_BLOCK, _stable_sum


@pytest.mark.parametrize("blocks", [1, 5, 1280])
def test_stable_sum_of_equal_values_does_not_drift(blocks):
    """1,280 equal block sums into a RUNNING float32 total round every
    addition the same way (7e-6 of the total at this value); pairwise only
    a block's own sum rounds."""
    v = np.full(blocks * _SUM_BLOCK, 0.2475, np.float32)
    want = float(v.astype(np.float64).sum())
    got = float(_stable_sum(jnp.asarray(v)))
    assert abs(got - want) <= 1e-6 * want


@pytest.mark.parametrize("n, pad", [
    (20_000, 12_768), (3 * _SUM_BLOCK, 5 * _SUM_BLOCK), (70_001, 61_071)])
def test_stable_sum_is_unmoved_by_zero_padding(n, pad):
    """The step cache's row buckets append zeros: the sum must not move by
    a bit, whatever power of two the block sums are padded to."""
    v = np.random.default_rng(n).standard_normal(n).astype(np.float32)
    padded = np.concatenate([v, np.zeros(pad, np.float32)])
    assert (np.asarray(_stable_sum(jnp.asarray(v))).tobytes()
            == np.asarray(_stable_sum(jnp.asarray(padded))).tobytes())


def test_leaf_count_past_2_24_rows_on_one_chip():
    """2^24 + 2^14 rows, one split that sends 2^14 - 1 of them right: the
    float32 route read the left count as 2^24 (it is 2^24 + 1) and gave the
    right leaf the difference, 2^14. Counted from the leaf ids it is exact;
    the larger leaf still rounds where the record stores it as float32."""
    n, small = 2 ** 24 + 2 ** 14, 2 ** 14 - 1
    X = np.zeros((n, 1), np.float32)
    X[:small] = 1.0
    y = X[:, 0].copy()
    params = {"objective": "regression", "num_leaves": 2, "max_bin": 3,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 0.0,
              "learning_rate": 1.0, "verbose": -1}
    bst = lgb.Booster(dict(params),
                      lgb.Dataset(X, label=y, params=dict(params)).construct())
    bst.update()
    counts = next(line for line in bst.model_to_string().splitlines()
                  if line.startswith("leaf_count="))
    assert counts == f"leaf_count={2 ** 24} {small}"
