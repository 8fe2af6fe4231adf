"""``tree_learner=data`` over four devices against the benchmark's plain
reference (``benchmark/reference.py``) and against the serial learner on the
same rows, at 65,536 x 67, 31 leaves, 3 trees, a float32 matrix through the
device ingest: every compared number under tiny limits, leaf counts exact,
the model the serial learner's but for the order of a float32 sum; one chip's
histograms left out of the sum reads not correct."""
import sys
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.device import get_devices

pytestmark = pytest.mark.skipif(
    len(get_devices()) < 4, reason="needs a 4-device mesh")

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path[:0] = [str(BENCH)]

ROWS, TREES = 65536, 3
SPEC = {"rows": ROWS, "features": 67, "levels": 255, "uniform_columns": 34,
        "skew_scale": 80.0, "label_noise": 0.5, "dtype": "float32"}
PARAMS = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
          "max_bin": 255, "min_data_in_leaf": 20,
          "min_sum_hessian_in_leaf": 1e-3, "lambda_l2": 0.0,
          "tpu_ingest": 1, "verbose": -1}
DATA = {"tree_learner": "data", "num_machines": 4}
# a float32 sum in another order, at this size: PERF.md section 2 has the
# cell's own limits, read on the chips
TINY = {"trees_short": 0, "leaf_count": 0, "best_split": 1e-4, "score": 1e-5,
        "leaf_value_rows": 2e-4, "loss": 2e-6, "update_norm": 2e-5}


@pytest.fixture(scope="module")
def data():
    import datagen_f32
    return datagen_f32.make(SPEC, 2147483659, 2)


def _train(data, **over):
    X, _levels, y = data
    params = dict(PARAMS, **over)
    bst = lgb.Booster(dict(params),
                      lgb.Dataset(X, label=y, params=dict(params)).construct())
    scores = []
    for _ in range(TREES):
        bst.update()
        scores.append(np.asarray(bst._gbdt.train_scores()[0]))
    return bst, scores


def _judge(data, bst, scores):
    import modeltext
    import reference
    _X, levels, y = data
    trees = modeltext.parse_trees(bst.model_to_string())
    ref = reference.compare(levels, y, trees[:TREES], scores,
                            {**PARAMS, "levels": SPEC["levels"]},
                            2147483659, n_sampled=3)
    return reference.decide(ref["numbers"], TINY), trees


@pytest.fixture(scope="module")
def trained(data):
    return _train(data, **DATA), _train(data)


def test_data_parallel_model_is_the_references(data, trained):
    (bst, scores), _ = trained
    rep = bst.device_report()
    assert rep["learner_mode"] == "data" and rep["num_devices"] == 4
    assert len({dev for dev, _ in rep["bins_shards"]}) == 4
    (ok, rows), trees = _judge(data, bst, scores)
    assert ok, [r for r in rows if not r[1] <= r[2]]
    assert dict((k, v) for k, v, _ in rows)["leaf_count"] == 0
    assert len(trees) == TREES


@pytest.mark.parametrize("field, exact", [
    ("split_feature", True), ("threshold", True), ("leaf_count", True),
    ("internal_count", True), ("leaf_value", False),
    ("split_gain", False)])
def test_data_parallel_model_is_the_serial_learners(trained, field, exact):
    import modeltext
    (dp, _), (serial, _) = trained

    def rows(bst):
        out = []
        for block in bst.model_to_string().split("\nTree=")[1:]:
            kv = dict(l.split("=", 1) for l in block.splitlines()
                      if "=" in l)
            out.append(np.asarray(kv[field].split(), np.float64))
        return out
    a, b = rows(dp), rows(serial)
    assert len(a) == len(b) == TREES
    for x, y in zip(a, b):
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(x, y, rtol=1e-5,
                                       atol=1e-5 * np.abs(y).max())


def test_one_chip_left_out_of_the_sum_is_not_correct(data):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.parallel import learners

    def reduce(value, psum):
        if jnp.ndim(value) < 3:
            return psum(value)
        mine = jax.lax.axis_index(learners.AXIS) != 1
        return psum(jnp.where(mine, value, jnp.zeros_like(value)))

    learners.set_network_functions(reduce_scatter_fn=reduce)
    try:
        bst, scores = _train(data, **DATA)
    finally:
        learners.set_network_functions()
    assert not bst._gbdt._cache_eligible       # no shared step under a wrap
    (ok, rows), _ = _judge(data, bst, scores)
    assert not ok
    assert [k for k, v, lim in rows if not v <= lim]
