"""A float32 matrix through ``Dataset`` (basic.py ``keeps_float32``): it
reaches the device binner as float32, its bins are the float64 route's
bit for bit, one device or four, and no float64 copy of the matrix is
made on the way.

Every case forces ``tpu_ingest=1`` so the device kernels run on the CPU
backend (the path a TPU takes by default).
"""
import tracemalloc

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.basic import _data_to_2d, keeps_float32
from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.utils.device import get_devices

pytestmark = pytest.mark.ingest

PARAMS = {"objective": "binary", "max_bin": 63, "min_data_in_leaf": 20,
          "tpu_ingest": 1, "tpu_ingest_chunk_rows": 1024, "verbose": -1}
MESH = {"serial": {}, "data": {"tree_learner": "data", "num_machines": 4}}


def _matrix(n=6000, seed=3):
    """float32 columns with every edge the rounding rule has: plain
    values, NaN, the zero crossing, a categorical and a constant column
    (the values one ulp either side of a bound are put in by
    ``_ulp_neighbours`` once the bounds are known)."""
    r = np.random.default_rng(seed)
    zero = np.concatenate([
        np.array([-0.0, 0.0, 1e-36, -1e-36, 1e-35, -1e-35,
                  np.nextafter(np.float32(1e-35), np.float32(1)),
                  np.nextafter(np.float32(-1e-35), np.float32(-1))]),
        r.normal(size=n - 8) * 1e-30])
    X = np.column_stack([
        r.normal(size=n),
        np.where(r.uniform(size=n) < 0.15, np.nan, r.normal(size=n)),
        np.where(r.uniform(size=n) < 0.5, 0.0, r.normal(size=n)),
        r.integers(0, 9, n).astype(np.float64),           # categorical
        zero,
        np.full(n, 2.5),                                  # constant
        r.exponential(size=n) * 1e3,
    ]).astype(np.float32)
    y = (X[:, 0] + np.nan_to_num(X[:, 1]) > 0).astype(np.float32)
    return np.ascontiguousarray(X), y


def _ulp_neighbours(X, ds):
    """Overwrite the head of each numerical column with the float32
    values just under, at and just over every bin bound."""
    X = X.copy()
    for m, col in zip(ds._inner.mappers, ds._inner.used_feature_map):
        if m.bin_type != 0:
            continue
        b = np.asarray(m.bin_upper_bound[:-1], np.float64)
        b = b[np.isfinite(b)]
        f = b.astype(np.float32)
        vals = np.concatenate([f, np.nextafter(f, np.float32(-np.inf)),
                               np.nextafter(f, np.float32(np.inf))])
        X[100:100 + len(vals), col] = vals[:X.shape[0] - 100]
    return X


def _bins(ds):
    inner = ds._inner
    assert inner.bins_t_dev is not None, "device ingest did not engage"
    return np.asarray(inner.bins_t_dev)[:, :inner.num_data]


def _construct(X, y, params, reference=None):
    ds = lgb.Dataset(X, label=y, params=dict(params),
                     categorical_feature=[3], reference=reference)
    return ds.construct()


def test_keeps_float32_is_narrow():
    X, _ = _matrix(64)
    assert keeps_float32(X) and _data_to_2d(X)[0] is X
    for other in (X.astype(np.float64), X[:, ::2], X.T, X[:, 0],
                  X.tolist(), np.asfortranarray(X)):
        got = _data_to_2d(other)[0]
        assert not keeps_float32(other) and got.dtype == np.float64


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_float32_bins_are_the_float64_routes(learner):
    if learner == "data" and len(get_devices()) < 4:
        pytest.skip("needs a 4-device mesh")
    params = dict(PARAMS, **MESH[learner])
    X, y = _matrix()
    X = _ulp_neighbours(X, _construct(X, y, PARAMS))
    before = dict(obs.default_registry().counter_items()).get(
        "ingest/f32_rows", 0)
    d32 = _construct(X, y, params)
    after = dict(obs.default_registry().counter_items())["ingest/f32_rows"]
    d64 = _construct(X.astype(np.float64), y, params)
    assert after - before == len(X)       # every row, and the f64 route none
    assert dict(obs.default_registry().counter_items())[
        "ingest/f32_rows"] == after
    m32, m64 = d32._inner.mappers, d64._inner.mappers
    assert len(m32) == len(m64) == 6      # the constant column is dropped
    for a, b in zip(m32, m64):
        assert np.array_equal(a.bin_upper_bound, b.bin_upper_bound,
                              equal_nan=True)
    np.testing.assert_array_equal(_bins(d32), _bins(d64))
    # the host binner is the oracle of both
    host = _construct(X, y, dict(params, tpu_ingest=0,
                                 tree_learner="serial"))
    np.testing.assert_array_equal(_bins(d32), host._inner.bins.T)
    if learner == "data":
        shards = d32._inner.bins_t_dev.addressable_shards
        assert len({sh.device for sh in shards}) == 4
        assert {sh.data.shape for sh in shards} == {
            (6, d32._inner.bins_t_dev.shape[1] // 4)}


def test_valid_set_of_a_float32_train_set():
    X, y = _matrix()
    tr = _construct(X, y, PARAMS)
    v32 = _construct(X[:999], y[:999], PARAMS, reference=tr)
    v64 = _construct(X[:999].astype(np.float64), y[:999], PARAMS,
                     reference=tr)
    np.testing.assert_array_equal(_bins(v32), _bins(v64))
    np.testing.assert_array_equal(_bins(v32), _bins(tr)[:, :999])


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_no_float64_copy_of_the_matrix(learner):
    if learner == "data" and len(get_devices()) < 4:
        pytest.skip("needs a 4-device mesh")
    r = np.random.default_rng(0)
    X = r.normal(size=(200_000, 16)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = dict(PARAMS, **MESH[learner], tpu_ingest_chunk_rows=32768)
    lgb.Dataset(X[:40_000], label=y[:40_000],
                params=dict(params)).construct()       # compiles, imports
    tracemalloc.start()
    try:
        ds = lgb.Dataset(X, label=y, params=dict(params)).construct()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert ds._inner.bins_t_dev is not None
    assert peak < 1.5 * X.nbytes, (peak, X.nbytes)


def test_train_on_float32_equals_train_on_float64():
    X, y = _matrix()
    p = dict(PARAMS, num_leaves=15)
    texts = []
    for M in (X, X.astype(np.float64)):
        bst = lgb.Booster(dict(p), _construct(M, y, p))
        for _ in range(3):
            bst.update()
        texts.append(bst.model_to_string())
    assert texts[0] == texts[1]
