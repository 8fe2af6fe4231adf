"""CSR-native sparse engine (io/sparse.py + SparseDeviceBinner +
wave_histogram_sparse): bit-parity against the densified path.

The densified dense-matrix route is the semantic oracle everywhere: the
CSR route must produce the SAME bin mappers (identical rng sample), the
SAME bin matrix (implicit cells = value_to_bin(0.0)), and therefore the
SAME trained model text and predictions — for numerical, categorical
and EFB-bundled features (the acceptance bar of ROADMAP item 5). The
sparse histogram TIER is additionally proven bit-equal to the dense
tier under quantized (integer, order-free) accumulation, and the O(nnz)
promise is asserted directly: a 1%-density workload trains without any
dense [N, F] materialization.
"""
import threading

import numpy as np
import pytest

from conftest import TEST_PARAMS, fit_gbdt
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata, TpuDataset, \
    find_column_mappers
from lightgbm_tpu.io.sparse import (SparseMatrix, bin_entries,
                                    find_column_mappers_sparse,
                                    host_bins_from_sparse,
                                    route_sparse, warn_dense_cliff,
                                    zero_bins)

pytestmark = pytest.mark.sparse

sp_sparse = pytest.importorskip("scipy.sparse")


def _sparse_task(n=1500, f=18, density=0.05, seed=0, cat_col=None,
                 nan_frac=0.0, tiny_col=None):
    """(dense X, SparseMatrix, y): a sparse matrix with the BinMapper
    edge cases on demand — categorical column, NaN entries, values
    straddling ±kZeroThreshold."""
    r = np.random.default_rng(seed)
    mask = r.uniform(size=(n, f)) < density
    X = np.where(mask, r.normal(size=(n, f)) * 2, 0.0)
    if cat_col is not None:
        X[:, cat_col] = np.where(mask[:, cat_col],
                                 r.integers(0, 7, n).astype(float), 0.0)
    if tiny_col is not None:
        X[:, tiny_col] = np.where(
            mask[:, tiny_col],
            np.sign(r.normal(size=n)) * 10.0 ** r.uniform(-37, -33, n),
            0.0)
    if nan_frac:
        X[(r.uniform(size=(n, f)) < nan_frac) & mask] = np.nan
    y = (np.nansum(X[:, : min(6, f)], axis=1)
         + 0.3 * r.normal(size=n) > 0).astype(np.float32)
    sm = SparseMatrix.from_scipy(sp_sparse.csr_matrix(X))
    return X, sm, y


def _trees(g):
    """Model text minus the parameters: block (config knobs like
    tpu_sparse legitimately differ across compared routes)."""
    s = g.model_to_string() if hasattr(g, "model_to_string") else g
    return s.split("\nparameters:\n")[0]


# ---------------------------------------------------------------------------
# Representation + binning parity
# ---------------------------------------------------------------------------

class TestRepresentation:
    def test_mappers_bit_identical(self):
        X, sm, _ = _sparse_task(n=2000, f=14, cat_col=3, nan_frac=0.02,
                                tiny_col=5)
        cfg = Config().set(dict(TEST_PARAMS))
        m0 = find_column_mappers(X, cfg, categorical=[3])
        m1 = find_column_mappers_sparse(sm, cfg, categorical=[3])
        assert len(m0) == len(m1)
        for a, b in zip(m0, m1):
            assert repr(a.to_dict()) == repr(b.to_dict())

    @pytest.mark.parametrize("zam", [False, True])
    def test_host_bins_cell_for_cell(self, zam):
        X, sm, y = _sparse_task(n=1600, f=12, cat_col=2, nan_frac=0.02,
                                tiny_col=7, seed=3)
        cfg = Config().set(dict(TEST_PARAMS, zero_as_missing=zam,
                                enable_bundle=False))
        ds = TpuDataset(cfg).construct_from_matrix(
            X, Metadata(label=y), categorical=[2])
        hb = host_bins_from_sparse(sm, ds.mappers, ds.used_feature_map,
                                   ds.bin_dtype())
        np.testing.assert_array_equal(hb, ds.bins)
        # explicit zeros / sub-threshold values land on the zero bin
        zb = zero_bins(ds.mappers)
        codes, feat, rows = bin_entries(sm, ds.mappers,
                                        ds.used_feature_map)
        rebuilt = np.empty_like(hb)
        rebuilt[:] = zb[None, :].astype(hb.dtype)
        rebuilt[rows, feat] = codes.astype(hb.dtype)
        np.testing.assert_array_equal(rebuilt, hb)

    def test_csc_and_duplicate_semantics(self):
        X, sm, _ = _sparse_task(n=400, f=6, seed=9)
        csc = sp_sparse.csc_matrix(X)
        sm2 = SparseMatrix.from_csc(csc.indptr, csc.indices, csc.data,
                                    *X.shape)
        np.testing.assert_array_equal(sm2.to_dense(), X)
        # duplicate (row, col) in raw CSR planes: LAST wins (the old
        # densify assignment's semantics)
        smd = SparseMatrix.from_csr([0, 2], [1, 1], [5.0, 7.0], 3)
        assert smd.nnz == 1 and smd.to_dense()[0, 1] == 7.0


# ---------------------------------------------------------------------------
# End-to-end route parity (model text + predictions)
# ---------------------------------------------------------------------------

def _capi_train(handle_factory, params, rounds=12):
    from lightgbm_tpu import capi
    h = handle_factory(params)
    b = capi.LGBM_BoosterCreate(h, params)
    for _ in range(rounds):
        capi.LGBM_BoosterUpdateOneIter(b)
    return b


class TestRouteParity:
    PARAMS = ("objective=binary max_bin=63 num_leaves=15 "
              "min_data_in_leaf=20 num_iterations=12")

    def _roundtrip(self, make_sparse_handle, X, y):
        from lightgbm_tpu import capi

        def dense_handle(params):
            h = capi.LGBM_DatasetCreateFromMat(X, parameters=params)
            capi.LGBM_DatasetSetField(h, "label", y)
            return h

        def sparse_handle(params):
            h = make_sparse_handle(params)
            capi.LGBM_DatasetSetField(h, "label", y)
            return h

        bd = _capi_train(dense_handle, self.PARAMS)
        bs = _capi_train(sparse_handle, self.PARAMS)
        sd = capi.LGBM_BoosterSaveModelToString(bd)
        ss = capi.LGBM_BoosterSaveModelToString(bs)
        assert sd == ss, "CSR-native model text differs from densified"
        pd = capi.LGBM_BoosterPredictForMat(bd, X[:300])
        csr = sp_sparse.csr_matrix(X[:300])
        ps = capi.LGBM_BoosterPredictForCSR(
            bs, csr.indptr, 0, csr.indices, csr.data, 0,
            len(csr.indptr), csr.nnz, X.shape[1])
        np.testing.assert_array_equal(np.asarray(pd), np.asarray(ps))

    def test_csr_roundtrip(self):
        from lightgbm_tpu import capi
        X, _, y = _sparse_task(n=1800, f=16, seed=1)
        csr = sp_sparse.csr_matrix(X)

        def mk(params):
            return capi.LGBM_DatasetCreateFromCSR(
                csr.indptr, 0, csr.indices, csr.data, 0,
                len(csr.indptr), csr.nnz, X.shape[1],
                parameters=params)

        self._roundtrip(mk, X, y)

    def test_csc_roundtrip(self):
        from lightgbm_tpu import capi
        X, _, y = _sparse_task(n=1500, f=12, seed=2)
        csc = sp_sparse.csc_matrix(X)

        def mk(params):
            return capi.LGBM_DatasetCreateFromCSC(
                csc.indptr, 0, csc.indices, csc.data, 0,
                len(csc.indptr), csc.nnz, X.shape[0],
                parameters=params)

        self._roundtrip(mk, X, y)

    def test_scipy_dataset_parity(self):
        import lightgbm_tpu as lgb
        X, _, y = _sparse_task(n=1500, f=14, seed=4)
        params = dict(TEST_PARAMS, objective="binary", verbosity=-1)
        bd = lgb.train(params, lgb.Dataset(X.copy(), label=y),
                       num_boost_round=10)
        bs = lgb.train(params,
                       lgb.Dataset(sp_sparse.csr_matrix(X), label=y),
                       num_boost_round=10)
        assert bd.model_to_string() == bs.model_to_string()
        np.testing.assert_array_equal(
            bd.predict(X[:200]),
            bs.predict(sp_sparse.csr_matrix(X[:200])))

    def test_categorical_parity(self):
        X, sm, y = _sparse_task(n=1800, f=12, cat_col=4, seed=5)
        params = dict(TEST_PARAMS, objective="binary")
        gd = fit_gbdt(X, y, params, num_round=10)
        # fit_gbdt passes categorical through construct: do it directly
        cfg = Config().set(dict(TEST_PARAMS, objective="binary"))

        def train(Xin):
            from lightgbm_tpu.metrics import create_metrics
            from lightgbm_tpu.models.gbdt import GBDT
            from lightgbm_tpu.objectives import create_objective
            ds = TpuDataset(cfg.copy()).construct_from_matrix(
                Xin, Metadata(label=y), categorical=[4])
            obj = create_objective("binary", cfg)
            obj.init(ds.metadata, ds.num_data)
            g = GBDT()
            g.init(cfg.copy(), ds, obj, [])
            for _ in range(10):
                g.train_one_iter()
            return g

        g0, g1 = train(X.copy()), train(sm)
        assert g0.model_to_string() == g1.model_to_string()
        np.testing.assert_array_equal(g0.predict_raw(X[:200]),
                                      g1.predict_raw(X[:200]))
        del gd

    def test_efb_on_sparse_parity(self):
        # mutually exclusive columns bundle; the sparse route must take
        # the host-bins path and produce the identical bundled dataset
        r = np.random.default_rng(7)
        n = 1500
        owner = r.integers(0, 6, n)
        X = np.zeros((n, 6))
        X[np.arange(n), owner] = r.normal(size=n) + 3.0
        y = (X.sum(1) + 0.2 * r.normal(size=n) > 3.0).astype(np.float32)
        sm = SparseMatrix.from_scipy(sp_sparse.csr_matrix(X))
        params = dict(TEST_PARAMS, objective="binary")
        g0 = fit_gbdt(X.copy(), y, params, num_round=10)
        g1 = fit_gbdt(sm, y, params, num_round=10)
        assert g0.train_data.bundles is not None
        assert g1.train_data.bundles is not None
        assert g0.train_data.bundles == g1.train_data.bundles
        assert g0.model_to_string() == g1.model_to_string()

    def test_valid_set_sparse(self):
        X, sm, y = _sparse_task(n=1200, f=10, seed=6)
        Xv, smv, yv = _sparse_task(n=400, f=10, seed=16)
        params = dict(TEST_PARAMS, objective="binary", metric="auc")
        g0 = fit_gbdt(X.copy(), y, params, num_round=8,
                      valid=(Xv.copy(), yv))
        g1 = fit_gbdt(X.copy(), y, params, num_round=8, valid=(smv, yv))
        e0 = g0.get_eval_at(1)
        e1 = g1.get_eval_at(1)
        assert e0 == e1


# ---------------------------------------------------------------------------
# Streamed sparse device ingest
# ---------------------------------------------------------------------------

class TestDeviceIngest:
    def test_device_bins_bit_identical(self):
        X, sm, y = _sparse_task(n=2100, f=10, cat_col=4, nan_frac=0.02,
                                tiny_col=6, seed=8)
        base = dict(TEST_PARAMS, enable_bundle=False)
        ds0 = TpuDataset(Config().set(dict(base, tpu_ingest=0))) \
            .construct_from_matrix(sm, Metadata(label=y),
                                   categorical=[4])
        ds1 = TpuDataset(Config().set(dict(
            base, tpu_ingest=1, tpu_ingest_chunk_rows=257,
            tpu_sparse=1))).construct_from_matrix(
            sm, Metadata(label=y), categorical=[4])
        assert ds1.bins_t_dev is not None, "sparse device ingest off"
        np.testing.assert_array_equal(
            ds0.bins, np.ascontiguousarray(np.asarray(ds1.bins_t_dev).T))
        # the retained coordinate planes rebuild the same matrix
        codes, feat, rows = [np.asarray(a) for a in ds1.sparse_coords]
        keep = feat < len(ds1.mappers)
        rb = np.empty_like(ds0.bins)
        rb[:] = zero_bins(ds1.mappers)[None, :].astype(rb.dtype)
        rb[rows[keep], feat[keep]] = codes[keep].astype(rb.dtype)
        np.testing.assert_array_equal(rb, ds0.bins)

    def test_training_parity_ingest_on_off(self):
        X, sm, y = _sparse_task(n=1600, f=12, seed=10)
        params = dict(TEST_PARAMS, objective="binary",
                      enable_bundle=False)
        g0 = fit_gbdt(sm, y, dict(params, tpu_ingest=0), num_round=8)
        g1 = fit_gbdt(sm, y, dict(params, tpu_ingest=1,
                                  tpu_ingest_chunk_rows=300),
                      num_round=8)
        assert _trees(g0) == _trees(g1)


# ---------------------------------------------------------------------------
# Sparse histogram tier
# ---------------------------------------------------------------------------

class TestSparseHistTier:
    def test_wave_histogram_sparse_vs_dense_oracle(self):
        import jax.numpy as jnp

        from lightgbm_tpu.ops.hist_wave import (wave_histogram_sparse,
                                                wave_histogram_xla)
        r = np.random.default_rng(2)
        N, F, B, W = 700, 6, 16, 5
        zb = r.integers(0, B, F).astype(np.int32)
        bins = np.empty((N, F), np.int32)
        bins[:] = zb[None, :]
        mask = r.uniform(size=(N, F)) < 0.1
        rows, feats = np.nonzero(mask)
        codes = r.integers(0, B, mask.sum()).astype(np.int32)
        bins[rows, feats] = codes
        leaf = r.integers(-1, 7, N).astype(np.int32)    # -1 = oob
        wl = np.array([0, 3, 5, -1, 2], np.int32)
        pad = 37                                        # sentinels
        sp = (jnp.asarray(np.concatenate([codes,
                                          np.zeros(pad, np.int32)])),
              jnp.asarray(np.concatenate([feats.astype(np.int32),
                                          np.full(pad, F, np.int32)])),
              jnp.asarray(np.concatenate([rows.astype(np.int32),
                                          np.zeros(pad, np.int32)])),
              jnp.asarray(zb))
        gi = r.integers(-127, 128, N).astype(np.float32)
        hi = r.integers(0, 128, N).astype(np.float32)
        dense = wave_histogram_xla(
            jnp.asarray(bins.T), jnp.asarray(gi), jnp.asarray(hi),
            jnp.asarray(leaf), jnp.asarray(wl), num_bins=B)
        sparse = wave_histogram_sparse(
            sp, jnp.asarray(gi), jnp.asarray(hi), jnp.asarray(leaf),
            jnp.asarray(wl), num_bins=B, num_features=F)
        # integer-valued accumulation: BIT-equal
        np.testing.assert_array_equal(np.asarray(dense),
                                      np.asarray(sparse))
        # dequantization multiplies identically to the dense path
        sc = (0.031, 0.017)
        s2 = wave_histogram_sparse(
            sp, jnp.asarray(gi), jnp.asarray(hi), jnp.asarray(leaf),
            jnp.asarray(wl), num_bins=B, num_features=F, gh_scale=sc)
        np.testing.assert_array_equal(
            np.asarray(dense) * np.array([sc[0], sc[1], 1.0],
                                         np.float32),
            np.asarray(s2))
        # f32 gradients: equal up to completion reassociation
        gf = r.normal(size=N).astype(np.float32)
        hf = r.uniform(0.1, 1, N).astype(np.float32)
        df = wave_histogram_xla(
            jnp.asarray(bins.T), jnp.asarray(gf), jnp.asarray(hf),
            jnp.asarray(leaf), jnp.asarray(wl), num_bins=B)
        sf = wave_histogram_sparse(
            sp, jnp.asarray(gf), jnp.asarray(hf), jnp.asarray(leaf),
            jnp.asarray(wl), num_bins=B, num_features=F)
        np.testing.assert_allclose(np.asarray(df), np.asarray(sf),
                                   rtol=1e-5, atol=1e-4)

    def _tier_pair(self, sm, y, tpu_sparse, rounds=8, **extra):
        params = dict(TEST_PARAMS, objective="binary",
                      enable_bundle=False, tpu_quantized_hist=True,
                      tpu_count_proxy=0, tpu_sparse=tpu_sparse)
        params.update(extra)
        return fit_gbdt(sm, y, params, num_round=rounds)

    def test_quantized_bit_parity(self):
        # integer accumulation is order-free: the sparse tier's trees
        # are BIT-equal to the dense tier's on the same CSR input
        X, sm, y = _sparse_task(n=2200, f=20, cat_col=5, seed=11,
                                density=0.03)
        g0 = self._tier_pair(sm, y, 0)
        g1 = self._tier_pair(sm, y, 1)
        assert not g0._grower_cfg.sparse_hist
        assert g1._grower_cfg.sparse_hist
        assert _trees(g0) == _trees(g1)
        np.testing.assert_array_equal(g0.predict_raw(X[:200]),
                                      g1.predict_raw(X[:200]))

    def test_auto_rule(self):
        from lightgbm_tpu.ops.autotune import tune_hist_tier
        kw = dict(nnz=100, F=10, B=64, W=0)
        assert tune_hist_tier(requested=1, density=0.5, quant=False,
                              **kw)
        assert not tune_hist_tier(requested=0, density=0.001,
                                  quant=True, **kw)
        # auto: exactness-first (quantized only) + density ceiling
        assert tune_hist_tier(requested=-1, density=0.01, quant=True,
                              **kw)
        assert not tune_hist_tier(requested=-1, density=0.01,
                                  quant=False, **kw)
        assert not tune_hist_tier(requested=-1, density=0.5,
                                  quant=True, **kw)

    def test_f32_forced_tier_trains_close(self):
        """f32 sparse tier vs dense tier: close, not bitwise — the
        default-bin completion reassociates f32 sums (Design.md §5f).
        min_gain_to_split gates NOISE-level gains out of the
        comparison: a leaf whose true best gain is 0 computes it as
        ±1e-6 of rounding residue, and ``gain > 0`` then admits the
        split in one tier and not the other (seen under jax 0.9: the
        dense tier split a 44-row leaf at gain 1.9e-6, spent a leaf
        slot on it, and the trees diverged — 0.09 apart in raw score).
        With the gate, the tolerance the tier promises holds."""
        X, sm, y = _sparse_task(n=1500, f=12, seed=12)
        params = dict(TEST_PARAMS, objective="binary",
                      enable_bundle=False, min_gain_to_split=1e-3)
        g0 = fit_gbdt(sm, y, dict(params, tpu_sparse=0), num_round=6)
        g1 = fit_gbdt(sm, y, dict(params, tpu_sparse=1), num_round=6)
        assert g1._grower_cfg.sparse_hist
        np.testing.assert_allclose(g0.predict_raw(X[:300]),
                                   g1.predict_raw(X[:300]),
                                   rtol=1e-4, atol=1e-5)

    def test_step_cache_reuse_same_geometry(self):
        # the sparse planes ride the step as TRACED arguments: a second
        # same-geometry sparse booster is a registry hit serving ITS
        # OWN coordinates (the sliding-window pattern)
        from lightgbm_tpu.ops import step_cache
        r = np.random.default_rng(13)
        X1, sm1, y1 = _sparse_task(n=1500, f=12, seed=13)
        X2 = np.where(r.uniform(size=X1.shape) < 0.05,
                      r.normal(size=X1.shape), 0.0)
        sm2 = SparseMatrix.from_scipy(sp_sparse.csr_matrix(X2))
        y2 = (X2.sum(1) > 0).astype(np.float32)
        s0 = step_cache.stats()
        g1 = self._tier_pair(sm1, y1, 1, rounds=4)
        mid = step_cache.stats()
        g2 = self._tier_pair(sm2, y2, 1, rounds=4)
        s1 = step_cache.stats()
        assert g1._grower_cfg.sparse_hist and g2._grower_cfg.sparse_hist
        assert s1["hits"] > mid["hits"], \
            "same-geometry sparse booster missed the step registry"
        # the hit served booster 2's OWN data, not booster 1's
        assert _trees(g1) != _trees(g2)
        del s0

    def test_tier_geometry_key_distinguishes(self):
        # a sparse-tier booster and a dense-tier booster of the same
        # shape must NOT share a compiled step
        X, sm, y = _sparse_task(n=1500, f=12, seed=14)
        g0 = self._tier_pair(sm, y, 0, rounds=3)
        g1 = self._tier_pair(sm, y, 1, rounds=3)
        k0 = g0._step_geometry_key(False, g0.objective, None, None,
                                   g0._meta)
        k1 = g1._step_geometry_key(False, g1.objective, None, None,
                                   g1._meta)
        assert k0 != k1


# ---------------------------------------------------------------------------
# O(nnz) memory + route decision
# ---------------------------------------------------------------------------

class TestMemoryAndRoute:
    def test_o_nnz_no_dense_materialization(self, monkeypatch):
        """A ~1%-density workload trains end to end without EVER
        allocating a dense [N, F] matrix: to_dense is banned outright,
        and the python-side allocation peak during construct+train
        stays under even a UINT8 [N, F] (the float64 cliff is 8x
        that)."""
        import tracemalloc

        r = np.random.default_rng(15)
        n, f = 60_000, 100                   # float64 [N, F] = 48 MB
        k = max(1, int(f * 0.01))
        rows = np.repeat(np.arange(n, dtype=np.int64), k)
        cols = r.integers(0, f, size=n * k).astype(np.int64)
        key = rows * f + cols
        _, first = np.unique(key, return_index=True)
        rows, cols = rows[first], cols[first]
        vals = r.normal(size=len(rows)) + 2.0
        indptr = np.concatenate(
            [[0], np.cumsum(np.bincount(rows, minlength=n))])
        sm = SparseMatrix(vals, cols, indptr, (n, f))
        y = np.zeros(n, np.float32)
        np.add.at(y, rows, vals.astype(np.float32))
        y = (y > y.mean()).astype(np.float32)
        assert sm.density <= 0.0105

        def boom(*a, **kw):
            raise AssertionError("dense [N, F] materialized on the "
                                 "CSR-native route")

        monkeypatch.setattr(SparseMatrix, "to_dense", boom)
        from lightgbm_tpu.obs import registry as obs
        routed0 = obs.counter("sparse/route_sparse").value
        densified0 = obs.counter("sparse/route_dense").value
        params = dict(TEST_PARAMS, objective="binary",
                      enable_bundle=False, tpu_ingest=1)
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        g = fit_gbdt(sm, y, params, num_round=3)
        peak = tracemalloc.get_traced_memory()[1] - base
        tracemalloc.stop()
        assert obs.counter("sparse/route_sparse").value == routed0 + 1
        assert obs.counter("sparse/route_dense").value == densified0
        # numpy/python peak far below the float64 cliff (8 * n * f):
        # the bound leaves room for trace/compile bookkeeping but any
        # [N, F] float64 (or even float32) materialization blows it
        assert peak < n * f * 4, \
            f"python allocation peak {peak} suggests densification"
        assert g.current_iteration == 3

    def test_route_threshold_and_fallback(self):
        X, _, y = _sparse_task(n=800, f=8, density=0.6, seed=17)
        sm = SparseMatrix.from_scipy(sp_sparse.csr_matrix(X))
        from lightgbm_tpu.obs import registry as obs
        cfg = Config().set(dict(TEST_PARAMS))
        assert not route_sparse(cfg, sm)     # too dense for the route
        d0 = obs.counter("sparse/route_dense").value
        ds = TpuDataset(cfg).construct_from_matrix(sm, Metadata(label=y))
        assert obs.counter("sparse/route_dense").value == d0 + 1
        assert ds.sparse_coords is None
        # identical result to the explicitly-densified construction
        ds2 = TpuDataset(Config().set(dict(TEST_PARAMS))) \
            .construct_from_matrix(X, Metadata(label=y))
        np.testing.assert_array_equal(ds.bins, ds2.bins)
        # is_enable_sparse=false refuses the CSR route regardless
        cfg2 = Config().set(dict(TEST_PARAMS, is_enable_sparse=False))
        _, smn, _ = _sparse_task(n=500, f=8, density=0.02, seed=18)
        assert not route_sparse(cfg2, smn)

    def test_config_knob_validation(self):
        cfg = Config().set({"sparse_threshold": 1.7})
        assert cfg.sparse_threshold == 0.8
        cfg = Config().set({"tpu_sparse": 5})
        assert cfg.tpu_sparse == -1
        cfg = Config().set({"sparse_threshold": 0.5, "tpu_sparse": 1})
        assert cfg.sparse_threshold == 0.5 and cfg.tpu_sparse == 1

    def test_dense_cliff_warning_unified(self):
        from lightgbm_tpu import capi
        from lightgbm_tpu.utils import log as tlog
        seen = []
        old = tlog._callback
        old_level = tlog.get_level()
        tlog.set_callback(seen.append)
        tlog.set_level(tlog.LogLevel.INFO)   # a verbosity=-1 test may
        try:                                 # have lowered the level
            warn_dense_cliff(600_000_000, 2_000, 12_345)
            assert any("GiB" in m for m in seen), seen
            seen.clear()
            warn_dense_cliff(100, 10, 50)     # tiny: no warning
            assert not seen
        finally:
            tlog.set_callback(old)
            tlog.set_level(old_level)
        # both explicit densify helpers route through the one guard
        calls = []
        orig = capi.warn_dense_cliff
        try:
            capi.warn_dense_cliff = \
                lambda *a, **k: calls.append(a)
            capi._csr_to_dense([0, 1], [0], [1.0], 3)
            capi._csc_to_dense([0, 1, 1, 1], [0], [1.0], 2, 3)
        finally:
            capi.warn_dense_cliff = orig
        assert len(calls) == 2

    def test_predict_chunked_paths(self, monkeypatch):
        import lightgbm_tpu.models.gbdt as gbdt_mod
        from lightgbm_tpu.io import sparse as sparse_mod
        X, sm, y = _sparse_task(n=900, f=10, seed=19)
        params = dict(TEST_PARAMS, objective="binary")
        g = fit_gbdt(X.copy(), y, params, num_round=8)
        monkeypatch.setattr(sparse_mod, "PREDICT_CHUNK_ROWS", 128)
        np.testing.assert_array_equal(g.predict_raw(X), g.predict_raw(sm))
        np.testing.assert_array_equal(g.predict(X), g.predict(sm))
        np.testing.assert_array_equal(g.predict_leaf_index(X),
                                      g.predict_leaf_index(sm))
        np.testing.assert_array_equal(g.predict_contrib(X),
                                      g.predict_contrib(sm))

    def test_predict_during_construct_thread_safety(self):
        # cheap sanity: chunked sparse predict from a second thread
        # while the main thread trains another booster
        X, sm, y = _sparse_task(n=900, f=8, seed=20)
        params = dict(TEST_PARAMS, objective="binary")
        g = fit_gbdt(X.copy(), y, params, num_round=6)
        want = g.predict_raw(X[:256])
        errs = []

        def hammer():
            try:
                for _ in range(3):
                    got = g.predict_raw(
                        SparseMatrix.from_scipy(
                            sp_sparse.csr_matrix(X[:256])))
                    np.testing.assert_array_equal(got, want)
            except Exception as e:           # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=hammer)
        t.start()
        fit_gbdt(sm, y, params, num_round=3)
        t.join(timeout=60)
        assert not errs, errs
