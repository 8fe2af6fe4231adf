"""Streamed device ingest (io/ingest.py): bit-exact parity against the
host binner, pipeline routing, and determinism.

The host ``BinMapper.value_to_bin`` / ``TpuDataset.bin_rows`` path is
the semantic oracle; every test forces ``tpu_ingest=1`` so the device
kernels run on the CPU backend (the same code path a real TPU takes
under the default ``tpu_ingest=-1`` auto gate).
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata, TpuDataset

pytestmark = pytest.mark.ingest


def _mk(params, ingest, chunk=0):
    full = {"objective": "regression", "max_bin": 63,
            "min_data_in_leaf": 20, "tpu_ingest": ingest,
            "tpu_ingest_chunk_rows": chunk}
    full.update(params)
    return Config().set(full)


def _pair(X, y, params=None, categorical=(), chunk=257):
    """Construct the same dataset through the host binner and the
    device pipeline; returns (host_ds, dev_ds)."""
    params = params or {}
    ds0 = TpuDataset(_mk(params, 0)).construct_from_matrix(
        np.asarray(X), Metadata(label=y), categorical=categorical)
    ds1 = TpuDataset(_mk(params, 1, chunk)).construct_from_matrix(
        np.asarray(X), Metadata(label=y), categorical=categorical)
    return ds0, ds1


def _counters():
    from lightgbm_tpu.obs import registry as obs
    return dict(obs.default_registry().counter_items())


def _moved(before):
    """-> name -> how far that counter moved since ``before``."""
    after = _counters()
    return lambda name: after.get(name, 0) - before.get(name, 0)


def _dev_bins(ds):
    assert ds.bins_t_dev is not None, "device ingest did not engage"
    return np.ascontiguousarray(np.asarray(ds.bins_t_dev).T)


def _nasty_matrix(n=1601, seed=0):
    """Every BinMapper edge case in one matrix: plain continuous, NaN
    columns, zero-heavy columns, the negative-zero / kZeroThreshold
    crossing, a categorical column and a nibble-tier (<=16 bins)
    column."""
    r = np.random.default_rng(seed)
    zero_cross = np.concatenate([
        [-0.0, 0.0, 1e-36, -1e-36, 5e-324, -5e-324, 1e-35, -1e-35,
         np.nextafter(1e-35, 1), np.nextafter(-1e-35, -1)],
        r.normal(size=n - 10) * 1e-30])
    return np.column_stack([
        r.normal(size=n),
        np.where(r.uniform(size=n) < 0.15, np.nan, r.normal(size=n)),
        np.where(r.uniform(size=n) < 0.5, 0.0, r.normal(size=n)),
        r.integers(0, 9, n).astype(np.float64),      # categorical
        zero_cross,
        r.integers(0, 3, n).astype(np.float64),      # <=16-bin tier
    ])


class TestBinningParity:
    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    def test_nasty_matrix_bit_identical(self, dtype):
        X = _nasty_matrix().astype(dtype)
        y = np.zeros(len(X), np.float32)
        ds0, ds1 = _pair(X, y, categorical=[3])
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))

    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    def test_zero_as_missing(self, dtype):
        X = _nasty_matrix(seed=1).astype(dtype)
        y = np.zeros(len(X), np.float32)
        ds0, ds1 = _pair(X, y, params={"zero_as_missing": True})
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))

    def test_int32_tier(self):
        """Past 256 bounds the float64 route keeps the search, on the
        device's keys."""
        r = np.random.default_rng(2)
        X = r.normal(size=(1500, 3))
        y = np.zeros(1500, np.float32)
        before = _counters()
        ds0, ds1 = _pair(X, y, params={"max_bin": 500,
                                       "min_data_in_bin": 1})
        assert ds1.bins_t_dev.dtype == np.int32
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))
        moved = _moved(before)
        assert moved("ingest/rows_device") == 1500
        assert moved("ingest/rows_counted") == 0

    def test_values_at_bin_boundaries(self):
        """Adversarial: values placed exactly AT each bound and one
        ulp either side — the cases a rounded comparison would get
        wrong."""
        r = np.random.default_rng(3)
        base = r.normal(size=1200)
        ds = TpuDataset(_mk({}, 0)).construct_from_matrix(
            base[:, None], Metadata(label=np.zeros(1200, np.float32)))
        b = ds.mappers[0].bin_upper_bound[:-1]
        adv = np.concatenate([b, np.nextafter(b, -np.inf),
                              np.nextafter(b, np.inf), base])
        y = np.zeros(len(adv), np.float32)
        ds0, ds1 = _pair(adv[:, None], y)
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))

    def test_unseen_and_negative_categories(self):
        n = 1200
        r = np.random.default_rng(4)
        col = r.integers(0, 5, n).astype(np.float64)
        col[::7] = 99.0          # unseen at sample time? (still seen)
        col[::11] = np.nan
        X = np.column_stack([col, r.normal(size=n)])
        y = np.zeros(n, np.float32)
        ds0, ds1 = _pair(X, y, categorical=[0])
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))

    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    def test_multi_chunk_tail(self, dtype):
        """Chunking must be invisible: odd row count, chunk smaller
        than the matrix, tail chunk partially filled."""
        r = np.random.default_rng(5)
        X = r.normal(size=(999, 4)).astype(dtype)
        y = np.zeros(999, np.float32)
        ds0, ds1 = _pair(X, y, chunk=123)
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))

    def test_count_and_search_give_the_same_float64_bins(self,
                                                         monkeypatch):
        """The count by compares in the two key planes' order and the
        gather search it replaces bin the same float64 values alike:
        the search is forced by a bound of 0 (no knob)."""
        import lightgbm_tpu.io.ingest as ingest
        X = _nasty_matrix(seed=21)
        y = np.zeros(len(X), np.float32)
        before = _counters()
        _, counted = _pair(X, y, categorical=[3])
        assert _moved(before)("ingest/rows_counted") == len(X)
        monkeypatch.setattr(ingest, "_COUNT_MAX_BOUNDS", 0)
        before = _counters()
        host, searched = _pair(X, y, categorical=[3])
        assert _moved(before)("ingest/rows_counted") == 0
        np.testing.assert_array_equal(_dev_bins(counted),
                                      _dev_bins(searched))
        np.testing.assert_array_equal(host.bins, _dev_bins(searched))

    def test_float64_rows_cross_as_their_raw_words(self):
        """Every source column numerical, used and in order: a float64
        chunk crosses as the matrix's own 8 B a value (no key planes,
        no NaN mask), and the count bins every row."""
        r = np.random.default_rng(22)
        n, f, chunk = 1000, 5, 256
        X = r.normal(size=(n, f))
        X[::13, 2] = np.nan
        y = np.zeros(n, np.float32)
        before = _counters()
        ds0, ds1 = _pair(X, y, chunk=chunk)
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))
        moved = _moved(before)
        assert moved("ingest/h2d_bytes") == -(-n // chunk) * chunk * f * 8
        assert moved("ingest/rows_counted") == moved(
            "ingest/rows_device") == n
        assert moved("ingest/f32_rows") == 0


class TestSampledBoundaries:
    def test_sampled_boundaries_deterministic(self):
        """bin_construct_sample_cnt smaller than N: two constructions
        with the same data_random_seed must produce identical
        boundaries (the reference's deterministic sampled
        ConstructFromSampleData)."""
        r = np.random.default_rng(6)
        X = r.normal(size=(8000, 3))
        y = np.zeros(8000, np.float32)
        p = {"bin_construct_sample_cnt": 1500}
        a = TpuDataset(_mk(p, 0)).construct_from_matrix(
            X, Metadata(label=y))
        b = TpuDataset(_mk(p, 0)).construct_from_matrix(
            X, Metadata(label=y))
        for ma, mb in zip(a.mappers, b.mappers):
            np.testing.assert_array_equal(ma.bin_upper_bound,
                                          mb.bin_upper_bound)
            assert ma.num_bin == mb.num_bin

    def test_sampled_vs_full_same_mapping_contract(self):
        """Sampled and full boundary search agree when the budget
        covers every row — and the streamed path bins IDENTICALLY for
        either mapper set (boundaries in, bins out)."""
        r = np.random.default_rng(7)
        X = r.normal(size=(2500, 3))
        y = np.zeros(2500, np.float32)
        full = TpuDataset(_mk({"bin_construct_sample_cnt": 2500}, 0)) \
            .construct_from_matrix(X, Metadata(label=y))
        samp = TpuDataset(_mk({"bin_construct_sample_cnt": 2500}, 1)) \
            .construct_from_matrix(X, Metadata(label=y))
        for ma, mb in zip(full.mappers, samp.mappers):
            np.testing.assert_array_equal(ma.bin_upper_bound,
                                          mb.bin_upper_bound)
        np.testing.assert_array_equal(full.bins, _dev_bins(samp))


class TestPipelineRoutes:
    def test_training_same_trees(self):
        """Fixed-seed end-to-end run: tpu_ingest on/off grow identical
        trees (the acceptance bar for the streamed path)."""
        import sys, os
        sys.path.insert(0, os.path.dirname(__file__))
        from conftest import fit_gbdt, make_binary
        # default shapes on purpose: the grower compiled for other
        # tests' (1280-row, TEST_PARAMS) datasets is reused in-process
        X, y = make_binary()

        def trees(model_string):
            return model_string.split("parameters:")[0]

        g0 = fit_gbdt(X, y, {"objective": "binary", "tpu_ingest": 0},
                      num_round=8)
        g1 = fit_gbdt(X, y, {"objective": "binary", "tpu_ingest": 1,
                             "tpu_ingest_chunk_rows": 300},
                      num_round=8)
        assert trees(g0.model_to_string()) == trees(g1.model_to_string())

    def test_create_valid_streams_and_never_rederives(self, monkeypatch):
        """create_valid must take the streamed path AND never re-derive
        mappers — find_column_mappers is poisoned while the valid set
        is constructed."""
        import lightgbm_tpu.io.dataset as dsmod
        r = np.random.default_rng(8)
        X = r.normal(size=(1000, 4))
        y = np.zeros(1000, np.float32)
        ds = TpuDataset(_mk({}, 1, 300)).construct_from_matrix(
            X, Metadata(label=y))
        host_ref = TpuDataset(_mk({}, 0)).construct_from_matrix(
            X, Metadata(label=y))

        def boom(*a, **k):
            raise AssertionError("create_valid re-derived mappers")

        monkeypatch.setattr(dsmod, "find_column_mappers", boom)
        Xv = r.normal(size=(500, 4))
        vd = ds.create_valid(Xv, Metadata(label=np.zeros(500, np.float32)))
        assert vd.mappers is ds.mappers
        assert vd.bins_t_dev is not None
        vd_host = host_ref.create_valid(
            Xv, Metadata(label=np.zeros(500, np.float32)))
        np.testing.assert_array_equal(vd_host.bins, _dev_bins(vd))

    def test_efb_data_falls_back_identically(self):
        """Data EFB actually bundles must take the host path and end
        bit-identical to tpu_ingest=0 (bundling decision and bundled
        matrix included)."""
        r = np.random.default_rng(9)
        n = 2000
        which = r.integers(0, 3, n)
        X = np.zeros((n, 4))
        for j in range(3):
            X[which == j, j] = r.uniform(1, 5, (which == j).sum())
        X[:, 3] = r.normal(size=n)
        y = np.zeros(n, np.float32)
        ds0, ds1 = _pair(X, y, params={"max_bin": 31})
        assert ds1.bins_t_dev is None          # host fallback
        assert ds0.bundles == ds1.bundles and ds0.bundles is not None
        np.testing.assert_array_equal(ds0.bundled_bins, ds1.bundled_bins)
        np.testing.assert_array_equal(ds0.bins, ds1.bins)

    def test_two_round_loader_streams(self, tmp_path):
        r = np.random.default_rng(10)
        n = 1100
        X = r.normal(size=(n, 4))
        X[::9, 1] = np.nan
        y = (X[:, 0] > 0).astype(int)
        path = str(tmp_path / "d.csv")
        with open(path, "w") as fh:
            for i in range(n):
                fh.write(",".join([str(y[i])]
                                  + [repr(float(v)) for v in X[i]])
                         + "\n")
        from lightgbm_tpu.io.loader import DatasetLoader

        def load(ingest, ref=None):
            cfg = _mk({"objective": "binary", "two_round": True},
                      ingest, 300 if ingest else 0)
            return DatasetLoader(cfg).load_from_file(path, reference=ref)

        ds0, ds1 = load(0), load(1)
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))
        np.testing.assert_array_equal(ds0.metadata.label,
                                      ds1.metadata.label)
        v0, v1 = load(0, ref=ds0), load(1, ref=ds1)
        assert v1.mappers is ds1.mappers
        np.testing.assert_array_equal(v0.bins, _dev_bins(v1))

    def test_save_binary_roundtrip_from_device(self, tmp_path):
        """save_binary on a device-ingested set downloads once and
        round-trips bit-exactly (nibble packing included)."""
        X = _nasty_matrix(n=1001, seed=11)
        y = np.zeros(1001, np.float32)
        ds0, ds1 = _pair(X, y, categorical=[3])
        fn = str(tmp_path / "d.bin")
        ds1.save_binary(fn)
        loaded = TpuDataset.load_binary(fn, _mk({}, 0))
        np.testing.assert_array_equal(ds0.bins, loaded.bins)


class TestKeyOrder:
    def test_sortable_keys_match_float_order(self):
        """The uint32 key planes order exactly like float comparisons
        (NaN-free, -0.0 normalized)."""
        from lightgbm_tpu.io.ingest import _key32_host, _keys64_host
        r = np.random.default_rng(12)
        v = np.concatenate([
            r.normal(size=500) * 10.0 ** r.integers(-300, 300, 500),
            [0.0, 5e-324, -5e-324, np.inf, -np.inf, 1e-35, -1e-35]])
        v = v + 0.0                      # -0.0 -> +0.0, as the binner
        order = np.argsort(v, kind="stable")
        h, lo = _keys64_host(v)
        key_order = np.argsort(h.astype(np.uint64) << np.uint64(32)
                               | lo.astype(np.uint64), kind="stable")
        np.testing.assert_array_equal(np.sort(v), v[key_order])
        np.testing.assert_array_equal(v[order], v[key_order])
        with np.errstate(over="ignore"):    # huge f64 -> f32 inf is fine
            v32 = (v.astype(np.float32) + np.float32(0.0))
        k32 = _key32_host(v32)
        np.testing.assert_array_equal(np.sort(v32), v32[np.argsort(k32)])

    def test_device_keys_from_raw_words_are_the_hosts(self):
        """``_keys64_dev`` on the raw words of hostile float64 values
        gives ``_keys64_host``'s planes of ``where(isnan, 0, v) + 0.0``
        bit for bit, and its NaN mask is ``np.isnan``."""
        import jax
        from lightgbm_tpu.io.ingest import _keys64_dev, _keys64_host
        fmax, tiny = np.finfo(np.float64).max, np.finfo(np.float64).tiny
        nans = np.array([0x7FF8000000000000, 0xFFF8000000000000,
                         0x7FF0000000000001, 0xFFF0000000000001,
                         0x7FFFFFFFFFFFFFFF, 0xFFFFFFFFFFFFFFFF,
                         0x7FF0000100000000, 0x7FF4000000000000,
                         0xFFF00000FFFFFFFF],
                        np.uint64).view(np.float64)
        r = np.random.default_rng(23)
        v = np.concatenate([
            [0.0, -0.0, np.inf, -np.inf, fmax, -fmax, tiny, -tiny,
             5e-324, -5e-324, tiny / 3, -tiny / 3, 1.0, -1.0,
             np.nextafter(tiny, 0), -np.nextafter(tiny, 0)],
            nans, r.normal(size=71) * 10.0 ** r.integers(-300, 300, 71)])
        X = v.reshape(-1, 3)                        # [C, Fn], 3 features
        hi, lo, nanm = jax.jit(_keys64_dev)(X.view(np.uint32))
        want = np.where(np.isnan(X), 0.0, X) + 0.0
        wh, wl = _keys64_host(want)
        np.testing.assert_array_equal(np.asarray(hi), wh.T)
        np.testing.assert_array_equal(np.asarray(lo), wl.T)
        np.testing.assert_array_equal(np.asarray(nanm), np.isnan(X).T)

    def test_floor32_is_largest_f32_below(self):
        from lightgbm_tpu.io.ingest import _floor32
        r = np.random.default_rng(13)
        b = r.normal(size=1000) * 10.0 ** r.integers(-30, 30, 1000)
        f = _floor32(b)
        assert (f.astype(np.float64) <= b).all()
        up = np.nextafter(f, np.float32(np.inf))
        assert (up.astype(np.float64) > b).all()


@pytest.mark.slow
class TestIngestThroughput:
    def test_large_ingest_matches_host(self):
        """HIGGS-shaped slab (scaled down): the streamed pipeline over
        many chunks stays bit-identical and produces a usable
        dataset."""
        r = np.random.default_rng(14)
        X = r.normal(size=(400_000, 28)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        ds0, ds1 = _pair(X, y, chunk=1 << 16)
        np.testing.assert_array_equal(ds0.bins, _dev_bins(ds1))
