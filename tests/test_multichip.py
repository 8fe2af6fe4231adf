"""End-to-end sharded multi-chip training (pytest -m multichip).

Runs on the 8-device virtual CPU mesh (conftest): the same code path a
real v5e-8 takes, minus the Pallas kernels (interpret/XLA fallbacks).
Three properties anchor the distributed design (Design.md §7):

(a) sharded ingest is BIT-exact against single-device ingest — the
    round-robin chunk pipeline assembles the row-sharded [F, N] under
    the mesh's NamedSharding from the identical chunk kernel;
(b) data-parallel training with fully sharded iteration state (bins,
    scores, grad/hess, bagging mask) keeps same-seed serial parity —
    sharding is layout, never semantics;
(c) quantized training with the int32 quantized-histogram psum
    reproduces the single-chip quantized trees — the rounding hash is
    keyed by GLOBAL row index and the scales are global, so the wire
    format (int vs f32) and the shard count drop out of the model.
"""
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata, TpuDataset
from lightgbm_tpu.utils.device import get_devices

from conftest import fit_gbdt, make_binary

pytestmark = [
    pytest.mark.multichip,
    pytest.mark.skipif(len(get_devices()) < 2,
                       reason="needs multi-device mesh"),
]


@pytest.fixture(scope="module")
def serial_baseline():
    """One serial same-seed reference booster (every booster pays a
    full XLA compile on this backend, so the ingest-parity and
    sharded-state-parity tests share their serial half)."""
    X, y = make_binary(1280)
    g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc"},
                 num_round=5)
    return X, y, g


def _nasty_matrix(n=1601, seed=0):
    """The ingest parity matrix (tests/test_ingest.py): continuous,
    NaN, zero-heavy, the -0.0/kZeroThreshold crossing, categorical."""
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
    ])


def _ingest_ds(X, y, learner, categorical=(), chunk=97):
    cfg = Config().set({"objective": "regression", "max_bin": 63,
                        "min_data_in_leaf": 20, "tpu_ingest": 1,
                        "tpu_ingest_chunk_rows": chunk,
                        "tree_learner": learner})
    return TpuDataset(cfg).construct_from_matrix(
        np.asarray(X), Metadata(label=y), categorical=categorical)


class TestShardedIngest:
    """(a) row-sharded assembly under NamedSharding, bit-exact."""

    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    def test_nasty_matrix_bit_identical(self, dtype):
        X = _nasty_matrix().astype(dtype)
        y = np.zeros(len(X), np.float32)
        ds1 = _ingest_ds(X, y, "serial", categorical=[3])
        ds8 = _ingest_ds(X, y, "data", categorical=[3])
        assert ds1.bins_t_dev is not None and ds8.bins_t_dev is not None
        # the sharded matrix really is distributed over the mesh
        assert len(ds8.bins_t_dev.sharding.device_set) > 1
        n = ds1.num_data
        np.testing.assert_array_equal(
            np.asarray(ds1.bins_t_dev),
            np.asarray(ds8.bins_t_dev)[:, :n])
        # shard-equalizing pad columns are zero bins (what row padding
        # writes); shards are chunk-aligned to the largest power-of-two
        # unit u with n >= 4*D*u so the grower adopts the padding
        # (io/ingest.py bin_matrix_sharded)
        D = len(ds8.bins_t_dev.sharding.device_set)
        from lightgbm_tpu.ops.autotune import MAX_HIST_CHUNK
        u = 1
        while u * 2 <= MAX_HIST_CHUNK and n >= 4 * D * (u * 2):
            u *= 2
        S = -(-max(-(-n // D), 1) // u) * u
        assert ds8.bins_t_dev_pad == D * S - n
        assert (np.asarray(ds8.bins_t_dev)[:, n:] == 0).all()

    def test_matches_host_binner(self):
        X = _nasty_matrix(seed=3)
        y = np.zeros(len(X), np.float32)
        cfg = Config().set({"objective": "regression", "max_bin": 63,
                            "min_data_in_leaf": 20, "tpu_ingest": 0,
                            "tree_learner": "data"})
        host = TpuDataset(cfg).construct_from_matrix(
            X.copy(), Metadata(label=y), categorical=[3])
        dev = _ingest_ds(X, y, "data", categorical=[3])
        np.testing.assert_array_equal(
            host.bins,
            np.ascontiguousarray(
                np.asarray(dev.bins_t_dev)[:, :host.num_data].T))

    def test_sharded_ingest_trains_serial_parity(self, serial_baseline):
        """The sharded-ingest bins feed the sharded grower directly
        (no single-device staging) and the trees still match a fully
        host-binned serial run. (The baseline's tpu_ingest=-1 resolves
        to the host binner off-TPU — the same path as tpu_ingest=0.)"""
        X, y, gs = serial_baseline
        gd = fit_gbdt(X, y, {"objective": "binary", "metric": "auc",
                             "tree_learner": "data",
                             "tpu_ingest": 1}, num_round=5)
        assert gd._learner_mode == "data"
        assert gd.train_data.bins_t_dev is not None
        np.testing.assert_allclose(
            gd.predict_raw(X[:200]), gs.predict_raw(X[:200]),
            rtol=1e-5, atol=1e-5)


class TestShardedState:
    """(b) fully sharded iteration state keeps serial parity."""

    def test_five_iteration_serial_parity(self, serial_baseline):
        # 1280 % 8 == 0: scores shard too (the production-layout path)
        X, y, gs = serial_baseline
        gd = fit_gbdt(X, y, {"objective": "binary", "metric": "auc",
                             "tree_learner": "data"}, num_round=5)
        assert gd._learner_mode == "data"
        # the state really lives sharded on the mesh
        assert len(gd._bins_dev.sharding.device_set) > 1
        assert len(gd._scores.sharding.device_set) > 1
        assert len(gd._full_mask_dev.sharding.device_set) > 1
        np.testing.assert_allclose(
            gd.predict_raw(X[:300]), gs.predict_raw(X[:300]),
            rtol=1e-5, atol=1e-5)
        gd._ensure_host_trees()
        gs._ensure_host_trees()
        for td, ts in zip(gd.models, gs.models):
            assert td.split_feature == ts.split_feature

    def test_uneven_rows_with_bagging_and_valid(self):
        """Odd row count (scores stay unsharded), bagging mask and a
        passenger valid set — the whole iteration surface."""
        X, y = make_binary(1283, seed=5)
        Xv, yv = make_binary(257, seed=6)
        params = {"objective": "binary", "metric": "auc",
                  "bagging_fraction": 0.7, "bagging_freq": 1}
        gs = fit_gbdt(X, y, params, num_round=5, valid=(Xv, yv))
        gd = fit_gbdt(X, y, dict(params, tree_learner="data"),
                      num_round=5, valid=(Xv, yv))
        np.testing.assert_allclose(
            gd.predict_raw(X[:200]), gs.predict_raw(X[:200]),
            rtol=1e-5, atol=1e-5)
        # valid scores advanced identically through the passenger rows
        np.testing.assert_allclose(
            np.asarray(gd._valid_scores[0]),
            np.asarray(gs._valid_scores[0]), rtol=1e-5, atol=1e-5)


class TestQuantizedPsum:
    """(c) the int32 quantized-histogram reduction matches single-chip
    quantized training (and the f32 wire matches it too)."""

    def test_matches_single_chip_quantized(self):
        X, y = make_binary(1282, seed=7)
        base = {"objective": "binary", "metric": "auc",
                "tpu_quantized_hist": True}
        gs = fit_gbdt(X, y, base, num_round=6)
        gd = fit_gbdt(X, y, dict(base, tree_learner="data",
                                 tpu_quantized_psum=1), num_round=6)
        assert gd._grower_cfg.precision == "int8"
        assert gd._grower_cfg.quant_psum
        np.testing.assert_allclose(
            gd.predict_raw(X[:300]), gs.predict_raw(X[:300]),
            rtol=1e-5, atol=1e-5)
        gd._ensure_host_trees()
        gs._ensure_host_trees()
        for td, ts in zip(gd.models, gs.models):
            assert td.split_feature == ts.split_feature

    def test_f32_wire_is_near_but_not_exact(self):
        """The pre-compression wire: psumming per-shard DEQUANTIZED
        sums rounds (D multiplies + D-1 f32 adds where the int wire
        does one exact int sum), so parity is approximate — the
        quality bar holds but bit-parity is exactly what the int32
        wire buys."""
        X, y = make_binary(1282, seed=7)
        g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc",
                            "tpu_quantized_hist": True,
                            "tree_learner": "data",
                            "tpu_quantized_psum": 0}, num_round=6)
        assert not g._grower_cfg.quant_psum
        auc = dict((n, v) for n, v, _ in g.get_eval_at(0))["auc"]
        assert auc > 0.95

    def test_quant_psum_requires_default_seams(self):
        from lightgbm_tpu.ops.split import SplitParams
        from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                                  make_wave_grower)
        from lightgbm_tpu.ops.split import FeatureMeta
        F = 2
        meta = FeatureMeta(
            num_bin=np.full(F, 8, np.int32),
            missing_type=np.zeros(F, np.int32),
            default_bin=np.zeros(F, np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        cfg = WaveGrowerConfig(num_leaves=7, num_bins=8,
                               precision="int8", quant_psum=True,
                               hp=SplitParams())
        with pytest.raises(ValueError, match="quant_psum"):
            make_wave_grower(
                cfg, meta,
                hist_fn=lambda *a, gh_scale=None: None)
        bad = cfg._replace(precision="default")
        with pytest.raises(ValueError, match="int8"):
            make_wave_grower(bad, meta)

    def test_packed_wire_bit_parity(self):
        """(PR16) the narrow psum wire: with few enough global rows the
        127*N wrap bound proves an int16 (even int8) payload cannot
        overflow, so cast -> narrow psum -> widen is EXACT and the
        model must match the int32 wire byte for byte."""
        from lightgbm_tpu.parallel.elastic import _strip_volatile
        X, y = make_binary(256, seed=11)
        base = {"objective": "binary", "metric": "auc",
                "tpu_quantized_hist": True, "tree_learner": "data",
                "tpu_quantized_psum": 1, "min_data_in_leaf": 5}
        g32 = fit_gbdt(X, y, dict(base, tpu_psum_wire=0), num_round=5)
        gnw = fit_gbdt(X, y, dict(base, tpu_psum_wire=-1), num_round=5)
        assert g32.wire_encoding() == "int32"
        assert gnw.wire_encoding() in ("int8", "int16")
        assert _strip_volatile(gnw.model_to_string()) \
            == _strip_volatile(g32.model_to_string())

    def test_async_slot_psum_bit_parity(self):
        """(PR16) the double-buffered slot collective splits the psum
        along the feature axis — pure scheduling freedom, elementwise
        across shards, so the model is bit-identical to the monolithic
        (sync) collective."""
        from lightgbm_tpu.parallel.elastic import _strip_volatile
        X, y = make_binary(1282, seed=7)
        base = {"objective": "binary", "metric": "auc",
                "tpu_quantized_hist": True, "tree_learner": "data",
                "tpu_quantized_psum": 1}
        gsync = fit_gbdt(X, y, dict(base, tpu_async_psum=0),
                         num_round=5)
        gasync = fit_gbdt(X, y, dict(base, tpu_async_psum=1),
                          num_round=5)
        assert gsync._grower_cfg.psum_slots == 1
        assert gasync._grower_cfg.psum_slots == 2
        assert _strip_volatile(gasync.model_to_string()) \
            == _strip_volatile(gsync.model_to_string())


class TestReporting:
    """Mesh size + comm bytes surface through the public API and the
    run report (bench.py consumes exactly these)."""

    def test_num_devices_and_run_report(self, tmp_path):
        import json
        path = str(tmp_path / "run.json")
        X, y = make_binary(1280, seed=9)
        g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc",
                            "tree_learner": "data",
                            "num_iterations": 4,
                            "tpu_run_report": path}, num_round=0)
        assert g.num_devices == len(get_devices())
        g.train()
        report = json.load(open(path))
        assert report["meta"]["mesh_devices"] == g.num_devices
        iters = [r for r in report["iterations"] if "comm_bytes" in r]
        assert iters, "no per-iteration comm bytes recorded"
        gcfg = g._grower_cfg
        per_pass = gcfg.wave_size * g._f_pad \
            * gcfg.num_bins * 3 * 4
        for r in iters:
            assert r["comm_bytes"] % per_pass == 0
            assert r["comm_bytes"] >= per_pass
        # the registry is process-cumulative, so >= the run's own total
        assert report["counters"]["comm/psum_bytes"] >= sum(
            r["comm_bytes"] for r in iters)

    def test_booster_num_devices(self):
        import lightgbm_tpu as lgb
        X, y = make_binary(640, seed=10)
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "max_bin": 31, "tree_learner": "data",
                         "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=2)
        assert bst.num_devices == len(get_devices())


    @pytest.mark.parametrize("learner", ["serial", "data"])
    def test_device_report_says_what_the_booster_resolved_to(
            self, learner):
        """Booster.device_report() (what chip_smoke.py asserts on): the
        route and tier from the booster's own state, the bin matrix's
        shards one per mesh device for the data learner, and
        GBDT.lower_step() exposing the compiled step's collective."""
        import lightgbm_tpu as lgb
        X, y = make_binary(1280, seed=12)
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "max_bin": 31, "tree_learner": learner,
                         "tpu_ingest": 1, "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=2,
                        keep_training_booster=True)
        rep = bst.device_report()
        D = len(get_devices()) if learner == "data" else 1
        assert rep["platform"] == "cpu" and rep["device_kind"] == "cpu"
        assert (rep["learner_mode"], rep["num_devices"]) == (learner, D)
        # the CPU route: fused XLA twin, no Pallas, nothing interpreted
        assert rep["route"] == "fused-xla" and rep["fused_xla"]
        assert not rep["fused_pallas"] and not rep["interpret"]
        assert rep["precision"] == "highest" and rep["device_ingest"]
        assert rep["num_data"] == 1280 <= rep["score_rows"]
        shards = rep["bins_shards"]
        assert len(shards) == D == len({dev for dev, _ in shards})
        assert all(shape == (rep["bins_shape"][0],
                             rep["bins_shape"][1] // D)
                   for _, shape in shards)
        hlo = bst._gbdt.lower_step().compile().as_text()
        assert ("all-reduce" in hlo) == (learner == "data")

    def test_one_device_data_learner_fallback_is_counted(
            self, monkeypatch):
        """tree_learner=data on a mesh of one keeps falling back to
        serial — but visibly: learner_mode/num_devices say so and
        learner/serial_fallbacks counts it (a multi-chip smoke fails on
        it instead of passing as a one-chip run)."""
        from lightgbm_tpu.obs import registry as obs
        from lightgbm_tpu.parallel import learners
        monkeypatch.setattr(learners, "training_mesh", lambda cfg: None)
        before = obs.counter("learner/serial_fallbacks").value
        X, y = make_binary(640, seed=13)
        g = fit_gbdt(X, y, {"objective": "binary",
                            "tree_learner": "data"}, num_round=1)
        assert (g.learner_mode, g.num_devices) == ("serial", 1)
        assert obs.counter("learner/serial_fallbacks").value \
            == before + 1


class TestConfigFallback:
    def test_unknown_tree_learner_warns_to_serial(self):
        cfg = Config().set({"tree_learner": "bogus"})
        assert cfg.tree_learner == "serial"
