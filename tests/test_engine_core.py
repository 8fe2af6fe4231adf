"""End-to-end quality tests on the core GBDT engine — the model of the
reference's tests/python_package_test/test_engine.py (trains real models,
asserts metric thresholds). Shared fixtures keep the number of distinct
XLA compiles (and thus CPU test time) bounded."""
import numpy as np
import pytest

from lightgbm_tpu.models.gbdt import GBDT

from conftest import fit_gbdt, make_binary, make_regression, make_multiclass


@pytest.fixture(scope="module")
def binary_model():
    X, y = make_binary()
    g = fit_gbdt(X, y, {"objective": "binary",
                        "metric": "auc,binary_logloss"}, num_round=30)
    return g, X, y


@pytest.fixture(scope="module")
def regression_model():
    X, y = make_regression()
    g = fit_gbdt(X, y, {"objective": "regression", "metric": "l2"},
                 num_round=40)
    return g, X, y


@pytest.fixture(scope="module")
def multiclass_model():
    X, y = make_multiclass()
    g = fit_gbdt(X, y, {"objective": "multiclass", "num_class": 4,
                        "metric": "multi_error,multi_logloss"},
                 num_round=20)
    return g, X, y


class TestBinary:
    def test_auc(self, binary_model):
        g, X, y = binary_model
        evals = dict((n, v) for n, v, _ in g.get_eval_at(0))
        assert evals["auc"] > 0.97

    def test_logloss(self, binary_model):
        g, X, y = binary_model
        evals = dict((n, v) for n, v, _ in g.get_eval_at(0))
        assert evals["binary_logloss"] < 0.35

    def test_prediction_matches_internal_score(self, binary_model):
        g, X, y = binary_model
        p = g.predict_raw(X)
        internal = np.asarray(g.train_scores()[0])
        np.testing.assert_allclose(p, internal, rtol=1e-4, atol=1e-5)

    def test_predict_probability_range(self, binary_model):
        g, X, _ = binary_model
        p = g.predict(X[:100])
        assert np.all((p >= 0) & (p <= 1))

    def test_valid_auc_generalizes(self):
        X, y = make_binary()
        Xv, yv = make_binary(640, seed=7)
        g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc"},
                     num_round=20, valid=(Xv, yv))
        (_, auc, _), = g.get_eval_at(1)
        assert auc > 0.93


class TestRegression:
    def test_l2(self, regression_model):
        g, X, y = regression_model
        (_, l2, _), = g.get_eval_at(0)
        assert l2 < 0.35 * np.var(y)

    def test_l1_objective(self):
        X, y = make_regression()
        g = fit_gbdt(X, y, {"objective": "regression_l1", "metric": "l1"},
                     num_round=40)
        (_, l1, _), = g.get_eval_at(0)
        assert l1 < 0.7 * np.mean(np.abs(y - y.mean()))

    def test_quantile(self):
        X, y = make_regression()
        g = fit_gbdt(X, y, {"objective": "quantile", "alpha": 0.9},
                     num_round=30)
        p = g.predict(X)
        frac = np.mean(y <= p)
        assert 0.78 < frac <= 1.0

    @pytest.mark.parametrize("objective", ["huber", "fair", "poisson"])
    def test_other_objectives_run(self, objective):
        X, y = make_regression()
        if objective == "poisson":
            y = y - y.min() + 0.5
        g = fit_gbdt(X, y, {"objective": objective}, num_round=5)
        assert len(g.models) == 5


class TestMulticlass:
    def test_softmax_error(self, multiclass_model):
        g, X, y = multiclass_model
        evals = dict((n, v) for n, v, _ in g.get_eval_at(0))
        assert evals["multi_error"] < 0.12

    def test_predict_shape_and_simplex(self, multiclass_model):
        g, X, _ = multiclass_model
        p = g.predict(X[:50])
        assert p.shape == (50, 4)
        np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-5)

    def test_ova(self):
        X, y = make_multiclass()
        g = fit_gbdt(X, y, {"objective": "multiclassova", "num_class": 4,
                            "metric": "multi_error"}, num_round=15)
        (_, err, _), = g.get_eval_at(0)
        assert err < 0.15


class TestWeightsAndSampling:
    def test_weighted_binary(self):
        X, y = make_binary()
        w = np.where(y > 0, 2.0, 1.0).astype(np.float32)
        g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc"},
                     num_round=20, weight=w)
        (_, auc, _), = g.get_eval_at(0)
        assert auc > 0.95

    def test_bagging(self):
        X, y = make_binary()
        g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc",
                            "bagging_fraction": 0.5, "bagging_freq": 1},
                     num_round=20)
        (_, auc, _), = g.get_eval_at(0)
        assert auc > 0.94

    def test_feature_fraction(self):
        X, y = make_binary()
        g = fit_gbdt(X, y, {"objective": "binary", "metric": "auc",
                            "feature_fraction": 0.5}, num_round=20)
        (_, auc, _), = g.get_eval_at(0)
        assert auc > 0.94


class TestModelIO:
    def test_text_roundtrip_exact(self, binary_model):
        g, X, _ = binary_model
        s = g.model_to_string()
        g2 = GBDT().load_model_from_string(s)
        # the reference's own codegen test asserts 5-decimal equality
        # (tests/cpp_test/test.py); device f32 vs host f64 accumulation
        np.testing.assert_allclose(
            g.predict_raw(X), g2.predict_raw(X), rtol=0, atol=1e-5)

    def test_reference_format_header(self, binary_model):
        g, _, _ = binary_model
        s = g.model_to_string()
        lines = s.splitlines()
        assert lines[0] == "tree"
        assert any(l.startswith("version=v2") for l in lines)
        assert any(l.startswith("num_class=1") for l in lines)
        assert any(l.startswith("feature_infos=") for l in lines)
        assert any(l.startswith("tree_sizes=") for l in lines)
        assert "end of trees" in s
        assert "end of parameters" in s

    def test_multiclass_roundtrip(self, multiclass_model):
        g, X, _ = multiclass_model
        g2 = GBDT().load_model_from_string(g.model_to_string())
        np.testing.assert_allclose(
            g.predict_raw(X[:100]), g2.predict_raw(X[:100]), atol=1e-5)

    def test_json_dump(self, binary_model):
        g, _, _ = binary_model
        d = g.dump_model()
        assert d["num_class"] == 1
        assert len(d["tree_info"]) == len(g.models)
        t0 = d["tree_info"][0]["tree_structure"]
        assert "split_feature" in t0 or "leaf_value" in t0

    def test_num_iteration_clamp(self, binary_model):
        g, X, _ = binary_model
        p5 = g.predict_raw(X[:50], num_iteration=5)
        pall = g.predict_raw(X[:50])
        assert not np.allclose(p5, pall)


class TestRollback:
    def test_rollback_one_iter(self):
        X, y = make_binary()
        g = fit_gbdt(X, y, {"objective": "binary"}, num_round=5)
        p5 = g.predict_raw(X)
        g.train_one_iter()
        g.rollback_one_iter()
        np.testing.assert_allclose(g.predict_raw(X), p5, atol=1e-5)
        assert len(g.models) == 5


class TestMonotone:
    def test_monotone_constraints_hold(self):
        r = np.random.default_rng(3)
        n = 1280
        X = r.uniform(-2, 2, size=(n, 3))
        y = (X[:, 0] + 0.3 * np.sin(3 * X[:, 1])
             + 0.05 * r.normal(size=n)).astype(np.float32)
        g = fit_gbdt(X, y, {"objective": "regression",
                            "monotone_constraints": [1, 0, 0]},
                     num_round=25)
        base = np.zeros((200, 3))
        base[:, 0] = np.linspace(-2, 2, 200)
        p = g.predict(base)
        assert np.all(np.diff(p) >= -1e-6)


class TestFeatureImportance:
    def test_importance_finds_signal(self, binary_model):
        g, _, _ = binary_model
        imp = g.feature_importance("split")
        # features 0-3 carry signal, 4+ are noise
        assert imp[:4].sum() > imp[4:].sum()

    def test_gain_importance(self, binary_model):
        g, _, _ = binary_model
        imp = g.feature_importance("gain")
        assert imp.sum() > 0


def test_device_type_routing():
    """Explicit device_type routes the framework's device selection
    (the reference's CPU/GPU switch): cpu installs the host routing,
    an accelerator request clears it and is FATAL on a process whose
    jax backend is the CPU (never a quiet CPU run under a TPU request),
    unknown values fatal."""
    import pytest as _pytest
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils import device
    from lightgbm_tpu.utils.log import LightGBMError
    try:
        Config().set({"device_type": "cpu"})
        assert device._config_platform == "cpu"
        for accel in ("tpu", "gpu", "cuda"):
            device.set_config_platform("cpu")
            with _pytest.raises(LightGBMError, match="no accelerator"):
                Config().set({"device_type": accel})
            # the cpu routing is cleared BEFORE the refusal: the request
            # is never silently served by the previous booster's routing
            assert device._config_platform is None
        with _pytest.raises(LightGBMError):
            Config().set({"device_type": "banana"})
    finally:
        device.set_config_platform(None)


def test_cv_accepts_test_index_folds():
    """cv(folds=[test_idx, ...]) — the reference R package's custom
    folds semantics: bare test-index arrays whose train side is the
    complement, normalized AFTER the dataset is constructed with the
    merged params."""
    import lightgbm_tpu as lgb
    X, y = make_binary(n=900, f=5, seed=31)
    ds = lgb.Dataset(X, label=y)
    folds = [np.arange(0, 300), np.arange(300, 600),
             np.arange(600, 900)]
    res = lgb.cv({"objective": "binary", "metric": "auc",
                  "num_leaves": 15, "verbose": -1}, ds,
                 num_boost_round=8, folds=folds, verbose_eval=False)
    assert "auc-mean" in res and len(res["auc-mean"]) == 8
    assert res["auc-mean"][-1] > 0.9
    # pair form still works
    ds2 = lgb.Dataset(X, label=y)
    pairs = [(np.arange(300, 900), np.arange(0, 300))]
    res2 = lgb.cv({"objective": "binary", "metric": "auc",
                   "num_leaves": 15, "verbose": -1}, ds2,
                  num_boost_round=5, folds=pairs, verbose_eval=False)
    assert len(res2["auc-mean"]) == 5
