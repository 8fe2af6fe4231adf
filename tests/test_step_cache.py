"""Compiled-step registry suite (ops/step_cache.py): cross-booster
reuse proven by registry counters, and bit-parity of bucket-padded
training against exact-shape training across the eligibility matrix
(bagging, valid sets, quantized histograms, weights, renew objectives,
data-parallel learner). Run with ``pytest -m stepcache``.

Parity here is between the SHARED-step programs (tpu_row_bucket=-1 vs
0): that is the invariant the registry relies on — a booster served
from the cache must produce exactly what it would have compiled for
itself. The legacy per-instance closure (tpu_step_cache=0) is checked
too where the suite historically guaranteed it (K=1 objectives); for
multiclass, XLA's whole-program fusion can flip an exactly-tied
zero-gain split between the two PROGRAM SHAPES (observed as an
output-neutral extra leaf), so the legacy check there is on
predictions, not model text.
"""
import numpy as np
import pytest

from conftest import (TEST_PARAMS, fit_gbdt, make_binary,
                      make_multiclass, make_regression)
from lightgbm_tpu.ops import autotune, step_cache
from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
from lightgbm_tpu.ops.wave_grower import WaveGrowerConfig, make_wave_grower

pytestmark = pytest.mark.stepcache


def trees(g):
    """Model text minus the parameters section (the tpu_step_cache /
    tpu_row_bucket knobs legitimately differ between parity runs)."""
    return g.model_to_string().split("parameters:")[0]


def stats_delta(fn):
    s0 = step_cache.stats()
    out = fn()
    s1 = step_cache.stats()
    return out, {k: s1[k] - s0[k] for k in ("hits", "misses")}


def test_cross_booster_reuse_exact_counters():
    """Two boosters with identical geometry compile the fused step
    exactly once — the second is a pure registry hit."""
    X, y = make_binary(640, seed=11)
    g1, d1 = stats_delta(
        lambda: fit_gbdt(X, y, {"objective": "binary"}, num_round=4))
    g2, d2 = stats_delta(
        lambda: fit_gbdt(X, y, {"objective": "binary"}, num_round=4))
    assert d2["misses"] == 0, "second booster must not recompile"
    assert d2["hits"] >= 1
    assert trees(g1) == trees(g2)


def test_same_bucket_different_n_shares_step():
    """Row counts landing in the same power-of-two bucket share one
    compiled step; the padded run is bit-exact vs its own exact-shape
    run."""
    X, y = make_binary(1280, seed=12)
    _, d1 = stats_delta(
        lambda: fit_gbdt(X, y, {"objective": "binary"}, num_round=4))
    gb, d2 = stats_delta(
        lambda: fit_gbdt(X[:1100], y[:1100], {"objective": "binary"},
                         num_round=4))
    assert d2["misses"] == 0, \
        "n=1100 and n=1280 land in the same 2048 bucket"
    ge = fit_gbdt(X[:1100], y[:1100],
                  {"objective": "binary", "tpu_row_bucket": 0},
                  num_round=4)
    assert trees(gb) == trees(ge)


@pytest.mark.parametrize("name,params,kwargs", [
    ("bagging", {"objective": "binary", "bagging_freq": 2,
                 "bagging_fraction": 0.7}, {}),
    ("valid", {"objective": "binary"}, {"valid": True}),
    ("quantized", {"objective": "binary",
                   "tpu_quantized_hist": True}, {}),
    ("weights", {"objective": "regression"}, {"weight": True}),
    ("l1_renew", {"objective": "regression_l1"}, {}),
])
def test_bucket_padding_bit_parity(name, params, kwargs):
    """Bucket-padded training (tpu_row_bucket=-1) is bit-exact vs
    exact shapes (tpu_row_bucket=0) AND vs the legacy per-instance
    closure (tpu_step_cache=0)."""
    if params["objective"].startswith("regression"):
        X, y = make_regression(1280, seed=13)
    else:
        X, y = make_binary(1280, seed=13)
    kw = {}
    if kwargs.get("valid"):
        kw["valid"] = (X[:320], y[:320])
    if kwargs.get("weight"):
        r = np.random.default_rng(5)
        kw["weight"] = (np.abs(r.normal(size=1280)) + 0.5).astype(
            np.float32)
    gb = fit_gbdt(X, y, params, num_round=5, **kw)
    ge = fit_gbdt(X, y, dict(params, tpu_row_bucket=0), num_round=5,
                  **kw)
    gl = fit_gbdt(X, y, dict(params, tpu_step_cache=0), num_round=5,
                  **kw)
    assert trees(gb) == trees(ge), f"{name}: bucket != exact"
    assert trees(gb) == trees(gl), f"{name}: cached != legacy"


def test_data_parallel_reuse_and_legacy_parity():
    """The sharded f32 data learner caches at exact shapes (bucketing
    would regroup the cross-shard f32 psums): same-N boosters share
    one step, and the shared step matches the legacy closure."""
    X, y = make_binary(1280, seed=14)
    params = {"objective": "binary", "tree_learner": "data"}
    gb, _ = stats_delta(lambda: fit_gbdt(X, y, params, num_round=4))
    _, d2 = stats_delta(lambda: fit_gbdt(X, y, params, num_round=4))
    assert d2["misses"] == 0
    assert d2["hits"] >= 1
    gl = fit_gbdt(X, y, dict(params, tpu_step_cache=0), num_round=4)
    assert trees(gb) == trees(gl)


def test_data_parallel_quantized_bucket_parity():
    """Quantized data-parallel training buckets: the int32 histogram
    wire and integer root sums are grouping-invariant, so the padded
    run is bit-exact vs exact shapes even though the shard boundaries
    moved."""
    X, y = make_binary(1280, seed=19)
    params = {"objective": "binary", "tree_learner": "data",
              "tpu_quantized_hist": True}
    gb = fit_gbdt(X, y, params, num_round=4)
    assert gb._n_score > gb._n, "quantized data mode must bucket"
    ge = fit_gbdt(X, y, dict(params, tpu_row_bucket=0), num_round=4)
    assert trees(gb) == trees(ge)


def test_multiclass_bucket_parity():
    """K>1: bucket-vs-exact stays bit-exact within the shared path;
    vs the legacy program shape, predictions (not borderline zero-gain
    splits) are the guarantee."""
    X, y = make_multiclass(1280, seed=15)
    params = {"objective": "multiclass", "num_class": 4}
    gb = fit_gbdt(X, y, params, num_round=4)
    ge = fit_gbdt(X, y, dict(params, tpu_row_bucket=0), num_round=4)
    assert trees(gb) == trees(ge)
    gl = fit_gbdt(X, y, dict(params, tpu_step_cache=0), num_round=4)
    np.testing.assert_array_equal(gb.predict(X[:256]),
                                  gl.predict(X[:256]))


def test_step_cache_off_knob():
    """tpu_step_cache=0 keeps the legacy closure: no registry
    traffic."""
    X, y = make_binary(512, seed=16)
    _, d = stats_delta(
        lambda: fit_gbdt(X, y, {"objective": "binary",
                                "tpu_step_cache": 0}, num_round=3))
    assert d["misses"] == 0 and d["hits"] == 0


def test_custom_gradients_cached():
    """Objective-less boosters (custom fobj gradients) ride the shared
    step with grad_fn=None; parity with the legacy closure holds."""
    X, y = make_regression(700, seed=17)

    def run(extra):
        def go():
            import conftest as _c
            from lightgbm_tpu.config import Config
            from lightgbm_tpu.io.dataset import Metadata, TpuDataset
            from lightgbm_tpu.models.gbdt import GBDT
            p = dict(TEST_PARAMS)
            p.update({"objective": "none"})
            p.update(extra)
            cfg = Config().set(p)
            ds = TpuDataset(cfg).construct_from_matrix(
                X, Metadata(label=y))
            g = GBDT()
            g.init(cfg, ds, None, ())
            for _ in range(3):
                s = np.asarray(g.train_scores())[0]
                g.train_one_iter(grad=(s - y).astype(np.float32),
                                 hess=np.ones_like(y, np.float32))
            g.finish_training()
            return g
        return go
    gb, _ = stats_delta(run({}))
    _, d2 = stats_delta(run({}))
    assert d2["misses"] == 0
    gl = fit_gbdt  # noqa: F841  (uniform style)
    ge, _ = stats_delta(run({"tpu_step_cache": 0}))
    assert trees(gb) == trees(ge)


def test_reset_parameter_cannot_flip_step_implementation():
    """A mid-life reset_parameter that flips a step-cache knob must
    NOT switch step implementations: the live buffers are frozen at
    the widths chosen at init (the legacy closure cannot consume a
    bucketed score width)."""
    import lightgbm_tpu as lgb
    X, y = make_binary(1000, seed=21)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "max_bin": 31, "verbosity": -1},
                    lgb.Dataset(X, y), num_boost_round=2,
                    verbose_eval=False, keep_training_booster=True)
    g = bst._gbdt
    assert g._cache_eligible and g._n_score > g._n
    bst.reset_parameter({"tpu_step_cache": 0, "learning_rate": 0.05})
    bst.update()                      # crashed before the freeze
    assert g._cache_eligible, "implementation flipped mid-life"
    assert g._n_score > g._n
    assert len(g.records) == 3


def _goss_booster(extra):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.models.boosting import create_boosting
    from lightgbm_tpu.objectives import create_objective
    X, y = make_binary(640, seed=18)
    p = dict(TEST_PARAMS)
    p.update({"objective": "binary", "boosting": "goss",
              "top_rate": 0.3, "other_rate": 0.3})
    p.update(extra)
    cfg = Config().set(p)
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    g = create_boosting("goss")
    g.init(cfg, ds, obj, ())
    for _ in range(3):
        g.train_one_iter()
    return g


def test_ineligible_variants_keep_legacy():
    """Legacy GOSS (tpu_goss_hash=0) opts out — its in-jit sampler is
    positional in the row width — no registry traffic, and the booster
    still trains."""
    g, d = stats_delta(lambda: _goss_booster({"tpu_goss_hash": 0}))
    assert d["misses"] == 0 and d["hits"] == 0
    assert not g._cache_eligible
    assert g._n_score == g._n
    assert len(g.records) == 3


def test_goss_hash_rides_registry():
    """Default (hashed) GOSS is step-cache eligible: first booster
    misses once, a same-geometry retrain is a pure registry hit, and
    the two produce identical trees."""
    g1, d1 = stats_delta(lambda: _goss_booster({}))
    assert g1._cache_eligible
    assert d1["misses"] >= 1
    g2, d2 = stats_delta(lambda: _goss_booster({}))
    assert d2["misses"] == 0, "same-geometry GOSS retrain recompiled"
    assert d2["hits"] >= 1
    assert trees(g1) == trees(g2)
    assert len(g2.records) == 3


def test_lambdarank_rides_registry_and_retrain_hits():
    """lambdarank's query tables ride the aux pytree (replicated
    ``_``-keys, bucketed [nq, qmax] shapes): a same-geometry retrain is
    a pure registry hit and bit-identical — the windows-2+ zero-compile
    promise for the ranking workload."""
    rng = np.random.default_rng(31)
    n, qsize = 1200, 20
    X = rng.normal(size=(n, 8))
    y = np.clip((X[:, 0] * 2 + rng.normal(size=n)) // 1.0,
                0, 3).astype(np.float32)
    group = np.full(n // qsize, qsize, np.int64)
    params = {"objective": "lambdarank"}
    g1, d1 = stats_delta(
        lambda: fit_gbdt(X, y, params, num_round=4, group=group))
    assert g1._cache_eligible, "lambdarank must be registry-eligible"
    assert d1["misses"] >= 1
    g2, d2 = stats_delta(
        lambda: fit_gbdt(X, y, params, num_round=4, group=group))
    assert d2["misses"] == 0, "lambdarank retrain recompiled"
    assert d2["hits"] >= 1
    assert trees(g1) == trees(g2)
    # bucket-vs-exact parity: padded query tables are inert
    ge = fit_gbdt(X, y, dict(params, tpu_row_bucket=0), num_round=4,
                  group=group)
    assert trees(g1) == trees(ge)


def test_lrb_two_window_smoke():
    """Two sliding windows of the paper workload: fresh booster per
    window, ONE compile for the run — every window after the first is
    a registry hit with ~zero compile time (windows differ in observed
    bin counts AND surviving feature counts, so this exercises the B/F
    geometry bucketing, not just row bucketing)."""
    from lightgbm_tpu.lrb import LrbDriver, synthetic_trace
    import io
    out = io.StringIO()
    drv = LrbDriver(cache_size=1 << 16, window_size=512,
                    sample_size=256, cutoff=0.5, sampling=1,
                    result_file=out)
    for seq, oid, size, cost in synthetic_trace(1024, n_objects=60):
        drv.process_request(seq, oid, size, cost)
    assert len(drv.results) == 2
    assert drv.booster is not None
    trained = [r for r in drv.results if "train_s" in r]
    assert trained, "at least one window must have trained a model"
    assert all(r["compile_s"] >= 0 for r in trained)
    # amortization: windows after the first must NOT recompile
    for r in trained[1:]:
        assert r["step_cache_hits"] >= 1, \
            "later window re-compiled — geometry key drifted"
        assert r["compile_s"] < 1.0
    # the second window evaluates the first window's model
    assert "fp_rate" in drv.results[1]


def test_geometry_bucketing_shares_across_data_shapes():
    """The observed max bin count AND the surviving feature count are
    data-dependent (trivial columns are excluded) — the B/F axis
    buckets (pow2 bins, mult-of-8 features) make boosters trained on
    differently-shaped windows share ONE step.

    Against the exact-shape program the stated tolerance is: the SAME
    tree structure (every split feature and threshold), leaf values and
    raw scores equal to f32 rounding. Bit-equality held under jax 0.4
    and does not under 0.9 — PR 22 traced it to ONE reduction: the
    split scan's prefix sums are a tril matmul (ops/split.py
    _prefix_sums), and XLA:CPU now picks a different dot micro-kernel
    — a different K-axis accumulation grouping — when the bin axis
    pads 36 -> 64 (63 -> 64, the other parity tests' pad, happens to
    keep it). Feature-axis and row padding stay bit-free (the
    tpu_row_bucket=0 shared-step run below, F padded and B exact, IS
    bit-equal to the legacy closure). docs/Design.md §5d."""
    rng = np.random.default_rng(11)
    n = 1280
    # 10 informative + 1 constant column -> F=10 after trivial
    # exclusion (pads to 16); ~40 distinct levels -> B!=pow2 (pads 64)
    X = np.round(rng.normal(size=(n, 11)) * 6).clip(-20, 19)
    X[:, 7] = 3.0
    w = rng.normal(size=11)
    w[7] = 0
    y = ((X @ w + rng.normal(size=n) * 0.5) > 0).astype(np.float32)
    params = {"objective": "binary", "bagging_freq": 2,
              "bagging_fraction": 0.8}
    gb, _ = stats_delta(lambda: fit_gbdt(X, y, params, num_round=5))
    assert gb._f_pad % 8 == 0 and gb._f_pad > gb.train_data.num_features
    assert gb._grower_cfg.num_bins == 64
    gl = fit_gbdt(X, y, dict(params, tpu_step_cache=0), num_round=5)
    assert gl._f_pad == gl.train_data.num_features
    # F padded (10 -> 16), B exact (36): bit-equal to the legacy closure
    ge = fit_gbdt(X, y, dict(params, tpu_row_bucket=0), num_round=5)
    assert ge._f_pad == gb._f_pad and ge._grower_cfg.num_bins < 64
    assert trees(ge) == trees(gl), "padded F drifted vs legacy"
    # B padded too (36 -> 64): same structure, f32-rounding-equal values
    gb._ensure_host_trees()
    gl._ensure_host_trees()
    assert len(gb.models) == len(gl.models)
    for tb, tl in zip(gb.models, gl.models):
        assert list(tb.split_feature) == list(tl.split_feature)
        assert list(tb.threshold_in_bin) == list(tl.threshold_in_bin)
        np.testing.assert_allclose(tb.leaf_value, tl.leaf_value,
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gb.predict_raw(X[:512]),
                               gl.predict_raw(X[:512]),
                               rtol=0, atol=1e-5)
    # different observed bins (50 levels) AND features (12, no trivial
    # column): same (16, 64) bucket -> pure registry hit
    X2 = np.round(rng.normal(size=(n, 12)) * 8).clip(-25, 24)
    y2 = ((X2 @ rng.normal(size=12)) > 0).astype(np.float32)
    _, d2 = stats_delta(lambda: fit_gbdt(X2, y2, params, num_round=5))
    assert d2["misses"] == 0, "same-bucket shapes must share the step"
    assert d2["hits"] >= 1


def test_route_field_separates_config_identity():
    """The resolved route is part of WaveGrowerConfig, and so of the
    step's geometry key."""
    kw = dict(num_leaves=15, num_bins=63, wave_size=8, hp=SplitParams())
    cfgs = [WaveGrowerConfig(**kw, route=r) for r in autotune.HIST_ROUTES]
    assert len(set(cfgs)) == len({hash(c) for c in cfgs}) == 3


@pytest.mark.parametrize("backend", ["gpu", "rocm"])
def test_bogus_route_rejected(backend):
    """A Pallas route of any backend but the TPU is an unknown name."""
    meta = FeatureMeta(
        num_bin=np.full(4, 63, np.int32),
        missing_type=np.zeros(4, np.int32),
        default_bin=np.zeros(4, np.int32),
        monotone=np.zeros(4, np.int32),
        penalty=np.ones(4, np.float32))
    cfg = WaveGrowerConfig(num_leaves=15, num_bins=63, wave_size=8,
                           hp=SplitParams(), route=f"pallas-{backend}")
    with pytest.raises(ValueError, match="route"):
        make_wave_grower(cfg, meta)


def test_pinned_routes_train_bit_identical_and_key_apart(monkeypatch):
    """A pinned two-pass run trains the trees of the fused-XLA route
    bit for bit, compiles a step of its own (the route rides the
    geometry key), and a same-geometry retrain under the same pin is a
    pure registry hit."""
    X, y = make_binary(640, seed=21)
    params = dict(TEST_PARAMS, objective="binary")
    g_fused = fit_gbdt(X, y, params, num_round=4)   # this host's own route
    rep = g_fused.device_report()
    assert (rep["route"], rep["fused_xla"]) == ("fused-xla", True)
    monkeypatch.setattr(autotune, "tune_hist_route",
                        lambda **kw: "two-pass")
    g_two1, d1 = stats_delta(lambda: fit_gbdt(X, y, params, num_round=4))
    g_two2, d2 = stats_delta(lambda: fit_gbdt(X, y, params, num_round=4))
    rep = g_two1.device_report()
    assert (rep["route"], rep["fused_xla"]) == ("two-pass", False)
    assert trees(g_two1) == trees(g_fused)
    assert d1["misses"] >= 1, "the pinned route compiles its own step"
    assert d2["misses"] == 0 and d2["hits"] >= 1
    assert trees(g_two2) == trees(g_two1)
