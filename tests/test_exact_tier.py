"""Exact-semantics histogram tier suite (pytest -m exact_tier).

Three layers lock the exact-tier overhaul down:

1. **Kernel bit-parity** — the reduced-channel hi/lo layouts ("hilo4",
   "hilo3") of both Pallas kernels (interpret mode) must reproduce the
   original 5-channel kernel BIT-FOR-BIT on the same inputs, and their
   integer channels must match the XLA oracle exactly, across a
   fixture grid (-0.0 gradients, zero hessians, out-of-bag rows,
   missing-type metadata, categorical bitsets).
2. **Fused-XLA route parity** — the off-TPU fused partition+histogram
   region (ops/hist_wave.py fused_partition_histogram_xla, the new
   CPU hot path) trains BIT-identical models to the legacy two-pass
   pipeline (cfg.fused=False) across bagging / NaN / -0.0 /
   categorical / multiclass / quantized-off-and-on, at the grower
   level AND end-to-end through GBDT (pinned wave size, so the only
   change is the route).
3. **Selection + caching** — tune_exact_tier unit tests with a fake
   timer (winner by measured time, cache hit on re-encounter, hilo3
   gated on constant-unit hessians), and the step-cache geometry key
   carrying the winning variant (different variants = different
   compiled steps; same variant re-trains are pure hits).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import fit_gbdt, make_binary, make_regression
from lightgbm_tpu.ops import autotune, step_cache
from lightgbm_tpu.ops.hist_wave import (
    TBL_ROWS, fused_partition_histogram_pallas,
    fused_partition_histogram_xla, wave_histogram_pallas,
    wave_histogram_xla)
from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                          apply_wave_splits,
                                          make_wave_grower)

pytestmark = pytest.mark.exact_tier


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _kernel_problem(kind, N=777, F=6, B=63, n_leaves=5, seed=3):
    """(bins_t, g, h, leaf) with the grid's awkward numerics."""
    r = np.random.default_rng(seed)
    bins_t = r.integers(0, B, (F, N)).astype(np.uint8)
    g = r.normal(size=N).astype(np.float32)
    h = r.uniform(0.2, 1.0, N).astype(np.float32)
    leaf = r.integers(-1, n_leaves, N).astype(np.int32)
    if kind == "neg_zero":
        # -0.0 gradients: the bf16 hi/lo bit-truncation split must
        # carry the sign through both halves
        g[::7] = -0.0
        g[1::7] = 0.0
    elif kind == "zero_hess":
        h[::5] = 0.0
    elif kind == "bag_heavy":
        leaf[r.random(N) < 0.6] = -1
    return bins_t, g, h, leaf


KERNEL_KINDS = ["plain", "neg_zero", "zero_hess", "bag_heavy"]


def _jx(*arrs):
    return tuple(jnp.asarray(a) for a in arrs)


# ---------------------------------------------------------------------------
# 1. kernel bit-parity
# ---------------------------------------------------------------------------

class TestWaveKernelVariants:
    @pytest.mark.parametrize("kind", KERNEL_KINDS)
    def test_hilo4_bitwise_vs_hilo5_and_oracle(self, kind):
        bins_t, g, h, leaf = _kernel_problem(kind)
        wl = np.array([0, 2, -1, 4, 1], np.int32)
        args = _jx(bins_t, g, h, leaf, wl)
        ref = np.asarray(wave_histogram_xla(*args, num_bins=64))
        h5 = np.asarray(wave_histogram_pallas(
            *args, num_bins=64, chunk=256, interpret=True,
            variant="hilo5"))
        h4 = np.asarray(wave_histogram_pallas(
            *args, num_bins=64, chunk=256, interpret=True,
            variant="hilo4"))
        np.testing.assert_array_equal(h4, h5)
        # the second (count) dot must be exact, not merely close
        np.testing.assert_array_equal(h4[..., 2], ref[..., 2])
        np.testing.assert_allclose(h4, ref, atol=1e-4)

    @pytest.mark.parametrize("kind", ["plain", "neg_zero", "bag_heavy"])
    def test_hilo3_bitwise_on_unit_hessians(self, kind):
        """hilo3's fused hess/count plane: with h == membership mask
        (the constant-unit-hessian contract) all three channels are
        bit-equal to the 5-channel kernel AND the oracle's integer
        channels."""
        bins_t, g, h, leaf = _kernel_problem(kind)
        m = (leaf >= 0).astype(np.float32)      # bag mask via leaf=-1
        gm, hm = g * m, m.copy()                # h = 1.0 * mask
        wl = np.array([0, 2, -1, 4, 1], np.int32)
        args = _jx(bins_t, gm, hm, leaf, wl)
        ref = np.asarray(wave_histogram_xla(*args, num_bins=64))
        h5 = np.asarray(wave_histogram_pallas(
            *args, num_bins=64, chunk=256, interpret=True,
            variant="hilo5"))
        h3 = np.asarray(wave_histogram_pallas(
            *args, num_bins=64, chunk=256, interpret=True,
            variant="hilo3"))
        np.testing.assert_array_equal(h3, h5)
        np.testing.assert_array_equal(h3[..., 1], ref[..., 1])
        np.testing.assert_array_equal(h3[..., 2], ref[..., 2])

    def test_wide_waves_respect_new_lane_caps(self):
        """hilo4 admits W=32 and hilo3 W=40 — both beyond hilo5's 25 —
        while hilo5 still refuses them (the lane budget is the whole
        point of the reduced layouts)."""
        bins_t, g, h, leaf = _kernel_problem("plain", B=16, n_leaves=40)
        wl40 = np.arange(40, dtype=np.int32)
        args = _jx(bins_t, g, h, leaf, wl40)
        with pytest.raises(NotImplementedError, match="128 lanes"):
            wave_histogram_pallas(*args, num_bins=16, chunk=256,
                                  interpret=True, variant="hilo5")
        ref = np.asarray(wave_histogram_xla(*args, num_bins=16))
        h3 = np.asarray(wave_histogram_pallas(
            *args, num_bins=16, chunk=256, interpret=True,
            variant="hilo3"))
        np.testing.assert_array_equal(h3[..., 2], ref[..., 2])
        h4 = np.asarray(wave_histogram_pallas(
            *_jx(bins_t, g, h, leaf, np.arange(32, dtype=np.int32)),
            num_bins=16, chunk=256, interpret=True, variant="hilo4"))
        assert h4.shape == (32, 6, 16, 3)


class TestFusedKernelVariants:
    def _fused_case(self):
        r = np.random.default_rng(0)
        N, F, B, W = 999, 5, 64, 8
        bins_t = r.integers(0, 63, (F, N)).astype(np.uint8)
        g = r.normal(size=N).astype(np.float32)
        g[::9] = -0.0
        h = r.uniform(0.1, 1, N).astype(np.float32)
        mask = (r.uniform(size=N) > 0.3).astype(np.float32)
        leaf = r.integers(0, 4, N).astype(np.int32)
        wl = np.array([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
        new_ids = np.array([4, 5, 6, 7, -1, -1, -1, -1], np.int32)
        feat = r.integers(0, F, W).astype(np.int32)
        tbin = r.integers(0, 60, W).astype(np.int32)
        dleft = r.integers(0, 2, W).astype(bool)
        meta = FeatureMeta(
            num_bin=np.full(F, 64, np.int32),
            missing_type=np.array([0, 1, 2, 0, 1], np.int32),
            default_bin=np.array([0, 3, 0, 0, 5], np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        tbl = np.zeros((18, W), np.int32)
        tbl[0], tbl[1], tbl[2], tbl[3] = wl, new_ids, feat, tbin
        tbl[4] = dleft.astype(np.int32)
        tbl[5] = meta.missing_type[feat]
        tbl[6] = meta.default_bin[feat]
        tbl[7] = meta.num_bin[feat]
        tbl[8] = new_ids            # small = right child
        return (bins_t, g, h, mask, leaf, wl, new_ids, feat, tbin,
                dleft, meta, tbl, B, W)

    @pytest.mark.parametrize("compact", [False, True],
                             ids=["masked", "compacted"])
    @pytest.mark.parametrize("variant,unit_h", [("hilo4", False),
                                                ("hilo3", True)])
    def test_fused_variant_bitwise_vs_hilo5(self, variant, unit_h,
                                            compact):
        """Whether every row of a chunk goes through the dot under
        zero weights or only the compacted contributing rows do: the
        layouts stay bit-equal to one another."""
        (bins_t, g, h, mask, leaf, wl, new_ids, feat, tbin, dleft,
         meta, tbl, B, W) = self._fused_case()
        if unit_h:
            h = mask.copy()         # constant-unit-hessian contract
        gm, hm = g * mask, h * mask
        base = _jx(bins_t, gm, hm, mask, leaf, tbl)
        l5, h5, _ = fused_partition_histogram_pallas(
            *base, num_bins=B, chunk=256, interpret=True,
            variant="hilo5", compact=compact)
        lv, hv, _ = fused_partition_histogram_pallas(
            *base, num_bins=B, chunk=256, interpret=True,
            variant=variant, compact=compact)
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(l5))
        np.testing.assert_array_equal(np.asarray(hv), np.asarray(h5))

    @pytest.mark.parametrize("variant", ["hilo5", "hilo4", "hilo3"])
    def test_compacted_packed4_fused_kernel_bitwise(self, variant):
        """Nibble-packed bins through the compaction: the packed byte
        rows are what the gather carries (<= 255, exact), unpacked
        after it; same bits as unpacked bins through the same kernel."""
        r = np.random.default_rng(6)
        N, F, B, W = 900, 6, 16, 8
        bins = r.integers(0, B, (F, N)).astype(np.uint8)
        packed = (bins[0::2] | (bins[1::2] << 4)).astype(np.uint8)
        mask = (r.uniform(size=N) > 0.3).astype(np.float32)
        g = r.normal(size=N).astype(np.float32) * mask
        h = mask if variant == "hilo3" else \
            r.uniform(0.2, 1.0, N).astype(np.float32) * mask
        leaf = r.integers(0, 4, N).astype(np.int32)
        tbl = np.zeros((18, W), np.int32)
        tbl[0] = [0, 1, 2, 3, -1, -1, -1, -1]
        tbl[1] = [4, 5, 6, 7, -1, -1, -1, -1]
        tbl[2] = r.integers(0, F, W)
        tbl[3] = r.integers(2, B - 2, W)
        tbl[7] = B
        tbl[8] = tbl[1]
        kw = dict(num_bins=B, chunk=256, interpret=True, variant=variant,
                  compact=True)
        lu, hu, _ = fused_partition_histogram_pallas(
            *_jx(bins, g, h, mask, leaf, tbl), **kw)
        lp, hp, _ = fused_partition_histogram_pallas(
            *_jx(packed, g, h, mask, leaf, tbl), packed4=True,
            num_features=F, **kw)
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lu))
        np.testing.assert_array_equal(np.asarray(hp), np.asarray(hu))

    def test_fused_xla_bitwise_vs_legacy_pipeline(self):
        """The XLA fused route == [apply_wave_splits ->
        wave_histogram_xla] bit-for-bit: partition ints AND histogram
        f32 bits (same membership, same combined-scatter order)."""
        (bins_t, g, h, mask, leaf, wl, new_ids, feat, tbin, dleft,
         meta, tbl, B, W) = self._fused_case()
        gm, hm = g * mask, h * mask
        iscat = np.zeros(W, bool)
        catw = np.zeros((W, 8), np.int32)
        lf, hf = fused_partition_histogram_xla(
            *_jx(bins_t, gm, hm, mask, leaf, wl, new_ids, feat, tbin,
                 dleft, iscat, catw, new_ids,
                 meta.missing_type[np.maximum(feat, 0)],
                 meta.default_bin[np.maximum(feat, 0)],
                 meta.num_bin[np.maximum(feat, 0)]),
            num_bins=B)
        meta_j = FeatureMeta(*[jnp.asarray(x) for x in meta])
        lu = apply_wave_splits(
            *_jx(bins_t, leaf, wl, new_ids, feat, tbin, dleft,
                 wl >= 0), meta_j)
        bag_leaf = jnp.where(jnp.asarray(mask) > 0, lu, -1)
        hu = wave_histogram_xla(
            *_jx(bins_t, gm, hm), bag_leaf, jnp.asarray(new_ids),
            num_bins=B)
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lu))
        np.testing.assert_array_equal(np.asarray(hf), np.asarray(hu))


# ---------------------------------------------------------------------------
# 2. fused-XLA route parity (grower + end-to-end)
# ---------------------------------------------------------------------------

def _grower_inputs(kind):
    r = np.random.default_rng(4)
    N, F, B = 3000, 8, 63
    bins = r.integers(0, B, (F, N)).astype(np.uint8)
    y = (bins[0].astype(float) / B + 0.3 * (bins[1] > 30)
         + 0.2 * r.normal(size=N) > 0.55).astype(np.float32)
    g = 0.5 - y
    h = np.full(N, 0.25, np.float32)
    mask = np.ones(N, np.float32)
    if kind == "bagging":
        mask = (r.random(N) < 0.7).astype(np.float32)
    meta = FeatureMeta(
        num_bin=np.full(F, B, np.int32),
        missing_type=np.array([0, 1, 2, 0, 1, 0, 2, 0], np.int32),
        default_bin=np.array([0, 3, 0, 0, 5, 0, 0, 0], np.int32),
        monotone=np.zeros(F, np.int32),
        penalty=np.ones(F, np.float32))
    return bins, g, h, mask, meta, B


@pytest.mark.parametrize("kind", ["plain", "bagging"])
@pytest.mark.parametrize("quant", [False, True])
def test_grower_fused_xla_route_bit_parity(kind, quant):
    """Whole-tree parity: the auto (fused-XLA) route and the forced
    legacy route grow IDENTICAL TreeRecords and leaf assignments."""
    bins, g, h, mask, meta, B = _grower_inputs(kind)
    F = bins.shape[0]
    kw = dict(num_leaves=31, num_bins=B, wave_size=8, hp=SplitParams(),
              precision="int8" if quant else "highest")
    ga = make_wave_grower(WaveGrowerConfig(**kw), meta)
    gl = make_wave_grower(WaveGrowerConfig(**kw, fused=False), meta)
    args = _jx(bins, g, h, mask) + (jnp.ones(F, bool),)
    ra, la = ga(*args)
    rl, ll = gl(*args)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(ll))
    for a, b in zip(ra, rl):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _awkward_data(kind, n=900, f=8, seed=7):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, f))
    if kind == "nan":
        X[r.random((n, f)) < 0.1] = np.nan
    elif kind == "neg_zero":
        X[:, 0] = np.where(r.random(n) < 0.3, -0.0, X[:, 0])
    elif kind == "categorical":
        X[:, 1] = r.integers(0, 9, n).astype(float)
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 2])
         + 0.2 * r.normal(size=n) > 0).astype(np.float32)
    return X, y


END_TO_END_GRID = [
    ("nan", {"objective": "binary"}),
    ("neg_zero", {"objective": "binary"}),
    ("categorical", {"objective": "binary",
                     "categorical_feature": "1"}),
    ("plain", {"objective": "multiclass", "num_class": 3}),
    ("plain", {"objective": "binary", "bagging_freq": 2,
               "bagging_fraction": 0.7}),
    ("plain", {"objective": "binary", "tpu_quantized_hist": True}),
]


def _trees(g):
    return g.model_to_string().split("parameters:")[0]


@pytest.mark.parametrize("kind,params", END_TO_END_GRID)
def test_end_to_end_variant_bit_parity(kind, params):
    """Pinned wave size => the variant choice changes ONLY the kernel
    channel layout (off-TPU: nothing at all), so hilo4-pinned training
    must reproduce hilo5-pinned training model-text-identically across
    the awkward-data grid — no silent semantics downgrade."""
    X, y = _awkward_data(kind)
    if params["objective"] == "multiclass":
        y = (np.abs(X[:, 0]) * 2 % 3 // 1).astype(np.float32)
    base = dict(params, num_leaves=15, tpu_wave_size=8)
    g5 = fit_gbdt(X, y, dict(base, tpu_exact_tier="hilo5"), num_round=6)
    g4 = fit_gbdt(X, y, dict(base, tpu_exact_tier="hilo4"), num_round=6)
    assert _trees(g5) == _trees(g4)


def test_end_to_end_hilo3_bit_parity_on_l1():
    """hilo3 engages for the constant-unit-hessian family and trains
    the same trees as hilo5 at a pinned wave size."""
    X, y = make_regression(900)
    base = {"objective": "regression_l1", "num_leaves": 15,
            "tpu_wave_size": 8}
    g5 = fit_gbdt(X, y, dict(base, tpu_exact_tier="hilo5"), num_round=6)
    g3 = fit_gbdt(X, y, dict(base, tpu_exact_tier="hilo3"), num_round=6)
    assert g3._grower_cfg.exact_variant == "hilo3"
    assert _trees(g5) == _trees(g3)


def test_packed4_hilo_kernel_bitwise():
    """The nibble-packed HBM tier composes with the exact hi/lo
    layouts: packed bins through the interpret wave kernel ==
    unpacked bins, bit-for-bit, for every variant."""
    r = np.random.default_rng(5)
    N, F, B = 777, 6, 16
    bins = r.integers(0, B, (F, N)).astype(np.uint8)
    packed = (bins[0::2] | (bins[1::2] << 4)).astype(np.uint8)
    g = r.normal(size=N).astype(np.float32)
    h = r.uniform(0.2, 1.0, N).astype(np.float32)
    leaf = r.integers(-1, 5, N).astype(np.int32)
    wl = np.array([0, 2, -1, 4, 1], np.int32)
    for variant in ("hilo5", "hilo4", "hilo3"):
        hv = h if variant != "hilo3" else (leaf >= 0).astype(np.float32)
        ref = np.asarray(wave_histogram_pallas(
            *_jx(bins, g, hv, leaf, wl), num_bins=B, chunk=256,
            interpret=True, variant=variant))
        got = np.asarray(wave_histogram_pallas(
            *_jx(packed, g, hv, leaf, wl), num_bins=B, chunk=256,
            interpret=True, variant=variant, packed4=True,
            num_features=F))
        np.testing.assert_array_equal(got, ref)


def test_packed4_engages_on_exact_tier_end_to_end():
    """max_bin <= 16 non-quantized training rides the packed-bins
    HBM tier under exact semantics — and trains the SAME model as
    unpacked bins."""
    X, y = make_binary(900, seed=9)
    base = {"objective": "binary", "max_bin": 15, "num_leaves": 15}
    gp = fit_gbdt(X, y, base, num_round=5)
    assert gp._grower_cfg.packed4, \
        "packed bins must auto-engage on the exact tier at max_bin<=16"
    assert gp._grower_cfg.precision == "highest"
    gu = fit_gbdt(X, y, dict(base, tpu_packed_bins=0), num_round=5)
    assert not gu._grower_cfg.packed4
    assert _trees(gp) == _trees(gu)


def test_auto_variant_selection_per_objective():
    """Auto (off-TPU analytic) rule: widest feasible wave — hilo3 for
    constant-unit-hessian objectives, hilo4 otherwise; hilo3 requests
    on a varying-hessian objective demote to hilo4 with a warning."""
    Xb, yb = make_binary(640)
    gb = fit_gbdt(Xb, yb, {"objective": "binary", "num_leaves": 63},
                  num_round=2)
    assert gb._grower_cfg.exact_variant == "hilo4"
    assert gb._grower_cfg.wave_size == 32

    Xr, yr = make_regression(640)
    gr = fit_gbdt(Xr, yr, {"objective": "regression",
                           "num_leaves": 63}, num_round=2)
    assert gr._grower_cfg.exact_variant == "hilo3"
    assert gr._grower_cfg.wave_size == 40

    g_demoted = fit_gbdt(Xb, yb, {"objective": "binary",
                                  "tpu_exact_tier": "hilo3"},
                         num_round=2)
    assert g_demoted._grower_cfg.exact_variant == "hilo4"

    gq = fit_gbdt(Xb, yb, {"objective": "binary",
                           "tpu_quantized_hist": True}, num_round=2)
    assert gq._grower_cfg.precision == "int8"


def test_weighted_rows_exclude_hilo3():
    """Row weights make h == w, not the mask — the objective reports
    non-constant hessians and the auto rule must not pick hilo3."""
    X, y = make_regression(640)
    w = np.random.default_rng(0).uniform(0.5, 2.0, len(y)) \
        .astype(np.float32)
    g = fit_gbdt(X, y, {"objective": "regression"}, num_round=2,
                 weight=w)
    assert g._grower_cfg.exact_variant == "hilo4"


# ---------------------------------------------------------------------------
# 3. tune_exact_tier selection + step-cache keying
# ---------------------------------------------------------------------------

class TestTuneExactTier:
    @pytest.fixture
    def fresh_tuner(self, tmp_path):
        """Isolated tuning cache; restores the module tuner after."""
        autotune.configure("on", str(tmp_path / "tuning.json"))
        yield
        autotune.configure("on", None)

    def test_requested_variant_honored_and_gated(self, fresh_tuner):
        assert autotune.tune_exact_tier(
            F=8, B=64, requested="hilo5") == "hilo5"
        assert autotune.tune_exact_tier(
            F=8, B=64, constant_hessian=True,
            requested="hilo3") == "hilo3"
        # hilo3 without the constant-hessian contract demotes
        assert autotune.tune_exact_tier(
            F=8, B=64, constant_hessian=False,
            requested="hilo3") == "hilo4"

    def test_mode_off_pins_hilo5(self, tmp_path):
        autotune.configure("off", str(tmp_path / "t.json"))
        try:
            assert autotune.tune_exact_tier(
                F=8, B=64, constant_hessian=True) == "hilo5"
        finally:
            autotune.configure("on", None)

    def test_fake_timer_selection_and_cache(self, fresh_tuner):
        """Injected timer: the fastest candidate wins; the second
        encounter of the key is served from the cache without timing
        anything."""
        calls = []

        def fake(cand):
            calls.append(cand["variant"])
            return {"hilo3": 3.0, "hilo4": 0.5, "hilo5": 2.0}[
                cand["variant"]]

        got = autotune.tune_exact_tier(
            F=8, B=64, constant_hessian=True, _measure=fake)
        assert got == "hilo4"
        assert sorted(calls) == ["hilo3", "hilo4", "hilo5"]
        calls.clear()
        again = autotune.tune_exact_tier(
            F=8, B=64, constant_hessian=True, _measure=fake)
        assert again == "hilo4"
        assert calls == [], "second encounter must be a cache hit"

    def test_candidate_set_excludes_hilo3_without_contract(self):
        cands = [c["variant"] for c in autotune.exact_tier_candidates(
            constant_hessian=False)]
        assert "hilo3" not in cands
        assert cands[0] == "hilo4"
        cands_c = [c["variant"] for c in autotune.exact_tier_candidates(
            constant_hessian=True)]
        assert cands_c[0] == "hilo3"

    @pytest.mark.parametrize("constant_hessian, want, timed", [
        (False, "hilo5", []), (True, "hilo3", ["hilo3", "hilo5"])],
        ids=["two_layouts", "constant_hessian"])
    def test_hilo4_is_not_timed_where_the_flush_dots_by_slot(
            self, fresh_tuner, constant_hessian, want, timed):
        """At a shape the fused kernel's flush dots a slot at a time
        (67 x 255: it compacts, and the root's split applies) hilo4 is
        hilo5's dot at a wider wave, not a layout: the timed arm leaves
        it out (one candidate left: nothing is timed), though the fake
        timer would have it win; asked for by name it still runs. A
        narrow shape (8 x 64 above) times all of them as before."""
        calls = []

        def fake(cand):
            calls.append(cand["variant"])
            return {"hilo3": 1.0, "hilo4": 0.5, "hilo5": 2.0}[
                cand["variant"]]

        assert autotune.tune_exact_tier(
            F=67, B=255, constant_hessian=constant_hessian,
            _measure=fake) == want
        assert sorted(calls) == timed
        assert autotune.tune_exact_tier(
            F=67, B=255, requested="hilo4") == "hilo4"
        assert [c["variant"] for c in autotune.exact_tier_candidates(
            constant_hessian=False, by_slot=True)] == ["hilo5"]

    def test_failed_candidates_fall_back(self, fresh_tuner):
        def broken(cand):
            raise RuntimeError("mosaic says no")

        assert autotune.tune_exact_tier(
            F=9, B=64, constant_hessian=False,
            _measure=broken) == "hilo5"

    def test_vmem_pricing_accounts_hilo4_count_accumulator(self):
        geom = autotune.hist_geometry(F=28, B=64, W=32)
        base = autotune.hist_vmem_bytes(chunk=8192, geom=geom, W=32,
                                        fused=True, variant="hilo5")
        with_cnt = autotune.hist_vmem_bytes(chunk=8192, geom=geom,
                                            W=32, fused=True,
                                            variant="hilo4")
        assert with_cnt > base


class TestStepCacheKeying:
    def _delta(self, fn):
        s0 = step_cache.stats()
        out = fn()
        s1 = step_cache.stats()
        return out, {k: s1[k] - s0[k] for k in ("hits", "misses")}

    def test_variant_rides_geometry_key(self):
        """Different exact-tier variants are DIFFERENT compiled steps
        (no cross-variant contamination), and each variant's retrain
        is a pure registry hit — compiled-step reuse survives the
        tuner picking different variants for different geometries."""
        X, y = make_binary(640, seed=21)
        _, d5 = self._delta(lambda: fit_gbdt(
            X, y, {"objective": "binary", "tpu_wave_size": 8,
                   "tpu_exact_tier": "hilo5"}, num_round=3))
        assert d5["misses"] >= 1
        _, d4 = self._delta(lambda: fit_gbdt(
            X, y, {"objective": "binary", "tpu_wave_size": 8,
                   "tpu_exact_tier": "hilo4"}, num_round=3))
        assert d4["misses"] >= 1, \
            "a different variant must not hit the other's step"
        _, d5b = self._delta(lambda: fit_gbdt(
            X, y, {"objective": "binary", "tpu_wave_size": 8,
                   "tpu_exact_tier": "hilo5"}, num_round=3))
        assert d5b["misses"] == 0 and d5b["hits"] >= 1
        _, d4b = self._delta(lambda: fit_gbdt(
            X, y, {"objective": "binary", "tpu_wave_size": 8,
                   "tpu_exact_tier": "hilo4"}, num_round=3))
        assert d4b["misses"] == 0 and d4b["hits"] >= 1

    def test_auto_variant_reuse_across_boosters(self):
        """The auto-picked variant is deterministic per geometry, so
        the sliding-window pattern (fresh booster, same shape) stays a
        registry hit."""
        X, y = make_binary(640, seed=22)
        g1, _ = self._delta(lambda: fit_gbdt(
            X, y, {"objective": "binary"}, num_round=3))
        g2, d2 = self._delta(lambda: fit_gbdt(
            X, y, {"objective": "binary"}, num_round=3))
        assert d2["misses"] == 0 and d2["hits"] >= 1
        assert g1._grower_cfg.exact_variant \
            == g2._grower_cfg.exact_variant
        assert _trees(g1) == _trees(g2)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_config_validates_exact_tier_knob():
    from lightgbm_tpu.config import Config
    cfg = Config().set({"tpu_exact_tier": "hilo9"})
    assert cfg.tpu_exact_tier == ""          # warned + reset to auto
    cfg = Config().set({"tpu_exact_tier": "hilo4"})
    assert cfg.tpu_exact_tier == "hilo4"


def test_config_refuses_bad_tier_combos_at_param_time():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError, match="tpu_quantized_hist"):
        Config().set({"tpu_count_proxy": 1})
    with pytest.raises(LightGBMError, match="max_bin"):
        Config().set({"tpu_packed_bins": 1})     # default max_bin 255
    with pytest.raises(LightGBMError, match="count-proxy"):
        Config().set({"tpu_packed_bins": 1, "tpu_quantized_hist": True,
                      "tpu_count_proxy": 0, "max_bin": 15})
    with pytest.raises(LightGBMError, match="tpu_use_dp"):
        Config().set({"tpu_packed_bins": 1, "tpu_use_dp": False,
                      "max_bin": 15})
    # the valid combos still parse: count-proxy int8, and the hi/lo
    # exact tier (the packed-bins hilo tier this PR adds)
    cfg = Config().set({"tpu_packed_bins": 1,
                        "tpu_quantized_hist": True, "max_bin": 15})
    assert cfg.tpu_packed_bins == 1
    cfg = Config().set({"tpu_packed_bins": 1, "max_bin": 15})
    assert cfg.tpu_packed_bins == 1 and cfg.tpu_use_dp
