"""Wave histogram / fused kernel / wave grower regression tests.

Promotes the round-2 scratch parity checks into the collected suite
(VERDICT r2 weak #5) and adds coverage for the fused partition+histogram
kernel (hist_wave.py) now wired into the grower. The Pallas kernels run
in interpret mode on the CPU test backend — same code path as TPU, with
HIGHEST-precision dots standing in for the MXU's exact bf16 products.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.ops.hist_wave import (fused_partition_histogram_pallas,
                                        wave_histogram_pallas,
                                        wave_histogram_xla)
from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                          apply_wave_splits,
                                          make_wave_grower)


def _numpy_hist(bins_t, g, h, leaf, wl, B):
    """Per-slot histogram oracle (plain loops)."""
    W, F = len(wl), bins_t.shape[0]
    out = np.zeros((W, F, B, 3), np.float64)
    for k, l in enumerate(wl):
        if l < 0:
            continue
        m = leaf == l
        for f in range(F):
            out[k, f, :, 0] = np.bincount(
                bins_t[f, m], weights=g[m], minlength=B)[:B]
            out[k, f, :, 1] = np.bincount(
                bins_t[f, m], weights=h[m], minlength=B)[:B]
            out[k, f, :, 2] = np.bincount(bins_t[f, m], minlength=B)[:B]
    return out


def _problem(N=777, F=6, B=63, n_leaves=5, seed=3):
    r = np.random.default_rng(seed)
    bins_t = r.integers(0, B, (F, N)).astype(np.uint8)
    g = r.normal(size=N).astype(np.float32)
    h = r.uniform(0.2, 1.0, N).astype(np.float32)
    leaf = r.integers(-1, n_leaves, N).astype(np.int32)
    return bins_t, g, h, leaf


class TestWaveHistogram:
    def test_xla_matches_numpy_oracle(self):
        bins_t, g, h, leaf = _problem()
        wl = np.array([0, 2, -1, 4, 1], np.int32)
        out = np.asarray(wave_histogram_xla(
            jnp.asarray(bins_t), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(leaf), jnp.asarray(wl), num_bins=64))
        ref = _numpy_hist(bins_t, g, h, leaf, wl, 64)
        np.testing.assert_allclose(out, ref, atol=2e-4)
        np.testing.assert_array_equal(out[..., 2], ref[..., 2])

    @pytest.mark.parametrize("precision", ["highest", "default"])
    def test_pallas_matches_xla(self, precision):
        bins_t, g, h, leaf = _problem()
        wl = np.array([0, 2, -1, 4, 1], np.int32)
        args = (jnp.asarray(bins_t), jnp.asarray(g), jnp.asarray(h),
                jnp.asarray(leaf), jnp.asarray(wl))
        ref = np.asarray(wave_histogram_xla(*args, num_bins=64))
        out = np.asarray(wave_histogram_pallas(
            *args, num_bins=64, chunk=256, interpret=True,
            precision=precision))
        np.testing.assert_array_equal(out[..., 2], ref[..., 2])
        np.testing.assert_allclose(out, ref, atol=1e-4)


class TestFusedKernel:
    def test_fused_matches_unfused(self):
        """Partition bit-exact, histograms f32-grade vs the unfused
        (apply_wave_splits + wave_histogram_xla) pipeline."""
        r = np.random.default_rng(0)
        N, F, B, W = 999, 5, 64, 8
        bins_t = r.integers(0, 63, (F, N)).astype(np.uint8)
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.1, 1, N).astype(np.float32)
        mask = (r.uniform(size=N) > 0.3).astype(np.float32)
        leaf = r.integers(0, 4, N).astype(np.int32)
        meta_np = FeatureMeta(
            num_bin=np.full(F, 64, np.int32),
            missing_type=np.array([0, 1, 2, 0, 1], np.int32),
            default_bin=np.array([0, 3, 0, 0, 5], np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        meta = FeatureMeta(*[jnp.asarray(x) for x in meta_np])
        wl = np.array([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
        new_ids = np.array([4, 5, 6, 7, -1, -1, -1, -1], np.int32)
        feat = r.integers(0, F, W).astype(np.int32)
        tbin = r.integers(0, 60, W).astype(np.int32)
        dleft = r.integers(0, 2, W).astype(bool)
        small = new_ids.copy()

        gm, hm = g * mask, h * mask
        tbl = jnp.stack([jnp.asarray(x) for x in [
            wl, new_ids, feat, tbin, dleft.astype(np.int32),
            meta_np.missing_type[feat], meta_np.default_bin[feat],
            meta_np.num_bin[feat], small]])
        leaf_f, hist_f, _ = fused_partition_histogram_pallas(
            jnp.asarray(bins_t), jnp.asarray(gm),
            jnp.asarray(hm), jnp.asarray(mask), jnp.asarray(leaf), tbl,
            num_bins=B, chunk=256, interpret=True)

        leaf_u = apply_wave_splits(
            jnp.asarray(bins_t), jnp.asarray(leaf), jnp.asarray(wl),
            jnp.asarray(new_ids), jnp.asarray(feat), jnp.asarray(tbin),
            jnp.asarray(dleft), jnp.asarray(wl >= 0), meta)
        bag_leaf = jnp.where(jnp.asarray(mask) > 0, leaf_u, -1)
        hist_u = wave_histogram_xla(
            jnp.asarray(bins_t), jnp.asarray(gm), jnp.asarray(hm),
            bag_leaf, jnp.asarray(small), num_bins=B)

        np.testing.assert_array_equal(np.asarray(leaf_f),
                                      np.asarray(leaf_u))
        hf, hu = np.asarray(hist_f), np.asarray(hist_u)
        np.testing.assert_array_equal(hf[..., 2], hu[..., 2])
        np.testing.assert_allclose(hf, hu, atol=5e-5)


def _grower_problem():
    r = np.random.default_rng(0)
    N, F, B = 3000, 8, 63
    bins = r.integers(0, B, (N, F)).astype(np.uint8)
    logit = (bins[:, 0].astype(float) / B - 0.5
             + 0.3 * (bins[:, 1] > 30) - 0.2 * (bins[:, 2] < 10))
    y = (logit + 0.3 * r.normal(size=N) > 0).astype(np.float32)
    grad = jnp.asarray(0.5 - y)
    hess = jnp.full(N, 0.25, jnp.float32)
    mask = jnp.asarray((r.random(N) < 0.8).astype(np.float32))
    fmask = jnp.ones(F, bool)
    meta = FeatureMeta(
        num_bin=np.full(F, B, np.int32),
        missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        monotone=np.zeros(F, np.int32),
        penalty=np.ones(F, np.float32))
    return bins, grad, hess, mask, fmask, meta, B


class TestWaveGrower:
    def test_wave1_matches_legacy_grower(self):
        """W=1 reproduces the round-1 strict leaf-wise grower exactly
        (the correctness oracle relationship from scratch/, promoted)."""
        bins, grad, hess, mask, fmask, meta, B = _grower_problem()
        L = 31
        hp = SplitParams(min_data_in_leaf=20)
        old = make_tree_grower(
            GrowerConfig(num_leaves=L, num_bins=B, chunk=bins.shape[0],
                         hp=hp), meta)
        rec_o, leaf_o = old(jnp.asarray(bins), grad, hess, mask, fmask)
        new = make_wave_grower(
            WaveGrowerConfig(num_leaves=L, num_bins=B, wave_size=1,
                             hp=hp), meta)
        rec_n, leaf_n = new(jnp.asarray(bins.T.copy()), grad, hess,
                            mask, fmask)
        assert int(rec_o.num_leaves) == int(rec_n.num_leaves)
        np.testing.assert_array_equal(np.asarray(rec_o.split_feature),
                                      np.asarray(rec_n.split_feature))
        np.testing.assert_array_equal(np.asarray(rec_o.split_bin),
                                      np.asarray(rec_n.split_bin))
        np.testing.assert_array_equal(np.asarray(leaf_o),
                                      np.asarray(leaf_n))
        np.testing.assert_allclose(np.asarray(rec_o.leaf_output),
                                   np.asarray(rec_n.leaf_output),
                                   atol=1e-5)

    def test_wave_batched_quality(self):
        """W>1 trees reach the same total gain grade as W=1 (waves split
        in gain order; only budget-boundary choices may differ)."""
        bins, grad, hess, mask, fmask, meta, B = _grower_problem()
        L = 31
        hp = SplitParams(min_data_in_leaf=20)
        gains = {}
        for W in (1, 8):
            gr = make_wave_grower(
                WaveGrowerConfig(num_leaves=L, num_bins=B, wave_size=W,
                                 hp=hp), meta)
            rec, _ = gr(jnp.asarray(bins.T.copy()), grad, hess, mask,
                        fmask)
            gains[W] = float(np.asarray(rec.split_gain).sum())
            assert int(rec.num_leaves) == L
        assert gains[8] >= 0.95 * gains[1]

    def test_fused_grower_matches_unfused(self):
        """The fused Pallas grower path (interpret mode) grows the same
        tree as the unfused path."""
        bins, grad, hess, mask, fmask, meta, B = _grower_problem()
        L = 15
        hp = SplitParams(min_data_in_leaf=20)
        base = make_wave_grower(
            WaveGrowerConfig(num_leaves=L, num_bins=B, wave_size=8,
                             hp=hp, fused=False), meta)
        rec_b, leaf_b = base(jnp.asarray(bins.T.copy()), grad, hess,
                             mask, fmask)
        fused = make_wave_grower(
            WaveGrowerConfig(num_leaves=L, num_bins=B, wave_size=8,
                             hp=hp, fused=True, chunk=1024), meta)
        rec_f, leaf_f = fused(jnp.asarray(bins.T.copy()), grad, hess,
                              mask, fmask)
        assert int(rec_b.num_leaves) == int(rec_f.num_leaves)
        np.testing.assert_array_equal(np.asarray(rec_b.split_feature),
                                      np.asarray(rec_f.split_feature))
        np.testing.assert_array_equal(np.asarray(rec_b.split_bin),
                                      np.asarray(rec_f.split_bin))
        np.testing.assert_array_equal(np.asarray(leaf_b),
                                      np.asarray(leaf_f))
        np.testing.assert_allclose(np.asarray(rec_f.leaf_output),
                                   np.asarray(rec_b.leaf_output),
                                   atol=1e-4)


class TestLeafGather:
    def test_pallas_matches_xla_gather(self):
        from lightgbm_tpu.ops.predict import leaf_gather_pallas
        r = np.random.default_rng(9)
        table = r.normal(size=255).astype(np.float32)
        ids = r.integers(0, 255, 100_001).astype(np.int32)
        out = np.asarray(leaf_gather_pallas(
            jnp.asarray(table), jnp.asarray(ids), interpret=True))
        np.testing.assert_array_equal(out, table[ids])

    def test_out_of_range_ids_zero(self):
        from lightgbm_tpu.ops.predict import leaf_gather_pallas
        table = jnp.asarray([1.0, 2.0, 3.0])
        ids = jnp.asarray([0, -1, 2, 7, 1], jnp.int32)
        out = np.asarray(leaf_gather_pallas(table, ids, interpret=True))
        np.testing.assert_array_equal(out, [1.0, 0.0, 3.0, 0.0, 2.0])


class TestInt8Histogram:
    """tpu_quantized_hist kernels: int8 MXU products must reproduce the
    exact integer sums of the XLA scatter oracle."""

    def _qproblem(self):
        r = np.random.default_rng(11)
        N, F = 777, 6
        bins_t = r.integers(0, 63, (F, N)).astype(np.uint8)
        gq = r.integers(-127, 128, N).astype(np.float32)
        hq = r.integers(0, 128, N).astype(np.float32)
        leaf = r.integers(-1, 5, N).astype(np.int32)
        mask = (leaf >= 0).astype(np.float32)
        return bins_t, gq, hq, leaf, mask

    def test_wave_int8_matches_xla(self):
        bins_t, gq, hq, leaf, _ = self._qproblem()
        wl = np.array([0, 2, -1, 4, 1], np.int32)
        args = (jnp.asarray(bins_t), jnp.asarray(gq), jnp.asarray(hq),
                jnp.asarray(leaf), jnp.asarray(wl))
        ref = np.asarray(wave_histogram_xla(*args, num_bins=64))
        sg, sh = 0.5, 0.25
        out = np.asarray(wave_histogram_pallas(
            *args, num_bins=64, chunk=256, interpret=True,
            precision="int8", gh_scale=(sg, sh)))
        np.testing.assert_array_equal(out[..., 2], ref[..., 2])
        np.testing.assert_allclose(out[..., 0], ref[..., 0] * sg,
                                   rtol=1e-6)
        np.testing.assert_allclose(out[..., 1], ref[..., 1] * sh,
                                   rtol=1e-6)

    def test_fused_int8_matches_xla(self):
        from lightgbm_tpu.ops.hist_wave import (
            fused_partition_histogram_pallas)
        from lightgbm_tpu.ops.wave_grower import apply_wave_splits
        bins_t, gq, hq, leaf, mask = self._qproblem()
        F = bins_t.shape[0]
        meta_np = FeatureMeta(
            num_bin=np.full(F, 64, np.int32),
            missing_type=np.zeros(F, np.int32),
            default_bin=np.zeros(F, np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        meta = FeatureMeta(*[jnp.asarray(x) for x in meta_np])
        W = 8
        wl = np.array([0, 1, 2, 3, 4, -1, -1, -1], np.int32)
        new_ids = np.array([5, 6, 7, 8, 9, -1, -1, -1], np.int32)
        r = np.random.default_rng(12)
        feat = r.integers(0, F, W).astype(np.int32)
        tbin = r.integers(0, 60, W).astype(np.int32)
        dleft = np.zeros(W, bool)
        small = new_ids.copy()
        gm, hm = gq * mask, hq * mask
        tbl = jnp.stack([jnp.asarray(x) for x in [
            wl, new_ids, feat, tbin, dleft.astype(np.int32),
            meta_np.missing_type[feat], meta_np.default_bin[feat],
            meta_np.num_bin[feat], small,
            np.zeros(W, np.int32)]])
        leaf0 = np.where(mask > 0, leaf, 0).astype(np.int32)
        sg, sh = 0.125, 2.0
        leaf_f, hist_f, _ = fused_partition_histogram_pallas(
            jnp.asarray(bins_t), jnp.asarray(gm), jnp.asarray(hm),
            jnp.asarray(mask), jnp.asarray(leaf0), tbl,
            num_bins=64, chunk=256, interpret=True,
            precision="int8", gh_scale=(sg, sh))
        leaf_u = apply_wave_splits(
            jnp.asarray(bins_t), jnp.asarray(leaf0), jnp.asarray(wl),
            jnp.asarray(new_ids), jnp.asarray(feat), jnp.asarray(tbin),
            jnp.asarray(dleft), jnp.asarray(wl >= 0), meta)
        bag_leaf = jnp.where(jnp.asarray(mask) > 0, leaf_u, -1)
        hist_u = np.asarray(wave_histogram_xla(
            jnp.asarray(bins_t), jnp.asarray(gm), jnp.asarray(hm),
            bag_leaf, jnp.asarray(small), num_bins=64))
        np.testing.assert_array_equal(np.asarray(leaf_f),
                                      np.asarray(leaf_u))
        hf = np.asarray(hist_f)
        np.testing.assert_array_equal(hf[..., 2], hist_u[..., 2])
        np.testing.assert_allclose(hf[..., 0], hist_u[..., 0] * sg,
                                   rtol=1e-6)
        np.testing.assert_allclose(hf[..., 1], hist_u[..., 1] * sh,
                                   rtol=1e-6)

    def test_quantized_grower_quality(self):
        """End-to-end: int8-precision wave grower reaches f32-grade
        split quality on a separable problem (XLA fallback path — the
        same quantization code the TPU kernel path runs)."""
        from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                                  make_wave_grower)
        bins, grad, hess, mask, fmask, meta, B = _grower_problem()
        bins_t = jnp.asarray(np.ascontiguousarray(bins.T))
        outs = {}
        for prec in ("highest", "int8"):
            cfg = WaveGrowerConfig(num_leaves=15, num_bins=B,
                                   wave_size=8, precision=prec)
            grow = make_wave_grower(cfg, meta)
            rec, leaf_ids = grow(bins_t, grad, hess, mask, fmask)
            outs[prec] = rec
        exact, quant = outs["highest"], outs["int8"]
        assert int(quant.num_leaves) >= 12
        # same dominant split structure: root feature agrees
        assert int(quant.split_feature[0]) == int(exact.split_feature[0])
        # leaf outputs close in aggregate
        np.testing.assert_allclose(
            np.sort(np.asarray(quant.leaf_output)[:12]),
            np.sort(np.asarray(exact.leaf_output)[:12]), atol=0.05)


class TestWideBins:
    def test_fused_wide_bin_tier_exact(self):
        """>256 bins: the partition must stay exact (the bf16 MXU
        row-gather only covers the uint8 tier)."""
        from lightgbm_tpu.ops.hist_wave import (
            fused_partition_histogram_pallas)
        from lightgbm_tpu.ops.wave_grower import apply_wave_splits
        r = np.random.default_rng(31)
        N, F, B, W = 700, 4, 320, 8
        bins_t = r.integers(0, B, (F, N)).astype(np.int32)
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.1, 1, N).astype(np.float32)
        mask = np.ones(N, np.float32)
        leaf = r.integers(0, 4, N).astype(np.int32)
        meta_np = FeatureMeta(
            num_bin=np.full(F, B, np.int32),
            missing_type=np.zeros(F, np.int32),
            default_bin=np.zeros(F, np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        meta = FeatureMeta(*[jnp.asarray(x) for x in meta_np])
        wl = np.array([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
        new_ids = np.array([4, 5, 6, 7, -1, -1, -1, -1], np.int32)
        feat = r.integers(0, F, W).astype(np.int32)
        # thresholds far above 256 exercise the wide tier
        tbin = r.integers(250, 310, W).astype(np.int32)
        dleft = np.zeros(W, bool)
        tbl = jnp.stack([jnp.asarray(x) for x in [
            wl, new_ids, feat, tbin, dleft.astype(np.int32),
            meta_np.missing_type[feat], meta_np.default_bin[feat],
            meta_np.num_bin[feat], new_ids,
            np.zeros(W, np.int32)]])
        leaf_f, *_ = fused_partition_histogram_pallas(
            jnp.asarray(bins_t), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(mask), jnp.asarray(leaf), tbl,
            num_bins=B, chunk=256, interpret=True)
        leaf_u = apply_wave_splits(
            jnp.asarray(bins_t), jnp.asarray(leaf), jnp.asarray(wl),
            jnp.asarray(new_ids), jnp.asarray(feat), jnp.asarray(tbin),
            jnp.asarray(dleft), jnp.asarray(wl >= 0), meta)
        np.testing.assert_array_equal(np.asarray(leaf_f),
                                      np.asarray(leaf_u))


class TestCountProxy:
    """count-proxy int8 mode: the MXU dot carries only g/h (2 channels,
    waves up to 64); per-bin counts are hessian-proportional estimates
    and per-leaf counts stay exact via partition-mask counting."""

    def _qproblem(self, n=3000, F=5, seed=3):
        r = np.random.default_rng(seed)
        bins_t = r.integers(0, 64, (F, n), dtype=np.uint8)
        gq = r.integers(-127, 128, n).astype(np.float32)
        hq = r.integers(0, 128, n).astype(np.float32)
        leaf = r.integers(0, 5, n).astype(np.int32)
        mask = (r.random(n) < 0.8).astype(np.float32)
        return bins_t, gq, hq, leaf, mask

    def test_fused_proxy_kernel_matches_xla_gh_and_counts(self):
        from lightgbm_tpu.ops.hist_wave import (
            fused_partition_histogram_pallas, wave_histogram_xla)
        from lightgbm_tpu.ops.wave_grower import apply_wave_splits
        from lightgbm_tpu.ops.split import FeatureMeta
        bins_t, gq, hq, leaf, mask = self._qproblem()
        F = bins_t.shape[0]
        meta_np = FeatureMeta(
            num_bin=np.full(F, 64, np.int32),
            missing_type=np.zeros(F, np.int32),
            default_bin=np.zeros(F, np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        meta = FeatureMeta(*[jnp.asarray(x) for x in meta_np])
        W = 16
        wl = np.full(W, -1, np.int32); wl[:5] = np.arange(5)
        new_ids = np.full(W, -1, np.int32)
        new_ids[:5] = np.arange(5, 10)
        r = np.random.default_rng(12)
        feat = r.integers(0, F, W).astype(np.int32)
        tbin = r.integers(0, 60, W).astype(np.int32)
        dleft = np.zeros(W, bool)
        gm, hm = gq * mask, hq * mask
        tbl = jnp.stack([jnp.asarray(x) for x in [
            wl, new_ids, feat, tbin, dleft.astype(np.int32),
            meta_np.missing_type[feat], meta_np.default_bin[feat],
            meta_np.num_bin[feat], new_ids,
            np.zeros(W, np.int32)]])
        leaf0 = np.where(mask > 0, leaf, 0).astype(np.int32)
        sg, sh = 0.125, 2.0
        leaf_f, hist_f, cnt_r, _ = fused_partition_histogram_pallas(
            jnp.asarray(bins_t), jnp.asarray(gm), jnp.asarray(hm),
            jnp.asarray(mask), jnp.asarray(leaf0), tbl,
            num_bins=64, chunk=256, interpret=True,
            precision="int8", gh_scale=(sg, sh), count_proxy=True)
        assert hist_f.shape[-1] == 2
        leaf_u = apply_wave_splits(
            jnp.asarray(bins_t), jnp.asarray(leaf0), jnp.asarray(wl),
            jnp.asarray(new_ids), jnp.asarray(feat), jnp.asarray(tbin),
            jnp.asarray(dleft), jnp.asarray(wl >= 0), meta)
        np.testing.assert_array_equal(np.asarray(leaf_f),
                                      np.asarray(leaf_u))
        bag_leaf = jnp.where(jnp.asarray(mask) > 0, leaf_u, -1)
        hist_u = np.asarray(wave_histogram_xla(
            jnp.asarray(bins_t), jnp.asarray(gm), jnp.asarray(hm),
            bag_leaf, jnp.asarray(new_ids), num_bins=64))
        np.testing.assert_allclose(np.asarray(hist_f[..., 0]),
                                   hist_u[..., 0] * sg, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(hist_f[..., 1]),
                                   hist_u[..., 1] * sh, rtol=1e-6)
        # exact right-child counts = in-bag rows that landed on new ids
        lu = np.asarray(leaf_u)
        want = np.array([((lu == ni) & (mask > 0)).sum() if ni >= 0
                         else 0 for ni in new_ids], np.float32)
        np.testing.assert_array_equal(np.asarray(cnt_r), want)

    def _grow(self, count_proxy, W, n=4000, F=6, fused=True):
        from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
        from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                                  make_wave_grower)
        r = np.random.default_rng(9)
        bins = r.integers(0, 64, (n, F)).astype(np.uint8)
        x = bins[:, 0].astype(np.float32) / 64.0
        y = ((x + 0.3 * (bins[:, 1] > 40) + 0.1 * r.normal(size=n)) > 0.6)
        p = np.full(n, 0.5, np.float32)
        grad = (p - y).astype(np.float32)
        hess = (p * (1 - p)).astype(np.float32)
        # integer-quantize g/h exactly like the grower's quant step
        # does not matter here: feed pre-quantized integer g/h so the
        # proxy and exact paths see identical inputs
        gq = np.round(grad * 127).astype(np.float32)
        hq = np.maximum(np.round(hess * 127), 1).astype(np.float32)
        meta = FeatureMeta(
            num_bin=np.full(F, 64, np.int32),
            missing_type=np.zeros(F, np.int32),
            default_bin=np.zeros(F, np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        hp = SplitParams(min_data_in_leaf=0, min_sum_hessian_in_leaf=0.0,
                         count_lb=count_proxy)
        cfg = WaveGrowerConfig(
            num_leaves=31, num_bins=64, wave_size=W, hp=hp,
            precision="int8", fused=fused, chunk=512,
            count_proxy=count_proxy)
        grow = make_wave_grower(cfg, meta)
        mask = np.ones(n, np.float32)
        rec, leaf_ids = grow(jnp.asarray(bins.T.copy()),
                             jnp.asarray(gq), jnp.asarray(hq),
                             jnp.asarray(mask),
                             jnp.ones(F, bool))
        return rec, np.asarray(leaf_ids)

    def test_proxy_grower_matches_exact_when_gates_idle(self):
        """With min_data_in_leaf=1 the count gate never binds, so the
        proxy grower must build the IDENTICAL tree to the exact int8
        grower — per-bin counts only ever feed that gate."""
        rec_e, leaf_e = self._grow(count_proxy=False, W=8)
        rec_p, leaf_p = self._grow(count_proxy=True, W=8)
        assert int(rec_p.num_leaves) == int(rec_e.num_leaves)
        np.testing.assert_array_equal(leaf_p, leaf_e)
        np.testing.assert_array_equal(np.asarray(rec_p.split_feature),
                                      np.asarray(rec_e.split_feature))
        np.testing.assert_array_equal(np.asarray(rec_p.split_bin),
                                      np.asarray(rec_e.split_bin))
        np.testing.assert_allclose(np.asarray(rec_p.leaf_output),
                                   np.asarray(rec_e.leaf_output),
                                   rtol=1e-5, atol=1e-7)

    def test_proxy_leaf_counts_exact(self):
        """leaf_count / internal_count come from partition-mask
        counting and must equal a host recount of leaf_ids."""
        rec, leaf_ids = self._grow(count_proxy=True, W=16)
        nl = int(rec.num_leaves)
        counts = np.asarray(rec.leaf_count)[:nl]
        recount = np.array([(leaf_ids == k).sum() for k in range(nl)],
                           np.float32)
        np.testing.assert_array_equal(counts, recount)

    def test_proxy_unfused_oracle_path(self):
        """The XLA-oracle (non-fused) proxy path agrees with the fused
        interpret path."""
        rec_f, leaf_f = self._grow(count_proxy=True, W=8, fused=True)
        rec_u, leaf_u = self._grow(count_proxy=True, W=8, fused=False)
        np.testing.assert_array_equal(leaf_f, leaf_u)
        np.testing.assert_array_equal(np.asarray(rec_f.split_feature),
                                      np.asarray(rec_u.split_feature))


class TestPacked4:
    """4-bit packed HBM bins (count-proxy tier): two features per byte,
    nibble-unpack in the kernel; must grow IDENTICAL trees to the
    unpacked uint8 tier."""

    def _grow(self, packed, W=8, n=3000, F=5, fused=True):
        from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
        from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                                  make_wave_grower)
        r = np.random.default_rng(21)
        bins = r.integers(0, 16, (F, n)).astype(np.uint8)
        gq = r.integers(-127, 128, n).astype(np.float32)
        hq = r.integers(1, 128, n).astype(np.float32)
        meta = FeatureMeta(
            num_bin=np.full(F, 16, np.int32),
            missing_type=np.zeros(F, np.int32),
            default_bin=np.zeros(F, np.int32),
            monotone=np.zeros(F, np.int32),
            penalty=np.ones(F, np.float32))
        hp = SplitParams(min_data_in_leaf=0, min_sum_hessian_in_leaf=0.0,
                         count_lb=True)
        cfg = WaveGrowerConfig(
            num_leaves=15, num_bins=16, wave_size=W, hp=hp,
            precision="int8", fused=fused, chunk=512,
            count_proxy=True, packed4=packed)
        grow = make_wave_grower(cfg, meta)
        if packed:
            b = bins if F % 2 == 0 else np.concatenate(
                [bins, np.zeros((1, n), np.uint8)])
            dev_bins = jnp.asarray(b[0::2] | (b[1::2] << 4))
        else:
            dev_bins = jnp.asarray(bins)
        rec, leaf = grow(dev_bins, jnp.asarray(gq), jnp.asarray(hq),
                         jnp.ones(n, jnp.float32), jnp.ones(F, bool))
        return rec, np.asarray(leaf)

    def test_packed_fused_matches_unpacked(self):
        rec_u, leaf_u = self._grow(packed=False)
        rec_p, leaf_p = self._grow(packed=True)
        assert int(rec_p.num_leaves) == int(rec_u.num_leaves)
        np.testing.assert_array_equal(leaf_p, leaf_u)
        np.testing.assert_array_equal(np.asarray(rec_p.split_feature),
                                      np.asarray(rec_u.split_feature))
        np.testing.assert_array_equal(np.asarray(rec_p.split_bin),
                                      np.asarray(rec_u.split_bin))
        np.testing.assert_allclose(np.asarray(rec_p.leaf_output),
                                   np.asarray(rec_u.leaf_output),
                                   rtol=1e-5, atol=1e-7)

    def test_packed_unfused_fallback_matches(self):
        """The non-fused path unpacks up front and must agree too."""
        rec_p, leaf_p = self._grow(packed=True, fused=True)
        rec_q, leaf_q = self._grow(packed=True, fused=False)
        np.testing.assert_array_equal(leaf_p, leaf_q)
        np.testing.assert_array_equal(np.asarray(rec_p.split_feature),
                                      np.asarray(rec_q.split_feature))

    def test_gbdt_packs_and_matches_unpacked(self):
        """End-to-end: max_bin=15 + quantized training auto-packs the
        HBM bins (halved first axis) and trains the same model as
        tpu_packed_bins=0."""
        from conftest import fit_gbdt, make_binary
        X, y = make_binary(n=1500, f=6, seed=9)
        params = {"objective": "binary", "metric": "auc", "max_bin": 15,
                  "tpu_quantized_hist": True}
        gp = fit_gbdt(X, y, params, num_round=10)
        gu = fit_gbdt(X, y, dict(params, tpu_packed_bins=0),
                      num_round=10)
        assert gp._grower_cfg.packed4
        assert not gu._grower_cfg.packed4
        assert gp._bins_dev.shape[0] == (gu._bins_dev.shape[0] + 1) // 2
        np.testing.assert_allclose(
            np.asarray(gp.predict_raw(X[:200])),
            np.asarray(gu.predict_raw(X[:200])), atol=1e-6)

    def test_gbdt_packed_early_stop_trim_replays_correctly(self):
        """The early-stopping trim (and refit/continued training)
        replay the partition on the TRAINING bins — with the 4-bit tier
        those must be nibble-unpacked first (regression: reading packed
        bytes as [F, N] bin codes silently corrupted scores)."""
        from conftest import fit_gbdt, make_binary
        X, y = make_binary(n=1500, f=6, seed=15)
        params = {"objective": "binary", "metric": "auc", "max_bin": 15,
                  "tpu_quantized_hist": True}
        gp = fit_gbdt(X, y, params, num_round=10)
        gu = fit_gbdt(X, y, dict(params, tpu_packed_bins=0),
                      num_round=10)
        assert gp._grower_cfg.packed4
        gp._drop_last_iterations(3)     # replays partition on train bins
        gu._drop_last_iterations(3)
        np.testing.assert_allclose(
            np.asarray(gp.predict_raw(X[:200])),
            np.asarray(gu.predict_raw(X[:200])), atol=1e-6)


# ---------------------------------------------------------------------------
# stable row compaction ahead of the fused kernel's one-hot dot
# ---------------------------------------------------------------------------

_CT = 256          # the first cases' chunk; the tile is gcd(chunk, 512)

# the boundaries of the T-wide staging route: (chunk, rows, selected
# rows of each T-row sub-tile in turn). `c` = rows staged before a turn
_FILLS = {
    # c + n_sel == T exactly: the tile fills, nothing is carried over
    "fills_exactly": (_CT, 1000, [100, 156, 10, 0]),
    # a fully selected sub-tile arrives at c = T - 1; then one more row
    # fills the carried T - 1 exactly
    "full_sub_tile_at_t_minus_1": (_CT, 1000, [255, 256, 1, 0]),
    # two sub-tiles a grid step: the step's LAST turn overflows and the
    # remainder rides into the next chunk; a fully selected sub-tile
    # later; the last step's extra turn flushes a part tile
    "overflow_carried_into_next_chunk": (1024, 3000,
                                         [300, 400, 0, 100, 512, 0]),
    # the last live turn of the last step overflows: its remainder is
    # the part tile the extra turn flushes
    "extra_turn_after_last_overflow": (1024, 3000, [0, 0, 0, 0, 400, 300]),
    # a flush every turn, two turns a step, over three chunks
    "every_row_several_chunks": (1024, 3000, [512] * 5 + [440]),
}
_KINDS = ["none", "under_one_tile", "exact_tiles", "every_row",
          "oob_passengers_padded_tail"] + sorted(_FILLS)


def _compaction_case(kind):
    """(kernel args, oracle args, B, smaller children, bag mask, chunk)
    of one wave. In every case but one nobody moves (thresholds past
    every bin), the left child keeps the parent's id and is the smaller
    one, so the contributing rows are the in-bag rows of the four
    parents: placed by hand, at random or (_FILLS) so many a sub-tile.
    "oob_passengers_padded_tail" has real splits, out-of-bag rows,
    zero-weight passenger rows and a chunk-padded tail."""
    r = np.random.default_rng(31)
    F, B, W = 5, 64, 8
    chunk, N, fills = _FILLS.get(kind, (_CT, 1000, None))
    n_in = {"none": 0, "under_one_tile": 37, "exact_tiles": 2 * _CT,
            "every_row": N}.get(kind)
    bins_t = r.integers(0, 63, (F, N)).astype(np.uint8)
    g = r.normal(size=N).astype(np.float32)
    h = r.uniform(0.1, 1, N).astype(np.float32)
    wl = np.array([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
    new_ids = np.array([4, 5, 6, 7, -1, -1, -1, -1], np.int32)
    feat = r.integers(0, F, W).astype(np.int32)
    dleft = r.integers(0, 2, W).astype(bool)
    miss = np.array([0, 1, 2, 0, 1], np.int32)[feat]
    defb = np.array([0, 3, 0, 0, 5], np.int32)[feat]
    nb = np.full(W, B, np.int32)
    if kind == "oob_passengers_padded_tail":
        mask = (r.uniform(size=N) > 0.3).astype(np.float32)
        leaf = r.integers(0, 6, N).astype(np.int32)
        leaf[leaf >= 4] += 4                          # 8, 9: not split
        tbin = r.integers(5, 55, W).astype(np.int32)
        small = np.where(r.uniform(size=W) < 0.5, wl, new_ids)
        small = np.where(wl >= 0, small, -1).astype(np.int32)
        # passengers: rows that partition but carry no weight at all
        # (validation rows ride the training pass this way)
        mask[::11] = 0.0
    else:
        mask = np.ones(N, np.float32)
        leaf = np.full(N, 9, np.int32)                # not in the wave
        if fills is None:
            rows_in = r.choice(N, n_in, replace=False)
        else:
            T = math.gcd(chunk, 512)
            rows_in = np.concatenate([
                t * T + r.choice(min(T, N - t * T), k, replace=False)
                for t, k in enumerate(fills)])
        leaf[rows_in] = r.integers(0, 4, len(rows_in)).astype(np.int32)
        tbin = np.full(W, B + 10, np.int32)
        miss[:] = 0
        small = wl.copy()
    gm, hm = g * mask, h * mask
    tbl = np.stack([wl, new_ids, feat, tbin, dleft.astype(np.int32), miss,
                    defb, nb, small, np.zeros(W, np.int32)])
    kern = tuple(jnp.asarray(x) for x in
                 (bins_t, gm, hm, mask, leaf, tbl))
    orac = tuple(jnp.asarray(x) for x in
                 (bins_t, gm, hm, mask, leaf, wl, new_ids, feat, tbin,
                  dleft, np.zeros(W, bool), np.zeros((W, 8), np.int32),
                  small, miss, defb, nb))
    return kern, orac, B, small, mask, chunk


@pytest.mark.parametrize("kind", _KINDS)
def test_compacted_fused_kernel_matches_xla(kind):
    """Only rows in the wave's smaller children reach the dot, a tile
    at a time: leaf ids and counts bit-equal to the XLA twin, g/h
    f32-grade, and the kernel's count of dotted rows is the
    contributing rows rounded up to whole tiles."""
    from lightgbm_tpu.ops.hist_wave import (COMPACT_TILE_UNIT,
                                            fused_partition_histogram_xla)
    kern, orac, B, small, mask, chunk = _compaction_case(kind)
    T = math.gcd(chunk, 512)
    leaf_x, hist_x = fused_partition_histogram_xla(*orac, num_bins=B)
    # (the one-hot dot over T-row tiles; tests/test_wave_split.py holds
    # the flush by slot, which this width would take by the rule)
    leaf_c, hist_c, work = fused_partition_histogram_pallas(
        *kern, num_bins=B, chunk=chunk, interpret=True, compact=True,
        split=False)
    np.testing.assert_array_equal(np.asarray(leaf_c), np.asarray(leaf_x))
    hc, hx = np.asarray(hist_c), np.asarray(hist_x)
    np.testing.assert_array_equal(hc[..., 2], hx[..., 2])
    np.testing.assert_allclose(hc, hx, atol=5e-5)
    contributing = int((np.isin(np.asarray(leaf_x), small[small >= 0])
                        & (mask > 0)).sum())
    scanned, dotted, block_dots = (int(v) * COMPACT_TILE_UNIT for v in work)
    assert scanned == -(-len(mask) // chunk) * chunk
    assert dotted == block_dots == -(-contributing // T) * T
    # the same pass without compaction dots every row it scans
    *_, work_m = fused_partition_histogram_pallas(
        *kern, num_bins=B, chunk=chunk, interpret=True, compact=False)
    assert (int(work_m[1]) == int(work_m[0]) == int(work_m[2])
            == scanned // COMPACT_TILE_UNIT)


@pytest.mark.parametrize("tier", ["hilo5", "int8"])
def test_compacted_histogram_is_the_tiles_of_a_stable_compaction(tier):
    """BIT-equal to "stable compaction, T-row tiles dotted in order",
    emulated in NumPy: the contributing rows (in one of the wave's
    smaller children after the pass, carrying weight) are taken in row
    order and cut into T-row tiles, the last padded with weightless
    rows. The int8 tier's integer sums are then plain NumPy adds; the
    exact tier's f32 sums of a tile are the masked kernel's over that
    tile alone (chunk T, one tile a grid step, added in turn), so the
    order of every f32 addition is pinned."""
    from lightgbm_tpu.ops.hist_wave import (COMPACT_TILE_UNIT,
                                            fused_partition_histogram_xla)
    kern, orac, B, small, mask, chunk = _compaction_case(
        "oob_passengers_padded_tail")
    bins_t, g, h, _, leaf, tbl = (np.asarray(x) for x in kern)
    T = math.gcd(chunk, 512)
    kw = dict(num_bins=B, interpret=True)
    if tier == "int8":
        g = np.clip(np.round(g * 40), -127, 127).astype(np.float32)
        h = np.round(h * 100).astype(np.float32) * mask
        kw.update(precision="int8", gh_scale=(1.0, 1.0), dequant=False)
    leaf_new = np.asarray(fused_partition_histogram_xla(
        *orac, num_bins=B)[0])
    keep = np.flatnonzero(np.isin(leaf_new, small[small >= 0])
                          & ((mask > 0) | (g != 0) | (h != 0)))
    assert T < len(keep) and len(keep) % T          # a part tile at the end
    pad = (-len(keep)) % T
    rows = np.concatenate([keep, np.zeros(pad, keep.dtype)])
    live = np.arange(len(rows)) < len(keep)
    packed = (bins_t[:, rows], g[rows] * live, h[rows] * live,
              mask[rows] * live, np.where(live, leaf[rows], 9))
    _, hist_c, work = fused_partition_histogram_pallas(
        bins_t, g, h, mask, leaf, tbl, chunk=chunk, compact=True,
        split=False, **kw)
    assert int(work[1]) * COMPACT_TILE_UNIT == len(rows)
    hist_c = np.asarray(hist_c)
    if tier == "int8":
        assert hist_c.dtype == np.int32
        want = np.zeros_like(hist_c)
        slot = {int(v): k for k, v in enumerate(small) if v >= 0}
        k_of = np.array([slot[int(v)] for v in leaf_new[keep]])
        for f in range(bins_t.shape[0]):
            for c, w in enumerate((g, h, mask)):
                np.add.at(want[:, f, :, c], (k_of, bins_t[f, keep]),
                          w[keep].astype(np.int32))
    else:
        _, want, work_e = fused_partition_histogram_pallas(
            *packed, tbl, chunk=T, compact=False, **kw)
        assert int(work_e[1]) * COMPACT_TILE_UNIT == len(rows)
        want = np.asarray(want)
    assert hist_c.tobytes() == want.tobytes()


@pytest.mark.parametrize("tier", ["default", "int8", "int8_proxy",
                                  "packed4_proxy"])
def test_compacted_fused_kernel_vs_masked(tier):
    """The other tiers the kernel serves: with compaction the same leaf
    ids and counts as the masked full-chunk dot, the proxy's
    partition-mask counts included; the integer tiers' sums the same
    bits, the single-bf16 tier's f32 sums the same to rounding (its
    full-mantissa hessians add up in another order)."""
    r = np.random.default_rng(32)
    F, W, N = 6, 8, 1500
    B = 16 if tier == "packed4_proxy" else 64
    bins = r.integers(0, B, (F, N)).astype(np.uint8)
    mask = (r.uniform(size=N) > 0.25).astype(np.float32)
    if tier == "default":
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.1, 1, N).astype(np.float32)
        kw = dict(precision="default")
    else:
        g = r.integers(-127, 128, N).astype(np.float32)
        h = r.integers(0, 128, N).astype(np.float32)
        kw = dict(precision="int8", gh_scale=(0.5, 0.25),
                  count_proxy=tier != "int8")
    if tier == "packed4_proxy":
        kw.update(packed4=True, num_features=F)
        bins_dev = bins[0::2] | (bins[1::2] << 4)
    else:
        bins_dev = bins
    leaf = r.integers(0, 5, N).astype(np.int32)
    wl = np.array([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
    new_ids = np.array([5, 6, 7, 8, -1, -1, -1, -1], np.int32)
    feat = r.integers(0, F, W).astype(np.int32)
    tbl = np.stack([wl, new_ids, feat,
                    r.integers(2, B - 2, W).astype(np.int32),
                    np.zeros(W, np.int32), np.zeros(W, np.int32),
                    np.zeros(W, np.int32), np.full(W, B, np.int32),
                    new_ids, np.zeros(W, np.int32)])
    args = tuple(jnp.asarray(x) for x in
                 (bins_dev, g * mask, h * mask, mask, leaf, tbl))
    outs = [fused_partition_histogram_pallas(
        *args, num_bins=B, chunk=512, interpret=True, compact=c, **kw)
        for c in (False, True)]
    assert len(outs[0]) == (4 if "proxy" in tier else 3)
    (s_m, d_m, _), (s_c, d_c, _) = (np.asarray(o[-1]) for o in outs)
    assert s_m == d_m == s_c and 0 < d_c < d_m     # rows scanned, dotted
    for a, b in zip(outs[0][:-1], outs[1][:-1]):
        a, b = np.asarray(a), np.asarray(b)
        if tier == "default" and a.ndim == 4:
            np.testing.assert_array_equal(a[..., 2], b[..., 2])
            np.testing.assert_allclose(a, b, atol=5e-5)
        else:
            np.testing.assert_array_equal(a, b)


@pytest.fixture
def compaction_everywhere(monkeypatch):
    """The trace-time rule (autotune.hist_compact_tile: the dot's MACs a
    row) would not compact a test-sized geometry; lower its threshold,
    and keep traces made under it out of the other tests' jit caches."""
    from lightgbm_tpu.ops import autotune
    jax.clear_caches()
    monkeypatch.setattr(autotune, "HIST_COMPACT_MIN_MACS", 0)
    yield
    jax.clear_caches()


def test_grower_counts_rows_scanned_and_dotted(compaction_everywhere):
    """Through the whole grower: the compacted fused kernel grows the
    XLA route's tree, and the tree's record carries the wave passes'
    rows scanned and rows dotted (whole tiles of the smaller children's
    rows; the model's own counts say how many those are)."""
    from lightgbm_tpu.ops.hist_wave import COMPACT_TILE_UNIT
    bins, grad, hess, mask, fmask, meta, B = _grower_problem()
    bins_t = jnp.asarray(np.ascontiguousarray(bins.T))
    n = bins.shape[0]
    recs = {}
    for fused in (True, False):       # the interpreted kernel, the XLA twin
        cfg = WaveGrowerConfig(num_leaves=15, num_bins=B, wave_size=8,
                               chunk=512, fused=fused)
        grow = make_wave_grower(cfg, meta)
        recs[fused], leaf = grow(bins_t, grad, hess, mask, fmask)
        recs[fused, "leaf"] = np.asarray(leaf)
    rec, ref = recs[True], recs[False]
    np.testing.assert_array_equal(recs[True, "leaf"], recs[False, "leaf"])
    np.testing.assert_array_equal(np.asarray(rec.split_feature),
                                  np.asarray(ref.split_feature))
    assert np.asarray(ref.wave_work).tolist() == [0, 0, 0]
    scanned, dotted = (int(v) * COMPACT_TILE_UNIT
                       for v in np.asarray(rec.wave_work)[:2])
    n_splits = int(rec.num_leaves) - 1
    n_pad = -(-n // 512) * 512
    waves = scanned // n_pad          # a pass scans every (padded) row
    assert scanned == waves * n_pad and waves >= -(-n_splits // 8)
    # each split's smaller child, from the record's own counts
    cnt = np.asarray(rec.leaf_count)
    ic = np.asarray(rec.internal_count)
    assert 0 < dotted < scanned
    assert dotted >= (n - cnt.max())        # every split's smaller side
    assert dotted <= int(ic[:n_splits].sum() // 2) + waves * 512
