"""The feature-tile axis of the two histogram kernels (ops/hist_wave.py).

Where the accumulators of every feature, the bin block and the fused
kernel's compaction payload no longer fit one resident VMEM block
(2,000 features at 255 bins: 250 MiB of accumulators alone), both
kernels walk a grid axis over tiles of features. The tile is a
trace-time choice from the shapes (autotune.hist_feature_tile); here it
is forced, so that test-sized shapes walk tiles too:

* tiled against untiled kernels, bit for bit: histograms, leaf ids, the
  count-proxy's moved rows and the rows counted as scanned and dotted,
  with a part-filled last tile, with and without row compaction, on
  every tier;
* the pricing: one tile wherever everything fits (the benchmark's
  67-feature cell lowers as it did), tiles under the VMEM budget at
  2,000 features, a tuner candidate set that is never empty there and
  an error where it is;
* the grower under tiles grows the untiled grower's tree;
* a booster through ``Dataset`` / ``Booster`` / ``update()`` under tiles
  against the benchmark's plain reference (``benchmark/reference.py``),
  by the path the benchmark's cell takes.
"""
import argparse
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.ops import autotune
from lightgbm_tpu.ops.hist_wave import (fused_partition_histogram_pallas,
                                        wave_histogram_pallas)

ROOT = Path(__file__).resolve().parent.parent
F, N, W = 136, 600, 8
TILE = 64                 # stored bin rows a tile: 136 = 2 x 64 + 8
CHUNK = 256               # three grid steps, the last with a padded tail


# tier -> (B, kernel keywords); packed4 halves the stored rows
_TIERS = {
    "hilo5-255bins": (256, dict(precision="highest", variant="hilo5")),
    "hilo5": (64, dict(precision="highest", variant="hilo5")),
    "hilo4": (64, dict(precision="highest", variant="hilo4")),
    "bf16": (64, dict(precision="default")),
    "int8": (64, dict(precision="int8", gh_scale=(0.5, 0.25))),
    "int8-proxy": (64, dict(precision="int8", gh_scale=(0.5, 0.25),
                            count_proxy=True)),
    "packed4-proxy": (16, dict(precision="int8", gh_scale=(0.5, 0.25),
                               count_proxy=True, packed4=True,
                               num_features=F)),
}


def _problem(tier, seed=41):
    B, kw = _TIERS[tier]
    r = np.random.default_rng(seed)
    bins = r.integers(0, B, (F, N)).astype(np.uint8)
    if kw.get("packed4"):
        bins_dev = bins[0::2] | (bins[1::2] << 4)
    else:
        bins_dev = bins
    mask = (r.uniform(size=N) > 0.25).astype(np.float32)
    if kw["precision"] == "int8":
        g = r.integers(-127, 128, N).astype(np.float32)
        h = r.integers(0, 128, N).astype(np.float32)
    else:
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.1, 1, N).astype(np.float32)
    leaf = r.integers(0, 5, N).astype(np.int32)
    return B, kw, bins_dev, g * mask, h * mask, mask, leaf, r


@pytest.fixture(autouse=True)
def _drop_compiled_kernels():
    """An interpreted kernel at 140 features is a large XLA:CPU program
    (the fused kernel's flush by slot unrolls its groups' dots), and a
    worker that keeps a dozen of them loaded runs out of mappings
    (vm.max_map_count) and aborts inside the next compile: none of this
    file's executables is used twice, so each test drops its own."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("tier", ["hilo5-255bins", "hilo5", "hilo4",
                                  "int8", "packed4-proxy"])
def test_tiled_wave_kernel_equals_untiled(tier):
    """The root pass: every tile's accumulator is the untiled kernel's
    slice of groups, the same dots in the same order."""
    B, kw, bins, g, h, mask, leaf, _ = _problem(tier)
    wl = jnp.asarray(np.array([0, 2, 3, -1, -1, -1, -1, -1], np.int32))
    ids = jnp.asarray(np.where(mask > 0, leaf, -1).astype(np.int32))
    one, tiled = (np.asarray(wave_histogram_pallas(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), ids, wl,
        num_bins=B, chunk=CHUNK, interpret=True, feature_tile=t, **kw))
        for t in (None, TILE // (2 if "packed4" in tier else 1)))
    assert one.shape == (W, F, B, 2 if "proxy" in tier else 3)
    assert np.abs(one).sum() > 0
    np.testing.assert_array_equal(tiled, one)


@pytest.mark.parametrize("compact", [False, True], ids=["masked", "compact"])
@pytest.mark.parametrize("tier", sorted(_TIERS))
def test_tiled_fused_kernel_equals_untiled(tier, compact):
    """A wave pass: the split columns lie in other tiles than the one
    that dots (features 5, 70, 100 and 135, the last in the part-filled
    tile), every tile routes by the ids of before the pass, and what is
    counted once a pass (rows scanned and dotted, the count-proxy's
    moved rows) is counted once."""
    B, kw, bins, g, h, mask, leaf, r = _problem(tier)
    wl = np.array([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
    new_ids = np.array([5, 6, 7, 8, -1, -1, -1, -1], np.int32)
    feat = np.array([5, 70, 100, 135, 0, 0, 0, 0], np.int32)
    small = np.where(np.arange(W) % 2 == 0, wl, new_ids)
    small = np.where(wl >= 0, small, -1).astype(np.int32)
    tbl = np.stack([wl, new_ids, feat,
                    r.integers(2, B - 2, W).astype(np.int32),
                    r.integers(0, 2, W).astype(np.int32),
                    np.array([0, 1, 2, 0, 0, 0, 0, 0], np.int32),
                    np.array([0, 3, 0, 0, 0, 0, 0, 0], np.int32),
                    np.full(W, B, np.int32), small,
                    np.zeros(W, np.int32)])
    args = tuple(jnp.asarray(x) for x in (bins, g, h, mask, leaf, tbl))
    # (one compacting tier keeps the flush the rule hands it, by slot:
    # interpreted at 140 features in one tile it is a minute of XLA:CPU
    # compile; the other bf16 tiers' tiles under it:
    # tests/test_wave_split.py)
    split = None if tier == "hilo5-255bins" else False
    one, tiled = (fused_partition_histogram_pallas(
        *args, num_bins=B, chunk=CHUNK, interpret=True, any_cat=False,
        compact=compact, feature_tile=t, split=split, **kw)
        for t in (None, TILE // (2 if "packed4" in tier else 1)))
    assert len(one) == len(tiled) == (4 if "proxy" in tier else 3)
    assert (np.asarray(one[0]) != leaf).any()          # rows moved
    assert np.abs(np.asarray(one[1])).sum() > 0
    scanned, dotted, _pairs = np.asarray(one[-1])
    assert (0 < dotted < scanned) if compact else dotted == scanned
    for a, b in zip(one, tiled):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


# rows -> (operand shape, dtype): a pool of [F, B, 3] f32 histograms, a
# bins matrix's rows, a packed4 matrix's byte rows
_TAKE_OPERANDS = {
    "pool-f32": ((15, 6, 16, 3), np.float32),
    "bins-u8": ((72, 300), np.uint8),
    "packed4-bytes": ((36, 300), np.uint8),
}


@pytest.mark.parametrize("w", [1, 24])
@pytest.mark.parametrize("operand", sorted(_TAKE_OPERANDS))
def test_take_rows_is_the_gather(operand, w):
    """``take_rows(x, idx)`` is ``x[idx]`` bit for bit: negative indices
    wrap (an idle wave slot's -1 takes the last row), repeated ones take
    the row again, one past either end clamps."""
    from lightgbm_tpu.ops.hist_wave import take_rows
    shape, dt = _TAKE_OPERANDS[operand]
    r = np.random.default_rng(w)
    if dt == np.float32:
        x = r.normal(size=shape).astype(dt)
    else:
        x = r.integers(0, 256, shape).astype(dt)
    n = shape[0]
    idx = np.concatenate([[-1, 3, 3, -n, n - 1, -n - 2, n + 5],
                          r.integers(-n, n, 24)])[:w].astype(np.int32)
    if w == 1:
        idx[0] = -1
    got = jax.jit(take_rows)(jnp.asarray(x), jnp.asarray(idx))
    want = jnp.asarray(x)[jnp.asarray(idx)]
    assert got.shape == (w,) + shape[1:] and got.dtype == dt
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got)[0], x[-1])


def test_one_tile_wherever_everything_fits():
    """The benchmark's 67-feature cell (72 rows with the registry's
    pad) and the smoke's widths price as ONE tile, which takes the
    kernels' 1-D grid of before the tile axis."""
    for f, b, chunk in ((72, 256, 16384), (67, 255, 32768), (32, 64, 8192),
                        (56, 256, 8192)):
        for fused in (True, False):
            geom, tiles = autotune.hist_feature_tiling(
                F=f, B=b, W=24, chunk=chunk, fused=fused, variant="hilo5")
            assert tiles == 1 and geom == autotune.hist_geometry(
                F=f, B=b, W=24)
    from lightgbm_tpu.ops.hist_wave import _tile_grid
    grid, at = _tile_grid(1, 7)
    assert grid == (7,) and at(lambda t, i: (t, i))(3) == (0, 3)
    grid, at = _tile_grid(4, 7)
    assert grid == (4, 7) and at(lambda t, i: (t, i))(2, 3) == (2, 3)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "wave"])
def test_epsilon_width_prices_into_tiles_under_the_budget(fused):
    """2,000 features x 255 bins: 250 MiB of accumulators in one block,
    so tiles; each priced inside the VMEM budget, its unrolled group
    loop no longer than HIST_TILE_MAX_GROUPS, its rows whole uint8
    sublane tiles."""
    whole = autotune.hist_vmem_bytes(
        chunk=16384, geom=autotune.hist_geometry(F=2000, B=256, W=24),
        W=24, fused=fused, variant="hilo5")
    assert not autotune.fits_vmem(whole) and whole > 250 << 20
    for chunk in (4096, 8192, 16384, 32768):
        geom, tiles = autotune.hist_feature_tiling(
            F=2000, B=256, W=24, chunk=chunk, fused=fused, variant="hilo5")
        assert tiles == -(-2000 // geom["F_rows"]) > 1
        assert geom["F_rows"] % autotune.HIST_TILE_ROW_ALIGN == 0
        assert geom["groups"] <= autotune.HIST_TILE_MAX_GROUPS
        assert autotune.fits_vmem(autotune.hist_vmem_bytes(
            chunk=chunk, geom=geom, W=24, fused=fused, variant="hilo5",
            tiled=True))
    cands = autotune.hist_chunk_candidates(
        F=2000, B=256, W=24, fused=fused, n_rows=393216, variant="hilo5")
    assert cands and all(c["tile"] < 2000 for c in cands)


def test_no_fitting_tile_is_an_error_not_a_default(tmp_path, monkeypatch):
    """Under a budget no tile fits, the kernel's geometry and the tuner
    (its TPU arm) both refuse, in their own words, before Mosaic is
    asked; under the real budget the tuner times (chunk, tile) pairs."""
    from lightgbm_tpu.utils import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "device_kind", lambda: "TPU v5 lite")
    autotune.configure("on", str(tmp_path / "t.json"))
    try:
        timed = []
        assert autotune.tune_hist_chunk(
            fused=True, F=2000, B=256, W=24, variant="hilo5",
            n_rows=393216,
            _measure=lambda c: timed.append(c) or 1.0 / c["chunk"]) == 32768
        assert [c["chunk"] for c in timed] == [32768, 16384, 8192, 4096]
        assert all(c["tile"] == 64 for c in timed)
        monkeypatch.setattr(autotune, "PALLAS_VMEM_BUDGET_BYTES", 1 << 20)
        with pytest.raises(ValueError, match="no feature tile"):
            autotune.hist_feature_tiling(F=2000, B=256, W=24, chunk=8192,
                                         fused=True, variant="hilo5")
        assert autotune.hist_chunk_candidates(
            F=2000, B=256, W=24, fused=True, variant="hilo5") == []
        with pytest.raises(ValueError, match="no row chunk"):
            autotune.tune_hist_chunk(fused=True, F=2000, B=256, W=24,
                                     variant="hilo5",
                                     _measure=lambda c: 1.0)
    finally:
        autotune.configure("on", None)


@pytest.fixture
def forced_tiles(monkeypatch):
    """Every histogram kernel call walks tiles of 32 stored bin rows,
    whatever fits; traces made so stay out of the other tests' caches."""
    real = autotune.hist_feature_tile
    jax.clear_caches()
    monkeypatch.setattr(autotune, "hist_feature_tile",
                        lambda **kw: real(**{**kw, "force": 32}))
    yield
    jax.clear_caches()


def _grow(fused, packed4=False):
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    r = np.random.default_rng(5)
    n, f, B = 1500, 72, 16 if packed4 else 32
    bins = r.integers(0, B, (n, f)).astype(np.uint8)
    y = ((bins[:, 3] > 12) ^ (bins[:, 40] > 20) ^ (bins[:, 70] > 9))
    grad = jnp.asarray(np.where(y, -0.5, 0.5).astype(np.float32)
                       + 0.1 * r.normal(size=n).astype(np.float32))
    hess = jnp.full(n, 0.25, jnp.float32)
    meta = FeatureMeta(
        num_bin=np.full(f, B, np.int32), missing_type=np.zeros(f, np.int32),
        default_bin=np.zeros(f, np.int32), monotone=np.zeros(f, np.int32),
        penalty=np.ones(f, np.float32))
    cfg = WaveGrowerConfig(
        num_leaves=15, num_bins=B, wave_size=8, chunk=512, fused=fused,
        route="pallas-tpu" if fused else "two-pass", packed4=packed4,
        hp=SplitParams(min_data_in_leaf=5, has_cat=False))
    grow = make_wave_grower(cfg, meta)
    bins_t = np.ascontiguousarray(bins.T)
    if packed4:
        # two features a byte, the even one in the low nibble
        bins_t = bins_t[0::2] | (bins_t[1::2] << 4)
    rec, leaf = grow(jnp.asarray(bins_t), grad, hess,
                     jnp.ones(n, jnp.float32), jnp.ones(f, bool))
    return rec, np.asarray(leaf)


def test_grower_under_tiles_grows_the_same_tree(forced_tiles):
    """Root pass and wave passes through tiled, interpreted kernels: the
    two-pass XLA route's tree, split for split, and the gauges say how
    many tiles a pass walked and what the pool holds."""
    rec, leaf = _grow(fused=True)
    assert obs.default_registry().snapshot()["gauges"][
        "hist/feature_tiles"] == 3.0                   # 72 rows / 32
    assert obs.default_registry().snapshot()["gauges"][
        "mem/hist_pool_bytes"] == 15 * 72 * 32 * 3 * 4
    ref, leaf_ref = _grow(fused=False)
    assert int(rec.num_leaves) == int(ref.num_leaves) == 15
    np.testing.assert_array_equal(leaf, leaf_ref)
    for name in ("split_feature", "split_bin", "split_leaf", "leaf_count"):
        np.testing.assert_array_equal(np.asarray(getattr(rec, name)),
                                      np.asarray(getattr(ref, name)))
    np.testing.assert_allclose(np.asarray(rec.leaf_output),
                               np.asarray(ref.leaf_output), rtol=2e-5)
    assert {int(f) // 32 for f in np.asarray(rec.split_feature)
            if f >= 0} == {0, 1, 2}                    # splits in every tile


@pytest.mark.parametrize("packed4", [False, True],
                         ids=["uint8", "packed4"])
def test_grower_takes_rows_as_a_gather_would(forced_tiles, monkeypatch,
                                             packed4):
    """Under tiles the grower takes the wave's parent histograms out of
    the pool and the fused kernel's wrapper its split columns out of the
    bins by ``take_rows``; the tree is the one plain ``x[idx]`` gathers
    grow, every field of the record bit for bit, and the gauge
    ``hist/row_take_bytes`` counts both takes."""
    from lightgbm_tpu.ops import hist_wave, wave_grower
    rec, leaf = _grow(fused=True, packed4=packed4)
    f_rows = 36 if packed4 else 72
    assert obs.default_registry().snapshot()["gauges"][
        "hist/row_take_bytes"] == 8 * 72 * (16 if packed4 else 32) * 3 * 4 \
        + 8 * 1500
    assert obs.default_registry().snapshot()["gauges"][
        "hist/feature_tiles"] == -(-f_rows // 32)
    jax.clear_caches()
    for mod in (hist_wave, wave_grower):
        monkeypatch.setattr(mod, "take_rows", lambda x, idx: x[idx])
    ref, leaf_ref = _grow(fused=True, packed4=packed4)
    assert int(rec.num_leaves) == 15
    np.testing.assert_array_equal(leaf, leaf_ref)
    for name, a in rec._asdict().items():
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(getattr(ref, name)), name)


def test_wide_booster_under_tiles_against_the_plain_reference(
        forced_tiles, monkeypatch):
    """``Dataset`` -> ``Booster`` -> ``update()`` at 32,768 rows x 104
    features (the reference's row block; four tiles of 32 rows, the last
    part-filled; at 300 features the interpreted kernels take three
    minutes), both kernels interpreted, by the benchmark's own path
    (``run.measure`` of the cell ``epsilon_wide.train``, cut in rows,
    features and leaves) and under the cell's own limits: the plain
    reference recomputes every row's leaf, the sums, the leaf values,
    the best cuts and the scores of the first three trees."""
    sys.path[:0] = [str(ROOT / "benchmark"), str(ROOT)]
    import run
    monkeypatch.setattr(autotune, "tune_hist_route",
                        lambda **kw: "pallas-tpu")
    cell_name = "epsilon_wide.train"
    bench, cell, config, traffic = run.load_cell(cell_name)
    assert config["data"]["features"] == 2000 and config["limits"]
    cut = {"data": {"rows": 32768, "features": 104, "uniform_columns": 104,
                    "levels": 63},
           "params": {"num_leaves": 15, "max_bin": 63}}
    # the run's guards read these counters whole (a benchmark run is a
    # process of its own); an earlier test of this worker may have
    # retried or failed a candidate on purpose
    for name in ("retry/retries", "autotune/candidates_failed"):
        c = obs.counter(name)
        c.add(-c.value)
    ns = argparse.Namespace(workload=cell_name, seed=7, seconds=0.01, trace=0)
    line, res = run.measure(
        ns, bench, cell, config, traffic,
        {"platform": "cpu", "kind": "test", "count": 1}, on_chip=False,
        overrides=cut)
    assert obs.default_registry().snapshot()["gauges"][
        "hist/feature_tiles"] == 4.0
    over = [k for k, c in line["checks"].items()
            if not c["value"] <= c["limit"]]
    assert line["correct"], over
    assert line["checks"]["leaf_count"]["value"] == 0
    assert line["attempted"] >= 1 and line["failed"] == 0
