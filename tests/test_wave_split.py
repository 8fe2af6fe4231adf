"""The wave passes' dot by the root's two-digit split, a slot at a time
(ops/hist_wave.py _flush_by_slot).

A dense dot over rows of mixed slots spends its whole output on every
row; only a dot whose rows share a slot addresses one slot's histogram.
Where the fused kernel compacts and the root's split applies
(autotune.wave_split_applies) its flush stages the compacted rows wider,
puts them in slot order by one more exact gather, and dots each 128-row
block against the slots it holds by the root kernel's two digits. Here,
interpreted on the CPU:

* against the one-hot dot of ``_accumulate_hist`` on the same staged
  rows (``split=False``) and the XLA scatter oracle: to the last bit on
  integer-valued g and h (every float sum is then exact), within the
  compaction tests' bound on random floats, counts exact always; over
  live slots, a tile that is all one slot, slots with no row, runs that
  cross every block boundary, the ragged last flush of a pass, a forced
  feature tile, the four layouts;
* the kernel's count of blocks and (block, slot) pairs against what the
  staged rows' slots say;
* three trees grown with the new flush against the one-hot dot's, forced
  by the rule's other side: structure and counts equal;
* the predicate's two sides, by name; the pricing of the three benchmark
  cells' shapes at every chunk the tuner offers them; the gauge
  ``hist/wave_macs``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.ops import autotune
from lightgbm_tpu.ops.hist_wave import (COMPACT_TILE_UNIT,
                                        fused_partition_histogram_pallas,
                                        fused_partition_histogram_xla)

STAGE = 512                   # four blocks a flush: several flushes a pass
_LAYOUTS = {
    "hilo5": dict(precision="highest", variant="hilo5"),
    "hilo4": dict(precision="highest", variant="hilo4"),
    "hilo3": dict(precision="highest", variant="hilo3"),
    "bf16": dict(precision="default"),
}


def _slots_random(r, n, live, share=0.6):
    """A row's 0-based slot, -1 outside the wave: ``share`` of the rows
    spread over ``live`` slots."""
    return np.where(r.uniform(size=n) < share, r.integers(0, live, n), -1)


def _slots_runs(r, n, runs):
    """The first sum(runs) rows shuffled over slots of the given sizes,
    the others outside the wave."""
    slot = np.full(n, -1)
    slot[:sum(runs)] = r.permutation(np.repeat(np.arange(len(runs)), runs))
    return slot


# name: (F, B, W, N, chunk, row -> slot, layout, feature_tile)
_CASES = {
    "live1": (6, 64, 24, 2500, 512, lambda r, n: _slots_random(r, n, 1),
              "hilo5", None),
    "live2": (6, 64, 24, 2500, 512, lambda r, n: _slots_random(r, n, 2),
              "hilo5", None),
    "live5": (6, 255, 24, 2500, 512, lambda r, n: _slots_random(r, n, 5),
              "hilo5", None),
    "live24": (6, 255, 24, 2500, 1024,
               lambda r, n: _slots_random(r, n, 24), "hilo5", None),
    # the first flush holds slot 2 alone (no ordering), the next mix
    "tile_all_one_slot": (5, 64, 8, 2000, 512, lambda r, n: np.concatenate([
        np.full(STAGE, 2), _slots_random(r, n - STAGE, 5)]), "hilo5", None),
    # eight live slots, rows in three: first and last of a block skip
    # slots that hold nothing
    "slots_with_no_row": (5, 64, 8, 1500, 512, lambda r, n: np.where(
        r.uniform(size=n) < 0.7, r.choice([1, 4, 6], n), -1), "hilo5",
        None),
    # one flush of 64 + 3 x 128 + 64 rows: every run crosses a boundary
    "crosses_every_block_boundary": (5, 64, 8, 1024, 512, lambda r, n:
                                     _slots_runs(r, n, [64, 128, 128, 128,
                                                        64]),
                                     "hilo5", None),
    # one whole flush and 37 rows more
    "ragged_last_flush": (5, 64, 8, 1024, 512, lambda r, n: _slots_runs(
        r, n, [200, 200, 149]), "hilo5", None),
    "feature_tile": (70, 255, 8, 1200, 512,
                     lambda r, n: _slots_random(r, n, 6), "hilo5", 32),
    "hilo4": (9, 255, 32, 1500, 512, lambda r, n: _slots_random(r, n, 7),
              "hilo4", None),
    "hilo3": (9, 255, 40, 1500, 512, lambda r, n: _slots_random(r, n, 7),
              "hilo3", None),
    "bf16": (9, 255, 32, 1500, 512, lambda r, n: _slots_random(r, n, 7),
             "bf16", None),
    "bf16_128_bins": (9, 128, 32, 1500, 512,
                      lambda r, n: _slots_random(r, n, 7), "bf16", None),
}


def _wave(name, *, integers):
    """(kernel args, oracle args, keywords, a contributing row's slot in
    row order) of one wave in which nobody moves (thresholds past every
    bin) and the left child, which keeps the parent's id, is the smaller
    one: the rows of slot k's histogram are the in-bag rows of leaf k."""
    F, B, W, N, chunk, slot_of, layout, tile = _CASES[name]
    r = np.random.default_rng(sorted(_CASES).index(name) + 41)
    slot = np.asarray(slot_of(r, N))
    live = int(slot.max()) + 1
    bins = r.integers(0, B, (F, N)).astype(np.uint8)
    mask = (r.uniform(size=N) > 0.15).astype(np.float32)
    if integers:
        g = r.integers(-8, 9, N).astype(np.float32)
        h = r.integers(0, 5, N).astype(np.float32)
    else:
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.1, 1, N).astype(np.float32)
    if layout == "hilo3":
        h = np.ones(N, np.float32)      # the layout's gate: h == the mask
    leaf = np.where(slot >= 0, slot, 99).astype(np.int32)
    wl = np.full(W, -1, np.int32)
    wl[:live] = np.arange(live)
    new_ids = np.where(wl >= 0, wl + 100, -1).astype(np.int32)
    z = np.zeros(W, np.int32)
    feat = r.integers(0, F, W).astype(np.int32)
    tbin, nb = np.full(W, B + 10, np.int32), np.full(W, B, np.int32)
    tbl = np.stack([wl, new_ids, feat, tbin, z, z, z, nb, wl, z])
    gm, hm = g * mask, h * mask
    kern = tuple(jnp.asarray(x) for x in (bins, gm, hm, mask, leaf, tbl))
    orac = tuple(jnp.asarray(x) for x in (
        bins, gm, hm, mask, leaf, wl, new_ids, feat, tbin, z.astype(bool),
        z.astype(bool), np.zeros((W, 8), np.int32), wl, z, z, nb))
    kw = dict(num_bins=B, chunk=chunk, interpret=True, compact=True,
              stage_rows=STAGE, feature_tile=tile, **_LAYOUTS[layout])
    return kern, orac, kw, slot[(slot >= 0) & (mask > 0)]


def _pairs(slots):
    """(blocks, (block, slot) pairs) of a pass whose contributing rows
    hold these slots, in row order: STAGE rows a flush, each sorted by
    slot (stably) and cut into 128-row blocks."""
    blocks = pairs = 0
    for i in range(0, len(slots), STAGE):
        tile = np.sort(slots[i:i + STAGE], kind="stable")
        for j in range(0, len(tile), COMPACT_TILE_UNIT):
            blocks += 1
            pairs += len(np.unique(tile[j:j + COMPACT_TILE_UNIT]))
    return blocks, pairs


@pytest.mark.parametrize("name", sorted(_CASES))
def test_flush_by_slot_is_the_one_hot_dots_sums_on_integers(name):
    """Integer-valued g and h: every float sum is exact, so the flush by
    slot, the one-hot dot over the same staged rows and the XLA scatter
    oracle agree to the last bit, leaf ids too; and the kernel counts
    the blocks and pairs the rows' slots say it must have dotted."""
    kern, orac, kw, slots = _wave(name, integers=True)
    leaf_x, hist_x = fused_partition_histogram_xla(
        *orac, num_bins=kw["num_bins"])
    leaf_s, hist_s, work = fused_partition_histogram_pallas(
        *kern, split=True, **kw)
    kw.pop("stage_rows")
    leaf_o, hist_o, work_o = fused_partition_histogram_pallas(
        *kern, split=False, **kw)
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_x))
    np.testing.assert_array_equal(np.asarray(hist_s), np.asarray(hist_o))
    if _CASES[name][6] != "hilo3":      # (its h plane is the count's)
        np.testing.assert_array_equal(np.asarray(hist_s),
                                      np.asarray(hist_x))
    scanned, blocks, pairs = (int(v) for v in work)
    assert (blocks, pairs) == _pairs(slots)
    assert scanned == int(work_o[0])
    # the one-hot dot rounds the same rows up to whole 512-row tiles
    assert int(work_o[1]) == int(work_o[2]) == -(-len(slots) // 512) * 4


@pytest.mark.parametrize("name", ["live1", "live5", "live24", "feature_tile",
                                  "hilo4", "hilo3", "bf16"])
def test_flush_by_slot_matches_the_one_hot_dot_on_floats(name):
    """Random float g and h: the same products added up in another
    order. Counts exact, sums within the compaction tests' bound of the
    one-hot dot's and (but hilo3's fused plane) of the oracle's."""
    kern, orac, kw, _ = _wave(name, integers=False)
    _, hist_x = fused_partition_histogram_xla(*orac, num_bins=kw["num_bins"])
    _, hist_s, _ = fused_partition_histogram_pallas(*kern, split=True, **kw)
    kw.pop("stage_rows")
    _, hist_o, _ = fused_partition_histogram_pallas(*kern, split=False, **kw)
    hs, ho, hx = (np.asarray(x) for x in (hist_s, hist_o, hist_x))
    np.testing.assert_array_equal(hs[..., 2], hx[..., 2])
    np.testing.assert_allclose(hs, ho, atol=5e-5)
    if _CASES[name][6] != "hilo3":
        np.testing.assert_allclose(hs, hx, atol=5e-5)


def test_the_rule_takes_the_flush_by_slot_where_it_applies():
    """No override: a compacting exact-tier pass at 255 bins takes the
    flush by slot (its third count is the pairs, over the blocks), at
    the constant's stage rows."""
    kern, _, kw, slots = _wave("live5", integers=True)
    kw.pop("stage_rows")
    _, hist_r, work = fused_partition_histogram_pallas(*kern, **kw)
    _, hist_o, _ = fused_partition_histogram_pallas(*kern, split=False, **kw)
    np.testing.assert_array_equal(np.asarray(hist_r), np.asarray(hist_o))
    assert len(slots) < autotune.WAVE_SPLIT_STAGE_ROWS     # one flush
    blocks = -(-len(slots) // COMPACT_TILE_UNIT)
    assert [int(v) for v in work[1:]] == [blocks, blocks + 5 - 1]


# (name, keywords of autotune.wave_split_applies, the side it takes)
_RULE = [
    ("exact_255_bins", dict(B=255, precision="highest", compact_tile=512),
     True),
    ("bf16_255_bins", dict(B=255, precision="default", compact_tile=512),
     True),
    ("exact_64_bins", dict(B=64, precision="highest", compact_tile=512),
     True),
    ("exact_128_row_tile", dict(B=255, precision="highest",
                                compact_tile=128), True),
    ("masked_side", dict(B=255, precision="highest", compact_tile=0),
     False),
    ("exact_63_bins_pad_to_64", dict(B=63, precision="highest",
                                     compact_tile=512), True),
    ("exact_48_bins", dict(B=48, precision="highest", compact_tile=512),
     False),
    ("int8", dict(B=255, precision="int8", compact_tile=512), False),
    ("count_proxy", dict(B=255, precision="int8", count_proxy=True,
                         compact_tile=512), False),
    ("packed4", dict(B=16, precision="highest", packed4=True,
                     compact_tile=512), False),
    ("word_bins", dict(B=300, precision="highest", compact_tile=0), False),
]


@pytest.mark.parametrize("name,kw,takes", _RULE, ids=[r[0] for r in _RULE])
def test_the_predicates_two_sides(name, kw, takes):
    assert autotune.wave_split_applies(**kw) is takes


@pytest.mark.parametrize("tier,kw", [
    ("int8", dict(precision="int8", gh_scale=(1.0, 1.0))),
    ("narrow", dict(precision="highest", num_bins=32))])
def test_the_split_is_not_forced_on_what_it_cannot_serve(tier, kw):
    kern, _, base, _ = _wave("live2", integers=True)
    base.update(kw)
    base.pop("variant", None)
    with pytest.raises(NotImplementedError, match="digit split"):
        fused_partition_histogram_pallas(*kern, split=True, **base)


_CELLS = {"criteo": dict(F=67, n_rows=10_485_760),
          "epsilon": dict(F=2000, n_rows=393_216),
          "yahoo": dict(F=700, n_rows=458_752)}


@pytest.mark.parametrize("exhaustive", [False, True],
                         ids=["tuner", "exhaustive"])
@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_cells_shapes_are_priced_inside_the_budget_at_every_offered_chunk(
        cell, exhaustive):
    """The three benchmark cells (255 bins, hilo5, wave 24) at every row
    chunk the tuner can hand the grower: the flush by slot's working set
    (the ordered tile, the ordering's one-hot, a block's weight rows, a
    slot's accumulators by digit) is priced from the block shapes the
    BlockSpecs use, inside the budget; 67 features stay one resident
    block, the wide cells keep their tiles of 64."""
    cands = autotune.hist_chunk_candidates(
        B=255, W=24, fused=True, exhaustive=exhaustive, variant="hilo5",
        **_CELLS[cell])
    assert {c["chunk"] for c in cands} >= {4096, 16384, 32768}
    for c in cands:
        geom, n_tiles = autotune.hist_feature_tiling(
            F=_CELLS[cell]["F"], B=255, W=24, chunk=c["chunk"], fused=True,
            variant="hilo5")
        T = autotune.hist_compact_tile(geom=geom, chunk=c["chunk"])
        split = autotune.fused_wave_split(geom=geom, compact_tile=T,
                                          variant="hilo5")
        assert T == 512 and split is not None
        assert (split["H"], split["L"], split["gf"], split["R"],
                split["nl"]) == (32, 8, 4, 160, 40)
        blk = autotune.fused_hist_block_shapes(
            chunk=c["chunk"], geom=geom, tbl_rows=24, compact_tile=T,
            tiled=n_tiles > 1, split=split, W=24,
            stage_rows=autotune.WAVE_SPLIT_STAGE_ROWS)
        # the accumulators are no larger than the one-hot dot's, and
        # three fifths of them leave the kernel
        assert blk["acc"] == (24, -(-geom["F_rows"] // 4), 40, 128)
        assert blk["hist"] == (24, -(-geom["F_rows"] // 4), 24, 128)
        assert np.prod(blk["acc"]) <= geom["groups"] * geom["gb_pad"] * 128
        assert blk["staged"][1] == autotune.WAVE_SPLIT_STAGE_ROWS
        assert autotune.hist_vmem_bytes(
            chunk=c["chunk"], geom=geom, W=24, fused=True, variant="hilo5",
            tiled=n_tiles > 1) <= autotune.PALLAS_VMEM_BUDGET_BYTES
        assert n_tiles == (1 if cell == "criteo"
                           else -(-_CELLS[cell]["F"] // 64))
    assert autotune.root_pass_macs(B=255, nchan=5, split=True) == 5120
    assert autotune.root_pass_macs(B=255, nchan=5, split=False) == 32768


@pytest.fixture
def compacting_growers(monkeypatch):
    """Test-sized geometries compact (the rule's threshold at 0), and
    traces made under a patched rule stay out of other tests' caches."""
    jax.clear_caches()
    monkeypatch.setattr(autotune, "HIST_COMPACT_MIN_MACS", 0)
    gauge = obs.default_registry().gauge("hist/wave_macs")
    monkeypatch.setattr(gauge, "_value", -1.0)
    yield gauge
    jax.clear_caches()


def _grow_three(B, precision="highest"):
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    r = np.random.default_rng(23)
    n, f = 1500, 10
    bins = r.integers(0, B, (f, n)).astype(np.uint8)
    meta = FeatureMeta(
        num_bin=np.full(f, B, np.int32), missing_type=np.zeros(f, np.int32),
        default_bin=np.zeros(f, np.int32), monotone=np.zeros(f, np.int32),
        penalty=np.ones(f, np.float32))
    cfg = WaveGrowerConfig(
        num_leaves=31, num_bins=B, wave_size=8, chunk=512,
        route="pallas-tpu", precision=precision,
        hp=SplitParams(min_data_in_leaf=5, has_cat=False))
    grow = make_wave_grower(cfg, meta)
    bag = jnp.asarray((r.uniform(size=n) < 0.85).astype(np.float32))
    hess = jnp.asarray(r.uniform(0.2, 0.3, n).astype(np.float32))
    out = []
    for t in range(3):
        y = (bins[t] > B // 2) ^ (bins[t + 4] > B // 3)
        grad = jnp.asarray(np.where(y, -0.5, 0.5).astype(np.float32)
                           + r.normal(0, 0.1, n).astype(np.float32))
        rec, leaf = grow(jnp.asarray(bins), grad, hess, bag,
                         jnp.ones(f, bool))
        out.append((rec, np.asarray(leaf)))
    return out


@pytest.mark.parametrize("precision", ["highest", "default"])
def test_three_trees_have_the_one_hot_dots_structure_and_counts(
        precision, compacting_growers, monkeypatch):
    """Through the whole grower, three trees on three gradients: every
    wave pass by the flush by slot against the same grower with the
    rule's other side forced (the parent's one-hot dot): the same
    splits, leaves and counts, values to f32 rounding; the record's
    third count reads the pairs (over the blocks) against one a unit,
    and the gauge the MACs of one block-dot either way."""
    new = _grow_three(255, precision)
    nch = 5 if precision == "highest" else 4
    assert compacting_growers.value == nch * 8 * 128
    jax.clear_caches()
    monkeypatch.setattr(autotune, "wave_split_applies", lambda **kw: False)
    old = _grow_three(255, precision)
    assert compacting_growers.value == 256 * 128
    for (rn, leaf_n), (ro, leaf_o) in zip(new, old):
        assert int(rn.num_leaves) == 31
        np.testing.assert_array_equal(leaf_n, leaf_o)
        for name in ("num_leaves", "split_leaf", "split_feature",
                     "split_bin", "split_default_left", "leaf_count",
                     "internal_count"):
            np.testing.assert_array_equal(np.asarray(getattr(rn, name)),
                                          np.asarray(getattr(ro, name)),
                                          name)
        # (a gain is a small difference of large sums)
        for name, rtol in (("leaf_output", 2e-5), ("leaf_sum_g", 2e-5),
                           ("leaf_sum_h", 2e-5), ("split_gain", 5e-4)):
            np.testing.assert_allclose(np.asarray(getattr(rn, name)),
                                       np.asarray(getattr(ro, name)),
                                       rtol=rtol, atol=1e-6, err_msg=name)
        wn, wo = np.asarray(rn.wave_work), np.asarray(ro.wave_work)
        assert wn[0] == wo[0] and 0 < wn[1] <= wo[1]
        assert wn[2] >= wn[1] and wo[2] == wo[1]


# The kernel a shape on the one-hot dot's side of the rule traces to, as
# sha256 of the text of the pallas_call's jaxpr and grid mapping: taken
# from this PR's parent (commit da71681, ``git archive`` into a scratch
# directory, the function below with that tree on the path) under jax
# 0.9.0. A JAX upgrade that prints a jaxpr otherwise re-takes them from
# the commit before it; a change to _fused_kernel that moves them moved
# what these shapes lower.
_PARENTS_KERNEL = {
    "int8_67x255": (
        dict(F=67, N=1 << 16, W=40, num_bins=255, chunk=16384,
             precision="int8", gh_scale=(1.0, 1.0)),
        "fcaf50486e1667755d54826f60cb353d"
        "c9afbf0f6db3f532844156f7865d0e4f"),
    "proxy_67x255": (
        dict(F=67, N=1 << 16, W=64, num_bins=255, chunk=16384,
             precision="int8", gh_scale=(1.0, 1.0), count_proxy=True),
        "5ed1ddaa1366d6cc8c75883bd46fb7b1"
        "9e7ed0119b0e5fc54ded743f6251df9e"),
    "hilo5_28x63": (
        dict(F=32, N=1 << 16, W=24, num_bins=64, chunk=4096,
             precision="highest", variant="hilo5"),
        "8d1ffa747f4d14aa7f6fd7d3515eecae"
        "d1b3507dca62df964555478780950f3b"),
    "hilo5_48bins_compacting": (
        dict(F=120, N=1 << 15, W=24, num_bins=48, chunk=4096,
             precision="highest", variant="hilo5"),
        "c115820f7186b2e3053a158afff45682"
        "52c07d5052ca72a9af05e18b32421709"),
}


def _kernel_text(*, F, N, W, **kw):
    """The one pallas_call of the fused kernel's wrapper at these
    shapes: its jaxpr and grid mapping, as text."""
    import functools
    S = jax.ShapeDtypeStruct
    args = (S((F, N), jnp.uint8), S((N,), jnp.float32), S((N,), jnp.float32),
            S((N,), jnp.float32), S((N,), jnp.int32), S((18, W), jnp.int32))
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(functools.partial(
        fused_partition_histogram_pallas, any_cat=False, **kw))(*args).jaxpr)
    (eqn,) = found
    return str(eqn.params["jaxpr"]) + "\n" + str(eqn.params["grid_mapping"])


@pytest.mark.parametrize("name", sorted(_PARENTS_KERNEL))
def test_the_one_hot_dots_side_lowers_the_parents_kernel(name):
    """The int8 tiers (which compact at 67 x 255), a shape that does not
    compact (28 x 63, the masked side) and one that compacts under 57
    bins keep _accumulate_hist, one T-row staging tile and one SMEM
    scalar: the kernel they trace to is the parent's, text for text."""
    import hashlib
    kw, want = _PARENTS_KERNEL[name]
    text = _kernel_text(**kw)
    assert "dot_general" in text
    assert hashlib.sha256(text.encode()).hexdigest() == want
