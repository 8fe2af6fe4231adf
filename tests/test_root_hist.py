"""The root pass's kernel of its own (ops/hist_wave.py
root_histogram_pallas): a two-digit split of the bin axis.

One leaf needs none of the lanes a wave kernel keeps for other leaves'
channels, so the root kernel asks the MXU for ``nchan x 8 x 128`` MACs
on a row of a feature where the wave kernel's one-hot dot asks for
``Bp x 128``; every histogram cell is the sum of the same products over
the same rows in the same order. Here, interpreted on the CPU:

* against the XLA scatter oracle's slot 0, exactly, on integer-valued
  g and h (every float sum is then exact), over shapes, layouts,
  out-of-bag rows, a row count that is no multiple of the chunk and a
  forced feature tile with a ragged last tile;
* against the wave kernel with one live slot, to the last bit, on random
  float g and h at the same chunk;
* a tree grown with the root kernel has the model text of one grown with
  the wave kernel's root passed in as ``hist_fn``;
* the predicate's two sides, by name; the pricing of the two benchmark
  cells' shapes, at every chunk the tuner offers them; the gauge
  ``hist/root_macs``, set on every path of the grower.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.ops import autotune
from lightgbm_tpu.ops.hist_wave import (root_histogram_pallas,
                                        wave_histogram_pallas,
                                        wave_histogram_xla)

N, CHUNK = 1000, 256          # four grid steps, the last with 24 pad rows
_LAYOUTS = {
    "hilo5": dict(precision="highest", variant="hilo5"),
    "hilo4": dict(precision="highest", variant="hilo4"),
    "hilo3": dict(precision="highest", variant="hilo3"),
    "bf16": dict(precision="default"),
}


def _problem(F, B, layout, *, integers, seed=5):
    r = np.random.default_rng(seed + 1000 * F + B)
    bins = r.integers(0, B, (F, N)).astype(np.uint8)
    leaf = np.where(r.uniform(size=N) < 0.8, 0, -1).astype(np.int32)
    in_bag = (leaf == 0).astype(np.float32)
    if integers:
        g = r.integers(-8, 9, N).astype(np.float32)
        h = r.integers(0, 5, N).astype(np.float32)
    else:
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.1, 1, N).astype(np.float32)
    if layout == "hilo3":
        h = np.ones(N, np.float32)      # the layout's gate: h == the mask
    return jnp.asarray(bins), g * in_bag, h * in_bag, leaf


@pytest.fixture
def root_macs_gauge(monkeypatch):
    """The process-wide gauge ``hist/root_macs`` at -1, and put back to
    what it held when the test ends: whoever runs next on this worker
    reads its own grower's value or the one before."""
    gauge = obs.default_registry().gauge("hist/root_macs")
    monkeypatch.setattr(gauge, "_value", -1.0)
    return gauge


def _one_slot(W=8):
    return jnp.asarray([0] + [-1] * (W - 1), jnp.int32)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("F,B", [(67, 255), (5, 256), (28, 63), (130, 255),
                                 (3, 17)])
def test_root_kernel_equals_the_oracles_slot_0_exactly(F, B, layout):
    bins, g, h, leaf = _problem(F, B, layout, integers=True)
    got = root_histogram_pallas(bins, g, h, leaf, num_bins=B, chunk=CHUNK,
                                interpret=True, **_LAYOUTS[layout])
    want = wave_histogram_xla(bins, g, h, leaf, _one_slot(), num_bins=B)[:1]
    assert got.shape == (1, F, B, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # out-of-bag rows (leaf -1) count nowhere
    assert float(got[0, 0, :, 2].sum()) == float((leaf == 0).sum())


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("F,B,tile", [(67, 255, None), (130, 255, 64),
                                      (67, 255, 32), (28, 63, None)])
def test_root_kernel_equals_the_wave_kernels_slot_0_bit_for_bit(
        F, B, tile, layout):
    """Random floats: the sums round, and round alike — the same
    products in the same order at the same chunk. ``tile`` forces the
    feature-tile axis (130 = 2 x 64 + 2, 67 = 2 x 32 + 3: a ragged last
    tile, and a ragged last group inside it)."""
    bins, g, h, leaf = _problem(F, B, layout, integers=False)
    got = root_histogram_pallas(bins, g, h, leaf, num_bins=B, chunk=CHUNK,
                                interpret=True, feature_tile=tile,
                                **_LAYOUTS[layout])
    old = wave_histogram_pallas(bins, g, h, leaf, _one_slot(), num_bins=B,
                                chunk=CHUNK, interpret=True,
                                **_LAYOUTS[layout])[:1]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))


@pytest.mark.parametrize("precision", ["int8"])
def test_root_kernel_refuses_the_int8_tier(precision):
    bins, g, h, leaf = _problem(8, 255, "hilo5", integers=True)
    with pytest.raises(NotImplementedError, match="bf16 tiers"):
        root_histogram_pallas(bins, g, h, leaf, num_bins=255, chunk=CHUNK,
                              interpret=True, precision=precision)


# (name, keywords of autotune.root_split_applies, the side it takes)
_PREDICATE = [
    ("criteo-255-hilo", dict(B=255, precision="highest"), True),
    ("256-bins-bf16", dict(B=256, precision="default"), True),
    ("int8", dict(B=255, precision="int8"), False),
    ("int8-count-proxy", dict(B=255, precision="int8", count_proxy=True),
     False),
    ("packed4", dict(B=16, precision="highest", packed4=True), False),
    ("higgs-63-bins", dict(B=63, precision="highest"), True),
    ("31-bins", dict(B=31, precision="highest"), False),
    ("256-bins-hilo", dict(B=256, precision="highest"), True),
    ("word-bins-257", dict(B=257, precision="highest"), False),
    ("word-bins-300", dict(B=300, precision="default"), False),
]


@pytest.mark.parametrize("name,kw,takes", _PREDICATE,
                         ids=[p[0] for p in _PREDICATE])
def test_predicate_sends_each_shape_and_tier_to_its_root(name, kw, takes):
    assert autotune.root_split_applies(**kw) is takes


def _grow(B, *, old_root, precision="highest"):
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    r = np.random.default_rng(17)
    n, f = 1200, 12
    bins = r.integers(0, B, (f, n)).astype(np.uint8)
    y = (bins[0] > B // 2) ^ (bins[5] > B // 3)
    grad = jnp.asarray(np.where(y, -0.5, 0.5).astype(np.float32)
                       + r.normal(0, 0.1, n).astype(np.float32))
    hess = jnp.asarray(r.uniform(0.2, 0.3, n).astype(np.float32))
    bag = jnp.asarray((r.uniform(size=n) < 0.8).astype(np.float32))
    meta = FeatureMeta(
        num_bin=np.full(f, B, np.int32), missing_type=np.zeros(f, np.int32),
        default_bin=np.zeros(f, np.int32), monotone=np.zeros(f, np.int32),
        penalty=np.ones(f, np.float32))
    cfg = WaveGrowerConfig(
        num_leaves=15, num_bins=B, wave_size=8, chunk=512, fused=False,
        route="pallas-tpu", precision=precision,
        hp=SplitParams(min_data_in_leaf=5, has_cat=False))
    hist_fn = None
    if old_root:
        def hist_fn(bt, g, h, lids, wl, gh_scale=None):
            return wave_histogram_pallas(
                bt, g, h, lids, wl, num_bins=B, chunk=512, interpret=True,
                precision=precision)
    grow = make_wave_grower(cfg, meta, hist_fn=hist_fn)
    rec, leaf = grow(jnp.asarray(bins), grad, hess, bag, jnp.ones(f, bool))
    return rec, np.asarray(leaf)


@pytest.mark.parametrize("B", [255, 63])
@pytest.mark.parametrize("precision", ["highest", "default"])
def test_tree_grown_with_the_new_root_is_the_old_roots_tree(
        precision, B, root_macs_gauge):
    """The grower's root by the root kernel against the wave kernel's
    root passed in as ``hist_fn`` (which keeps its own root, as the
    parallel learners' and the EFB seam's do): the same tree, field for
    field and bit for bit, so the same model text. The gauge follows
    the grower that was built last: an injected ``hist_fn``'s dot is
    not the grower's to price, and reads 0, not the grower's before."""
    new, leaf_new = _grow(B, old_root=False, precision=precision)
    assert root_macs_gauge.value == (
        5 if precision == "highest" else 3) * 8 * 128
    old, leaf_old = _grow(B, old_root=True, precision=precision)
    assert root_macs_gauge.value == 0.0
    assert int(new.num_leaves) == 15
    np.testing.assert_array_equal(leaf_new, leaf_old)
    for name in new._fields:
        np.testing.assert_array_equal(np.asarray(getattr(new, name)),
                                      np.asarray(getattr(old, name)), name)


def test_narrow_shape_keeps_the_wave_kernels_root(root_macs_gauge):
    """31 bins: the predicate's other side. The gauge reads the wave
    kernel's MACs a row a feature (Bp x 128) and the tree is the
    injected old root's all the same."""
    new, leaf_new = _grow(31, old_root=False)
    assert root_macs_gauge.value == 32 * 128
    old, leaf_old = _grow(31, old_root=True)
    np.testing.assert_array_equal(leaf_new, leaf_old)
    for name in new._fields:
        np.testing.assert_array_equal(np.asarray(getattr(new, name)),
                                      np.asarray(getattr(old, name)), name)


@pytest.mark.parametrize("F,tiles", [(67, 1), (2000, None)],
                         ids=["criteo", "epsilon"])
def test_root_tiles_of_the_benchmark_cells_are_priced_inside_the_budget(
        F, tiles):
    """At the cells' chunk the 67-feature root is one resident block;
    at 2,000 features the bin block and its digit scratches set the
    tile (the accumulators are 20 KiB a feature where the wave kernel's
    are 128 KiB), so the root walks far fewer than the 32 tiles of 64
    rows it walked."""
    chunk, geom, n_tiles = autotune.root_hist_tiling(
        F=F, B=255, nchan=5, chunk=16384)
    assert chunk == 16384
    assert autotune.root_hist_vmem_bytes(chunk=16384, geom=geom) \
        <= autotune.PALLAS_VMEM_BUDGET_BYTES
    assert (geom["H"], geom["L"], geom["gf"], geom["R"]) == (32, 8, 4, 160)
    assert autotune.root_pass_macs(B=255, nchan=5, split=True) == 5120
    assert autotune.root_pass_macs(B=255, nchan=5, split=False) == 32768
    if tiles is not None:
        assert n_tiles == tiles and geom["F"] == F
    else:
        assert n_tiles < 32 and geom["F"] % 32 == 0
        assert n_tiles == -(-F // geom["F"])


# every row chunk the tuner can hand the grower at the two cells' shapes
# (autotune.hist_chunk_candidates prices the wave and fused kernels
# only), tpu_autotune=exhaustive's included, and the chunk a user may
# set by hand
_CELLS = {"criteo": dict(F=67, n_rows=10_485_760),
          "epsilon": dict(F=2000, n_rows=393_216)}
_LAYOUT_NCHAN = {"hilo5": 5, "hilo4": 5, "hilo3": 3, "bf16": 3}


def _offered_chunks(cell):
    return sorted({c["chunk"] for fused in (False, True)
                   for exhaustive in (False, True)
                   for c in autotune.hist_chunk_candidates(
                       B=255, W=24, fused=fused, exhaustive=exhaustive,
                       variant="hilo5", **_CELLS[cell])})


def test_the_tuner_offers_the_cells_chunks_the_root_kernel_must_halve():
    """What the cases below stand on: the candidates reach past the
    16384 both cells pin, to a chunk no tile of the root kernel fits.
    (Until PR 35 tpu_autotune=exhaustive also offered MAX_HIST_CHUNK;
    the fused kernel's flush by slot keeps a 2,048-row stage, its
    ordered copy and the accumulators as scratch beside the chunk's
    25 MB of partition temporaries, and no tile of it is inside the
    budget there. A chunk set by hand still reaches the root kernel:
    the cases below keep 65536.)"""
    for cell in _CELLS:
        assert {16384, 32768} <= set(_offered_chunks(cell))
        assert autotune.MAX_HIST_CHUNK not in _offered_chunks(cell)
    geom = autotune.root_hist_geometry(F=32, B=255, nchan=5)
    assert not autotune.fits_vmem(
        autotune.root_hist_vmem_bytes(chunk=32768, geom=geom))


@pytest.mark.parametrize("layout", sorted(_LAYOUT_NCHAN))
@pytest.mark.parametrize("chunk", [1024, 2048, 4096, 8192, 16384, 32768,
                                   65536])
@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_root_kernel_is_priced_at_every_chunk_the_grower_may_run(
        cell, chunk, layout):
    """The grower's chunk is chosen by what the wave and fused kernels
    cost; the root kernel takes the largest ``chunk / 2^k`` its own
    working set fits at, and a tile at that chunk: never a trace-time
    refusal where the wave kernel's root trained."""
    F, nchan = _CELLS[cell]["F"], _LAYOUT_NCHAN[layout]
    # (65536: no longer the tuner's to offer, still a user's to set)
    assert (chunk in _offered_chunks(cell) or chunk < 4096
            or chunk == autotune.MAX_HIST_CHUNK)
    own, geom, n_tiles = autotune.root_hist_tiling(
        F=F, B=255, nchan=nchan, chunk=chunk)
    assert chunk % own == 0 and own >= min(chunk, 16384)
    assert autotune.root_hist_vmem_bytes(chunk=own, geom=geom) \
        <= autotune.PALLAS_VMEM_BUDGET_BYTES
    assert n_tiles * geom["F"] >= F
    if nchan == 5:
        # the cells' pinned chunk is kept as it is; the tuner's larger
        # ones come down to it
        assert own == min(chunk, 16384)


def test_root_kernel_halves_a_chunk_its_operands_outgrow():
    """End to end, interpreted: at a chunk no tile fits, the wrapper
    walks the halved chunk and returns what the wave kernel returns AT
    that chunk, bit for bit; a row count that is no multiple of either
    chunk is padded by the wrapper."""
    F, B, n = 32, 255, 40_000
    r = np.random.default_rng(3)
    bins = jnp.asarray(r.integers(0, B, (F, n)).astype(np.uint8))
    leaf = np.where(r.uniform(size=n) < 0.8, 0, -1).astype(np.int32)
    g = r.normal(size=n).astype(np.float32) * (leaf == 0)
    h = r.uniform(0.1, 1, n).astype(np.float32) * (leaf == 0)
    assert autotune.root_hist_tiling(F=F, B=B, nchan=5,
                                     chunk=32768)[0] == 16384
    got = root_histogram_pallas(bins, g, h, leaf, num_bins=B, chunk=32768,
                                interpret=True)
    old = wave_histogram_pallas(bins, g, h, leaf, _one_slot(), num_bins=B,
                                chunk=16384, interpret=True)[:1]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
