"""Ask the TPU's own compiler, without the chip: AOT-compile the Pallas
hot-path kernels at real widths for a DESCRIBED v5e.

Interpret mode (the rest of the suite) proves the kernels' arithmetic;
it cannot vouch for Mosaic legality — slices not aligned to the tiling,
more VMEM than a kernel may use, ops the TPU lowering lacks. The TPU
compiler is installed here and compiles for a topology that is
described, not attached (on-chip-measurement guide §2), so these cases
guard every later PR at no chip time. Nothing runs: a pass says "the
compiler accepts it", never "it is right" or "it is fast" —
``chip_smoke.py`` is the run on the chip.

Widths are the deployed ones: HIGGS (28 features -> 32 with the step
registry's mult-of-8 pad, 63 bins -> 64, 255 leaves, the wave width of
each tier) and the LRB window model (53 -> 56 features, 255 bins ->
256, 31 leaves). Row counts and chunks are cut — compile time grows
with the chunk, legality does not change with the row count. The edge
shapes of the retired ``tests/tpu_shape_sweep.py`` (F=1, 4-bin,
odd-F packed4, multiclass, sub-chunk N) ride as further cases.

The topology is described ONLY inside the module-scoped fixture below:
never at import time and never from a child process — one process at a
time may load the TPU library, and every xdist worker imports this
file.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on ONE described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _mosaic(compiled) -> int:
    """Number of Mosaic kernels the compiled program carries."""
    return compiled.as_text().count("tpu_custom_call")


def test_described_chip_reports_the_kind_the_vmem_table_knows(topo):
    """The VMEM limit/budget (ops/autotune.py) are fractions of a
    capacity keyed by the string the chip itself reports — a v5e says
    'TPU v5 lite', not 'TPU v5e' — and an unknown TPU kind is an
    error, not a v5e."""
    from lightgbm_tpu.ops import autotune
    kind = topo.devices[0].device_kind
    assert kind in autotune.TPU_VMEM_CAPACITY_BYTES, kind
    cap = autotune.TPU_VMEM_CAPACITY_BYTES[kind]
    assert (autotune.PALLAS_VMEM_BUDGET_BYTES
            < autotune.PALLAS_VMEM_LIMIT_BYTES <= cap)


# (F, B, W, precision, variant, count_proxy, packed4, any_cat, N, chunk)
_HIST_CASES = {
    # HIGGS widths, every tier the trainer can resolve to
    "higgs-int8-proxy-W64": (32, 64, 64, "int8", None, True, False,
                             False, 1 << 17, 4096),
    "higgs-int8-3ch-W40": (32, 64, 40, "int8", None, False, False,
                           False, 1 << 17, 4096),
    "higgs-hilo5-W24": (32, 64, 24, "highest", "hilo5", False, False,
                        False, 1 << 17, 4096),
    "higgs-hilo4-W32": (32, 64, 32, "highest", "hilo4", False, False,
                        False, 1 << 17, 4096),
    "higgs-hilo3-W40": (32, 64, 40, "highest", "hilo3", False, False,
                        False, 1 << 17, 4096),
    # LRB window model: 53 features (+3 pad), 255 bins, 31 leaves
    "lrb-hilo4-W30": (56, 256, 30, "highest", "hilo4", False, False,
                      False, 1 << 14, 1024),
    # the benchmark's cell (criteo_share.train): 67 features, 255 bins,
    # the pinned 16384-row chunk, both exact layouts the tuner times.
    # A dotted row costs enough here (autotune.hist_compact_tile) that
    # the kernel compacts rows ahead of its dot
    "criteo-hilo5-W24": (67, 255, 24, "highest", "hilo5", False, False,
                         False, 1 << 16, 16384),
    "criteo-hilo4-W32": (67, 255, 32, "highest", "hilo4", False, False,
                         False, 1 << 16, 16384),
    # the same width on the int8 count-proxy tier: it compacts too (the
    # scan's rank and gather dots are bf16 in every tier)
    "criteo-int8-proxy-W64": (67, 255, 64, "int8", None, True, False,
                              False, 1 << 16, 16384),
    # the benchmark's wide cell (epsilon_wide.train): 2,000 features at
    # 255 bins, no resident block of which fits VMEM, so both kernels
    # walk feature tiles (tests/test_wide_features.py); the chunk is
    # the one the cell's runs resolved
    "epsilon-hilo5-W24": (2000, 256, 24, "highest", "hilo5", False, False,
                          False, 1 << 16, 16384),
    # the benchmark's ranking cell (yahoo_ltr.train_rank): 700 features
    # in 11 tiles of 64, the chunk its cold tunes took
    "yahoo-hilo5-W24": (700, 255, 24, "highest", "hilo5", False, False,
                        False, 1 << 16, 4096),
    # edge shapes (the retired on-chip shape sweep)
    "edge-F1": (1, 64, 14, "int8", None, True, False, False, 8192, 4096),
    "edge-4bin-packed4-oddF": (27, 16, 64, "int8", None, True, True,
                               False, 1 << 15, 4096),
    "edge-4bin-unpacked": (6, 4, 14, "int8", None, True, False, False,
                           8192, 4096),
    "edge-categorical": (8, 64, 14, "highest", "hilo5", False, False,
                         True, 8192, 4096),
    "edge-sub-chunk-N": (8, 64, 14, "highest", "hilo4", False, False,
                         False, 900, 4096),
}


def _hist_args(spec, case, fused):
    F, B, W, prec, variant, proxy, packed4, any_cat, N, chunk = case
    f_rows = (F + 1) // 2 if packed4 else F
    kw = dict(num_bins=B, chunk=chunk, precision=prec,
              gh_scale=(1.0, 1.0) if prec == "int8" else None,
              count_proxy=proxy, packed4=packed4,
              num_features=F if packed4 else None,
              variant=variant or "hilo5")
    rows = (spec((f_rows, N), jnp.uint8), spec((N,), jnp.float32),
            spec((N,), jnp.float32))
    if fused:
        kw["any_cat"] = any_cat
        return rows + (spec((N,), jnp.float32), spec((N,), jnp.int32),
                       spec((18, W), jnp.int32)), kw
    return rows + (spec((N,), jnp.int32), spec((W,), jnp.int32)), kw


@pytest.mark.parametrize("name", sorted(_HIST_CASES))
def test_fused_partition_histogram_kernel_compiles(spec, name):
    from lightgbm_tpu.ops.hist_wave import \
        fused_partition_histogram_pallas
    args, kw = _hist_args(spec, _HIST_CASES[name], fused=True)
    compiled = jax.jit(functools.partial(
        fused_partition_histogram_pallas, **kw)).lower(*args).compile()
    assert _mosaic(compiled) == 1


# which flush a case's fused kernel takes by the rule
# (autotune.wave_split_applies), and the feature tiles it walks: the
# three benchmark cells and the LRB window model dot a slot at a time,
# by the root's two digits; the int8 tiers and a dot too cheap to
# compact for keep the one-hot dot
_FLUSH_BY_SLOT = {"criteo-hilo5-W24": 1, "criteo-hilo4-W32": 1,
                  "epsilon-hilo5-W24": 32, "yahoo-hilo5-W24": 11,
                  "lrb-hilo4-W30": 1}


@pytest.mark.parametrize("name", sorted(_HIST_CASES))
def test_fused_kernels_flush_is_the_rules(name):
    """The cases above compile whichever flush the rule hands them; this
    says which, so that a case cannot change sides unseen: the split's
    cases are the compacting bf16 tiers at 57 to 256 bins."""
    from lightgbm_tpu.ops import autotune
    F, B, W, prec, variant, proxy, packed4, _, N, chunk = _HIST_CASES[name]
    int8 = prec == "int8"
    kw = dict(int8=int8, count_proxy=proxy,
              variant=variant if prec == "highest" else None)
    geom, n_tiles = autotune.hist_feature_tiling(
        F=F, B=B, W=W, chunk=chunk, fused=True,
        F_rows=(F + 1) // 2 if packed4 else F, **kw)
    split = autotune.fused_wave_split(
        geom=geom, compact_tile=autotune.hist_compact_tile(
            geom=geom, chunk=chunk, int8=int8), **kw)
    assert (split is not None) == (name in _FLUSH_BY_SLOT)
    if split:
        assert n_tiles == _FLUSH_BY_SLOT[name]
        assert autotune.hist_vmem_bytes(
            chunk=chunk, geom=geom, W=W, fused=True, tiled=n_tiles > 1,
            **kw) <= autotune.PALLAS_VMEM_BUDGET_BYTES


@pytest.mark.parametrize("name", ["higgs-int8-proxy-W64",
                                  "higgs-hilo4-W32", "lrb-hilo4-W30",
                                  "edge-4bin-packed4-oddF",
                                  "epsilon-hilo5-W24"])
def test_wave_histogram_kernel_compiles(spec, name):
    """The partition-free wave kernel: every tree's root pass."""
    from lightgbm_tpu.ops.hist_wave import wave_histogram_pallas
    args, kw = _hist_args(spec, _HIST_CASES[name], fused=False)
    compiled = jax.jit(functools.partial(
        wave_histogram_pallas, **kw)).lower(*args).compile()
    assert _mosaic(compiled) == 1


@pytest.mark.parametrize("F, N", [(67, 10_485_760), (2000, 393_216)],
                         ids=["criteo", "epsilon"])
def test_root_histogram_kernel_compiles_at_the_cells_shapes(spec, F, N):
    """The root pass's kernel of its own (a two-digit split of the bin
    axis) at both benchmark cells' whole shapes, 255 bins, the cells'
    chunk and layout: Mosaic accepts it, the tile it walks is priced
    inside the VMEM budget, and its one rolled group body compiles in
    seconds (the wave kernel's 67 unrolled groups took 130 s)."""
    import time
    from lightgbm_tpu.ops import autotune
    from lightgbm_tpu.ops.hist_wave import root_histogram_pallas
    chunk, geom, tiles = autotune.root_hist_tiling(F=F, B=255, nchan=5,
                                                   chunk=16384)
    assert chunk == 16384
    assert autotune.root_hist_vmem_bytes(chunk=16384, geom=geom) \
        <= autotune.PALLAS_VMEM_BUDGET_BYTES
    args = (spec((F, N), jnp.uint8), spec((N,), jnp.float32),
            spec((N,), jnp.float32), spec((N,), jnp.int32))
    t0 = time.perf_counter()
    compiled = jax.jit(functools.partial(
        root_histogram_pallas, num_bins=255, chunk=16384,
        precision="highest", variant="hilo5")).lower(*args).compile()
    print(f"root kernel, {F} features in {tiles} tile(s) of "
          f"{geom['F']} rows: compiled in "
          f"{time.perf_counter() - t0:.1f} s")
    assert _mosaic(compiled) == 1


@pytest.mark.parametrize("layout, F, N, chunk", [
    ("hilo5", 67, 10_485_760, 65536), ("hilo5", 2000, 393_216, 65536),
    ("hilo5", 2000, 393_216, 32768), ("hilo3", 67, 10_485_760, 65536),
    ("bf16", 2000, 393_216, 32768)],
    ids=["criteo-65536", "epsilon-65536", "epsilon-32768",
         "criteo-hilo3-65536", "epsilon-bf16-32768"])
def test_root_histogram_kernel_compiles_at_the_tuners_largest_chunks(
        spec, layout, F, N, chunk):
    """The grower's chunk is the tuner's, priced for the wave and fused
    kernels: 32768 is offered both cells' shapes, 65536 under
    tpu_autotune=exhaustive. The root kernel's operands outgrow VMEM
    there, so it walks the largest half of the chunk that fits
    (autotune.root_hist_tiling) and Mosaic accepts that: the three-channel
    layouts keep a larger chunk than hilo5's 16384, one no cell runs."""
    from lightgbm_tpu.ops import autotune
    from lightgbm_tpu.ops.hist_wave import root_histogram_pallas, root_nchan
    kw = dict(hilo5=dict(precision="highest", variant="hilo5"),
              hilo3=dict(precision="highest", variant="hilo3"),
              bf16=dict(precision="default"))[layout]
    nchan = root_nchan(kw["precision"], kw.get("variant"))
    own, geom, tiles = autotune.root_hist_tiling(F=F, B=255, nchan=nchan,
                                                 chunk=chunk)
    assert own == (16384 if nchan == 5 else 32768)
    args = (spec((F, N), jnp.uint8), spec((N,), jnp.float32),
            spec((N,), jnp.float32), spec((N,), jnp.int32))
    compiled = jax.jit(functools.partial(
        root_histogram_pallas, num_bins=255, chunk=chunk,
        **kw)).lower(*args).compile()
    print(f"root kernel ({layout}), asked {chunk} rows a step, walks "
          f"{own} in {tiles} tile(s) of {geom['F']} rows")
    assert _mosaic(compiled) == 1


def test_largest_offered_chunk_is_priced_inside_what_compiles(spec):
    """The tuner's VMEM pricing against the compiler: the LARGEST
    chunk hist_chunk_candidates offers a proxy-tier geometry (priced
    under the 72 MB budget) compiles under the 100 MB limit the
    kernels ask for. F is cut to 8 — compile time grows with the
    feature groups, the per-chunk working set barely does; the full
    candidate grid at F=28 (and the 65536-row chunk the pricing
    refuses) was compiled once in the PR-22 rehearsal."""
    from lightgbm_tpu.ops import autotune
    from lightgbm_tpu.ops.hist_wave import \
        fused_partition_histogram_pallas
    cands = autotune.hist_chunk_candidates(
        F=8, B=64, W=64, fused=True, int8=True, count_proxy=True,
        n_rows=1 << 20)
    top = max(c["chunk"] for c in cands)
    assert top == 32768
    case = (8, 64, 64, "int8", None, True, False, False, 1 << 16, top)
    args, kw = _hist_args(spec, case, fused=True)
    compiled = jax.jit(functools.partial(
        fused_partition_histogram_pallas, **kw)).lower(*args).compile()
    assert _mosaic(compiled) == 1


def test_largest_offered_chunk_of_a_compacting_geometry_compiles(spec):
    """The same question where the kernel compacts rows ahead of its dot
    (the benchmark cell's geometry): the pricing then carries the
    [C, T] staging buffer, the payload and bin rows in bf16, the [T, T]
    one-hots and the gathered result, and T-wide one-hot tiles in place
    of chunk-wide ones."""
    from lightgbm_tpu.ops import autotune
    from lightgbm_tpu.ops.hist_wave import \
        fused_partition_histogram_pallas
    F, B, W = 67, 255, 32
    geom = autotune.hist_geometry(F=F, B=B, W=W)
    cands = autotune.hist_chunk_candidates(
        F=F, B=B, W=W, fused=True, variant="hilo4", n_rows=1 << 24)
    top = max(c["chunk"] for c in cands)
    assert top == 32768
    assert autotune.hist_compact_tile(geom=geom, chunk=top) == \
        autotune.HIST_COMPACT_TILE
    case = (F, B, W, "highest", "hilo4", False, False, False, 1 << 16, top)
    args, kw = _hist_args(spec, case, fused=True)
    compiled = jax.jit(functools.partial(
        fused_partition_histogram_pallas, **kw)).lower(*args).compile()
    assert _mosaic(compiled) == 1


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "wave"])
def test_epsilon_tiles_are_priced_inside_the_budget(fused):
    """What the two epsilon cases above compile is what the pricing
    says: tiles of 64 bin rows, each inside the VMEM budget, where the
    whole matrix in one block is over twice the chip's VMEM."""
    from lightgbm_tpu.ops import autotune
    F, B, W, _p, variant, *_rest, chunk = _HIST_CASES["epsilon-hilo5-W24"]
    geom, tiles = autotune.hist_feature_tiling(
        F=F, B=B, W=W, chunk=chunk, fused=fused, variant=variant)
    assert (tiles, geom["F_rows"]) == (32, 64)
    assert autotune.hist_vmem_bytes(
        chunk=chunk, geom=geom, W=W, fused=fused, variant=variant,
        tiled=True) <= autotune.PALLAS_VMEM_BUDGET_BYTES
    assert autotune.hist_vmem_bytes(
        chunk=chunk, geom=autotune.hist_geometry(F=F, B=B, W=W), W=W,
        fused=fused, variant=variant) > 2 * autotune.TPU_VMEM_CAPACITY_BYTES[
            "TPU v5 lite"]


@pytest.mark.parametrize("F, rows", [(67, 67), (2000, 64)],
                         ids=["criteo", "epsilon"])
def test_feature_tile_of_the_benchmark_cells_is_unmoved(F, rows):
    """The compaction's working set only shrank (a [C, T] staging
    buffer, [T, T] one-hots), and the flush by slot keeps accumulators
    of the one-hot dot's size beside a 2,048-row stage: both cells keep
    the tile they ran with, one resident block at 67 features, 64 stored
    rows (the 64-group cap) at 2,000: the fused kernel at every chunk
    the tuner offers, the root kernel (whose pricing did not change) at
    the cells' own. The one move: hilo4's 32-slot wave at the largest
    offered chunk no longer holds 67 features, nor a tile of 64, in one
    block (81 MB priced at 67: the stage, the ordered tile and a third
    more slots' accumulators beside 16.8 MB of partition temporaries)
    and walks tiles of 32; the tuner times that pair against the
    smaller chunks."""
    from lightgbm_tpu.ops import autotune
    for variant, W in (("hilo5", 24), ("hilo4", 32)):
        for chunk, fused in [(c, True) for c in (4096, 8192, 16384, 32768)
                             ] + [(16384, False)]:
            want = 32 if (variant, chunk, fused) == ("hilo4", 32768, True) \
                else rows
            assert autotune.hist_feature_tile(
                F=F, B=255, W=W, chunk=chunk, fused=fused,
                variant=variant) == want, (variant, chunk, fused)
    geom = autotune.hist_geometry(F=min(F, rows), B=255, W=24, F_rows=rows)
    blk = autotune.fused_hist_block_shapes(
        chunk=16384, geom=geom, tbl_rows=24,
        compact_tile=autotune.HIST_COMPACT_TILE, tiled=F > rows)
    assert blk["staged"] == (
        autotune.HIST_COMPACT_PAY_ROWS + -(-rows // 16) * 16,
        autotune.HIST_COMPACT_TILE)


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_eqns(inner)


def test_criteo_width_still_lowers_with_one_tile(spec):
    """67 features at 255 bins fit one resident block: the fused kernel
    keeps its 1-D grid over row chunks and the in-kernel gather of the
    split columns (four operands, no ``cols``), as before the tile
    axis; the epsilon width walks a 2-D grid with the fifth."""
    from lightgbm_tpu.ops.hist_wave import \
        fused_partition_histogram_pallas
    seen = {}
    for name in ("criteo-hilo5-W24", "epsilon-hilo5-W24"):
        args, kw = _hist_args(spec, _HIST_CASES[name], fused=True)
        fn = functools.partial(fused_partition_histogram_pallas, **kw)
        (call,) = _pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        seen[name] = (len(call.params["grid_mapping"].grid),
                      len(call.invars))
    assert seen == {"criteo-hilo5-W24": (1, 4),
                    "epsilon-hilo5-W24": (2, 5)}


@pytest.mark.parametrize("F", [67, 700])
def test_float64_bin_kernel_compiles_at_the_cells_widths(spec, F):
    """The dense float64 route's chunk kernel (io/ingest.py) at the
    criteo and yahoo widths, 255 levels a column, at the chunk rows it
    picks: the raw words in, keyed and counted on the chip, holding no
    more than the two key planes and the count ([Fn, C] each) besides
    its operands."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.io.ingest import DeviceBinner
    X = np.random.default_rng(F).integers(0, 255, (2000, F)).astype(
        np.float64)
    cfg = Config().set({"objective": "regression", "max_bin": 255,
                        "tpu_ingest": 0, "enable_bundle": False})
    ds = TpuDataset(cfg).construct_from_matrix(
        X, Metadata(label=np.zeros(2000, np.float32)))
    binner = DeviceBinner(ds.mappers, ds.used_feature_map, cfg,
                          np.float64)
    C = binner.chunk_rows
    assert binner.counts and binner._Bp == 256
    compiled = binner._chunk_fn.lower(spec((C, 2 * F), jnp.uint32),
                                      spec((C, 0), jnp.int32)).compile()
    plane = -(-F // 8) * 8 * C * 4          # [Fn, C] int32, sublane-padded
    assert compiled.memory_analysis().temp_size_in_bytes <= 3 * plane


@pytest.mark.parametrize("num_leaves", [255, 31])
def test_leaf_gather_kernel_compiles(spec, num_leaves):
    from lightgbm_tpu.ops.predict import leaf_gather_pallas
    compiled = leaf_gather_pallas.lower(
        spec((num_leaves,), jnp.float32),
        spec((1 << 20,), jnp.int32)).compile()
    assert _mosaic(compiled) == 1


# (F, per-feature table width, trees, leaves, classes)
_FOREST_CASES = {
    "higgs-500x255": (28, 72, 500, 255, 1),
    "lrb-50x31": (53, 96, 50, 31, 1),
    "edge-multiclass-K3": (5, 64, 12, 15, 3),
}


@pytest.mark.parametrize("from_x", [False, True],
                         ids=["codes", "from_x"])
@pytest.mark.parametrize("name", sorted(_FOREST_CASES))
def test_forest_kernel_compiles_at_the_tiles_the_guard_picks(
        spec, name, from_x):
    """The fused forest kernel at the stack shapes
    ``_device_arrays_pallas`` produces and the tree chunk ``_pallas_tc``
    picks for them (TC = 16 at the bench shape), both entry points:
    pre-binned codes and the device-binning ``forest_predict_from_x``."""
    from lightgbm_tpu.ops.stacked_predict import (
        StackedModel, forest_predict_from_x, forest_predict_pallas)
    F, per, T, L, K = _FOREST_CASES[name]
    row_tile, N = 512, 1 << 14
    offs = tuple(range(0, F * per + 1, per))
    sm = StackedModel.__new__(StackedModel)
    sm._S, sm._L, sm._Wtot, sm.num_class = L - 1, L, F * per, K
    sm._offsets = np.asarray(offs)
    tc = min(sm._pallas_tc(row_tile), T)
    assert tc == min(16, T), "VMEM guard no longer admits the bench tile"
    Sp, Lp = -(-(L - 1) // 128) * 128, -(-L // 128) * 128
    steps = -(-T // tc)
    stacks = (spec((steps, F * per, tc * Sp), jnp.int8),
              spec((steps, tc, Sp, Lp), jnp.int8),
              spec((steps, tc, Lp), jnp.int32),
              spec((steps, tc, Lp), jnp.float32),
              spec((steps, tc, K), jnp.float32))
    if from_x:
        lowered = forest_predict_from_x.lower(
            spec((N, F), jnp.float32), spec((F, per - 2), jnp.float32),
            spec((F,), jnp.int32), spec((F,), jnp.int32), *stacks,
            offsets=offs, row_tile=row_tile)
    else:
        lowered = forest_predict_pallas.lower(
            spec((F, N), jnp.int32), *stacks, offsets=offs,
            row_tile=row_tile)
    assert _mosaic(lowered.compile()) == 1


def _step_under_test(monkeypatch, tier, mesh=None, *,
                     shape=(1 << 17, 1 << 14, 32, 64), chunk=4096,
                     objective=None):
    """(jitted step, grower config pieces) of ops/step_cache.py
    build_train_step at HIGGS widths (``shape`` = train rows, valid rows,
    features, bins; ``objective(N)`` an initialised objective in the
    binary one's place). The grower factory asks
    utils/device which backend it is on (it would take its interpret
    branch on this CPU host), so the test steers that — here, not
    through an option of the program."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops import autotune, step_cache
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    from lightgbm_tpu.parallel.learners import make_data_parallel_grower
    from lightgbm_tpu.utils import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    (N, nvalid, F, B), L = shape, 255
    if tier == "proxy":
        gcfg = WaveGrowerConfig(
            num_leaves=L, num_bins=B, wave_size=64, chunk=4096,
            precision="int8", count_proxy=True, route="pallas-tpu",
            quant_psum=mesh is not None,
            hp=SplitParams(has_cat=False, count_lb=True))
    else:
        gcfg = WaveGrowerConfig(
            num_leaves=L, num_bins=B,
            wave_size=autotune.EXACT_TIER_CAPS[tier], chunk=chunk,
            precision="highest", exact_variant=tier,
            route="pallas-tpu", hp=SplitParams(has_cat=False))
    meta = FeatureMeta(
        num_bin=np.full(F, B, np.int32),
        missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        monotone=np.zeros(F, np.int32),
        penalty=np.ones(F, np.float32),
        is_cat=np.zeros(F, np.int32))
    grower = (make_wave_grower(gcfg, meta) if mesh is None
              else make_data_parallel_grower(gcfg, meta, mesh))
    assert {k: grower.resolved[k] for k in (
        "route", "fused_pallas", "fused_xla", "interpret")} == {
            "route": "pallas-tpu", "fused_pallas": True,
            "fused_xla": False, "interpret": False}
    if objective is None:
        obj = create_objective("binary", Config().set(
            {"objective": "binary"}))
        obj.init(Metadata(label=(np.arange(N) % 2).astype(np.float32)), N)
    else:
        obj = objective(N)
    step = step_cache.build_train_step(
        grower=grower, K=1, n_score=N, n_total=N + nvalid,
        valid_slices=((N, nvalid),) if nvalid else (), num_leaves=L,
        grad_fn=obj.gradient_builder(), renew_alpha=None,
        sample_hook=None, mesh=mesh, row_sharded=mesh is not None)
    return step, (N, nvalid, F), meta, obj.gradient_aux()


def _step_args(spec_rows, spec_rep, dims, meta, obj_aux):
    """The step's argument specs: ``spec_rows`` places arrays whose
    LAST axis is the row axis, ``spec_rep`` everything else."""
    from lightgbm_tpu.ops.split import FeatureMeta
    N, nvalid, F = dims

    def like(a):
        a = np.asarray(a)
        rows = a.ndim and a.shape[-1] == N
        return (spec_rows if rows else spec_rep)(a.shape, a.dtype)

    return (spec_rows((F, N + nvalid), jnp.uint8),
            spec_rows((1, N), jnp.float32),
            (spec_rows((1, nvalid), jnp.float32),) if nvalid else (),
            spec_rows((N + nvalid,), jnp.float32),
            spec_rep((F,), jnp.bool_), spec_rep((), jnp.float32),
            spec_rep((1,), jnp.float32), spec_rep((1, 1), jnp.float32),
            spec_rep((1, 1), jnp.float32), spec_rep((2,), jnp.uint32),
            spec_rows((N,), jnp.bool_),
            FeatureMeta(*[like(a) for a in meta]),
            {"obj": jax.tree_util.tree_map(like, obj_aux),
             "renew": None})


@pytest.mark.parametrize("tier", ["proxy", "hilo4"])
def test_whole_training_step_compiles(spec, monkeypatch, tier):
    """ops/step_cache.py build_train_step end to end — gradients, the
    wave grower (root wave kernel + fused kernel in the while loop),
    leaf gathers, score updates — as ONE program for the described
    chip."""
    step, dims, meta, aux = _step_under_test(monkeypatch, tier)
    compiled = step.lower(*_step_args(spec, spec, dims, meta,
                                      aux)).compile()
    # root wave kernel + fused kernel + the train/valid leaf gathers
    assert _mosaic(compiled) == 4


def test_ranking_step_compiles_at_the_cells_shape(spec, monkeypatch):
    """``yahoo_ltr.train_rank``'s whole step for the described chip: the
    lambdarank pair gradient over the cell's own query tables (458,752 rows
    in ~19,350 queries of 1..139 documents by the benchmark's generator: six
    width classes) ahead of the grower at 704 padded columns (11 feature
    tiles), 256 bins, 255 leaves, chunk 16384. The gradient lowers to
    gathers and reductions: no Mosaic kernel of its own."""
    import sys
    from pathlib import Path
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import create_objective
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmark"))
    import datagen_rank
    rows = 458_752
    data = {"rows": rows, "query_len_min": 1, "query_len_max": 139,
            "query_len_mean": 23.7, "query_len_sigma": 0.9}

    def objective(n):
        lengths = datagen_rank.query_lengths(data, seed=5)
        obj = create_objective("lambdarank",
                               Config().set({"objective": "lambdarank"}))
        obj.init(Metadata(label=(np.arange(n) % 5).astype(np.float32),
                          group=lengths), n)
        assert [c["lab"].shape[1] for c in obj._pair_classes] \
            == [8, 16, 32, 64, 128, 139]
        return obj
    step, dims, meta, aux = _step_under_test(
        monkeypatch, "hilo4", shape=(rows, 0, 704, 256), chunk=16384,
        objective=objective)
    compiled = step.lower(*_step_args(spec, spec, dims, meta,
                                      aux)).compile()
    # the root kernel + the fused kernel + the train rows' leaf gather
    assert _mosaic(compiled) == 3
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) < 3 * 2 ** 30


@pytest.mark.parametrize("tier", ["proxy", "hilo4"])
def test_data_parallel_step_compiles_over_the_four_chip_mesh(
        topo, monkeypatch, tier):
    """tree_learner=data over all four chips of the described host: the
    same step with row-sharded state. GSPMD cannot partition a Mosaic
    kernel, so EVERY kernel must sit under a shard_map — the grower's
    do; the score updates' leaf gather did not until PR 22 (this
    compile refused the step: "Mosaic kernels cannot be automatically
    partitioned"). The histogram psum must come out as an
    all-reduce."""
    from lightgbm_tpu.parallel.learners import AXIS
    mesh = Mesh(np.asarray(topo.devices), (AXIS,))

    def placed(*axes):
        return lambda shape, dt: jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(
                *([None] * (len(shape) - len(axes)) + list(axes)))))

    step, dims, meta, aux = _step_under_test(monkeypatch, tier, mesh)
    compiled = step.lower(*_step_args(placed(AXIS), placed(), dims,
                                      meta, aux)).compile()
    assert _mosaic(compiled) == 4
    assert "all-reduce" in compiled.as_text()
    # each chip holds a quarter of the row-sharded state
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    N, nvalid, F = dims
    assert per_chip < (F * (N + nvalid)) // 2


@pytest.mark.parametrize("rows_a_chip", [10_485_760])
def test_criteo_dp4_step_compiles_at_the_cells_shape(topo, monkeypatch,
                                                     rows_a_chip):
    """The step of cell ``criteo_dp4.train``: tree_learner=data over the
    described 2x2 host at 10,485,760 rows x 67 features (72 as the step
    pads them) a chip, hilo5, chunk 16384, no valid set. Inside the
    shards the kernels are the one-chip cells': the root kernel (one slot:
    what the root's sum carries), the flush by slot, the leaf gather; the
    sums come out as all-reduces; a chip holds a quarter of the rows."""
    from lightgbm_tpu.parallel.learners import AXIS
    mesh = Mesh(np.asarray(topo.devices), (AXIS,))

    def placed(*axes):
        return lambda shape, dt: jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(
                *([None] * (len(shape) - len(axes)) + list(axes)))))

    N, F = 4 * rows_a_chip, 72
    step, dims, meta, aux = _step_under_test(
        monkeypatch, "hilo5", mesh, shape=(N, 0, F, 256), chunk=16384)
    compiled = step.lower(*_step_args(placed(AXIS), placed(), dims,
                                      meta, aux)).compile()
    text = compiled.as_text()
    assert _mosaic(compiled) == 3
    for name in ("wave_histogram_pallas",
                 "fused_partition_histogram_pallas", "leaf_gather_pallas"):
        assert name in text, name
    assert "all-reduce" in text
    m = compiled.memory_analysis()
    per_chip = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"criteo_dp4 step, bytes a chip: arguments "
          f"{m.argument_size_in_bytes}, temp {m.temp_size_in_bytes}")
    # a quarter of the bins and of the row state, the whole pool
    assert F * rows_a_chip < per_chip < 3 * 2 ** 30



def test_op_scope_table_books_each_kernel_to_its_scope(spec, monkeypatch):
    """``obs/scopes.parse_hlo`` on the whole step compiled for the
    described chip, as ``obs.op_scopes()`` reads it on the chip: the
    Mosaic calls, under the names a device trace gives them, lie in the
    scopes the benchmark's readers book them to (the root kernel in
    ``lgbm/root_hist``, the fused kernel in ``lgbm/wave/hist``: the two
    share no name clash there, ROADMAP S2), and every fusion, custom call
    and copy the step runs lies under an ``lgbm/`` scope."""
    from test_op_scopes import RUN, opcodes
    from lightgbm_tpu.obs import scopes
    step, dims, meta, aux = _step_under_test(monkeypatch, "hilo5",
                                             shape=(1 << 15, 0, 32, 64))
    text = step.lower(*_step_args(spec, spec, dims, meta, aux)).compile(
        ).as_text()
    table, ops = scopes.parse_hlo(text), opcodes(text)
    calls = {n.rsplit(".", 1)[0]: table[n] for n in table
             if ops[n] == "custom-call" and "_pallas" in n}
    assert calls == {"wave_histogram_pallas": "lgbm/root_hist",
                     "fused_partition_histogram_pallas": "lgbm/wave/hist",
                     "leaf_gather_pallas": "lgbm/score_update"}
    unscoped = [n for n in table if ops[n] in RUN
                and not scopes.under(table[n], "lgbm")]
    assert not unscoped, unscoped


_HLO_INSTR = re.compile(
    r"^\s+(ROOT\s+)?%?([^\s=]+)\s+=\s+\w+\[([\d,]*)\]\S*\s+"
    r"([a-z][a-z0-9\-]*)\((.*)$")
_HLO_HEADER = re.compile(r"^(?:ENTRY\s+)?%?(\S+)\s+\(")


def _whole_operand_ops(text, dims):
    """``(name, opcode)`` of every instruction of a compiled module's text
    whose array output spans every row of an operand of shape ``dims``
    (its rank, its leading dimension: the whole operand, or a piece of it
    cut along another axis), less those that only pass the buffer on
    (parameters, tuple elements, loops, bitcasts) or update it in place
    (a dynamic-update-slice or a scatter, or a fusion whose root is
    one)."""
    roots, comp, found = {}, None, []
    for line in text.splitlines():
        if not line[:1].isspace():
            m = _HLO_HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        if m.group(1):
            roots[comp] = m.group(4)
        out = tuple(int(d) for d in m.group(3).split(",") if d)
        if len(out) == len(dims) and out[0] == dims[0]:
            called = re.search(r"calls=%?([\w.\-]+)", m.group(5))
            found.append((m.group(2), m.group(4),
                          called.group(1) if called else None))
    in_place = {"dynamic-update-slice", "scatter"}
    passes = {"parameter", "get-tuple-element", "tuple", "while",
              "bitcast"} | in_place
    return [(name, op) for name, op, called in found
            if op not in passes
            and not (op == "fusion" and roots.get(called) in in_place)]


@pytest.mark.parametrize("F, N", [(2000, 393_216), (704, 458_752)],
                         ids=["epsilon", "yahoo"])
def test_row_takes_copy_no_whole_operand_in_the_wave_loop(spec, F, N):
    """The wave loop's two row takes at the wide cells' shapes: the
    parents' histograms out of the ``[255, F, 256, 3]`` f32 pool, which
    the same pass then updates in place, and the split columns out of the
    ``u8[F, N]`` bins. ``take_rows`` lowers to row slices: no
    ``mini-gather-slice`` of XLA's gather expander, and no op in the
    compiled loop writes a buffer that spans every row of the pool or of
    the bins but the pool's in-place updates (a plain ``x[idx]`` copies
    each whole operand in pieces every pass there)."""
    from lightgbm_tpu.ops.hist_wave import take_rows
    L, B, W = 255, 256, 24
    pool_dims, bins_dims = (L, F, B, 3), (F, N)

    def loop(pool, bins, wl, feat, small):
        def body(i, carry):
            pool, acc = carry
            w = (wl + i) % L
            large = take_rows(pool, w) - small
            pool = pool.at[w].set(large, mode="drop")
            pool = pool.at[(w + 7) % L].set(small, mode="drop")
            cols = take_rows(bins, (feat + i) % F)
            return pool, acc + cols.astype(jnp.int32)
        return jax.lax.fori_loop(
            0, 15, body, (pool, jnp.zeros((W, N), jnp.int32)))

    text = jax.jit(loop, donate_argnums=0).lower(
        spec(pool_dims, jnp.float32), spec(bins_dims, jnp.uint8),
        spec((W,), jnp.int32), spec((W,), jnp.int32),
        spec((W, F, B, 3), jnp.float32)).compile().as_text()
    assert "while" in text and "mini-gather-slice" not in text
    assert _whole_operand_ops(text, pool_dims) == []
    assert _whole_operand_ops(text, bins_dims) == []
