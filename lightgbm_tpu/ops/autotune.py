"""Kernel autotuner + persistent tuning/compile caches for the Pallas
hot paths.

The engine has two Pallas hot paths — the fused partition+histogram
training kernel (ops/hist_wave.py) and the fused forest prediction
kernel (ops/stacked_predict.py) — and both are tiled: rows stream
through the training kernels in ``chunk``-row grid steps, prediction
rows in ``row_tile`` blocks of ``tc`` trees. The best tiling depends on
the (features, bins, dtype-tier, device) shape in exactly the way the
reference's own tuning guide documents for its GPU kernels
(docs/GPU-Performance.rst max_bin/workgroup trade-offs); one hardcoded
tile cannot serve arbitrary shapes.

This module is the single place that knows about tiles:

1. **Shared VMEM geometry.** ``hist_geometry`` / the ``*_block_shapes``
   functions compute the exact VMEM block shapes the kernels' BlockSpecs
   are built from, and the ``*_vmem_bytes`` predicates price those SAME
   shapes (double-buffering grid-indexed blocks, adding the in-kernel
   temporaries). The kernels import their shapes from here, so the
   VMEM-fit guards can never drift from what the kernels allocate.
2. **The autotuner.** On first encounter of a (kernel, n_features,
   n_bins, dtype-tier, device-kind) key, ``Autotuner.best`` times a
   small VMEM-feasible candidate set (median-of-k wall time with a
   device-sync readback, utils/timing.py) and persists the winner to a
   versioned JSON cache on disk — the same versioned-token discipline
   as the dataset binary cache (io/dataset.py BINARY_TOKEN): a version
   mismatch re-tunes instead of trusting stale entries.
3. **The persistent XLA compile cache.** ``ensure_compile_cache`` wires
   jax's compilation cache (idempotent, never overriding an explicit
   operator setting), so repeated runs skip both the tuning sweep AND
   recompilation.

Config surface: ``tpu_autotune`` (on / off / exhaustive) and
``tpu_tuning_cache`` (cache file path; empty = inside the compile-cache
directory, ``default_tuning_cache_path``). Tuning only ever runs on a
real TPU backend — CPU/interpret callers get the defaults for free. On
that backend nothing is swallowed: a candidate the compiler rejects is
warned about with the compiler's text and counted
(``autotune/candidates_failed``), and a key whose every candidate
failed is fatal (``Autotuner.best``).
"""
from __future__ import annotations

import functools
import json
import math
import os
from typing import Callable, Dict, List, Optional

from ..obs import registry as obs
from ..utils import log, timing

# ---------------------------------------------------------------------------
# Shared VMEM constants and kernel block geometry
# ---------------------------------------------------------------------------

# VMEM capacity per TPU ``device_kind``, keyed by the string the chip
# itself reports (a v5e says "TPU v5 lite") and taken from the TPU
# compiler, not a datasheet: compiling an over-sized kernel for the
# described v5e answers "Used 192.00M of 128.00M vmem" (libtpu 0.0.34).
# The limit and budget below are fractions of THAT capacity, so a TPU
# of a kind not listed here is an error (check_vmem_device), never
# quietly priced as a v5e.
TPU_VMEM_CAPACITY_BYTES = {"TPU v5 lite": 128 * 1024 * 1024}
# scoped-VMEM cap passed to every Pallas hot-path kernel (CompilerParams
# vmem_limit_bytes): the unrolled group loops' temporaries exceed the
# 16 MB default
PALLAS_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
# working-set budget the tile guards/tuner admit against: headroom under
# the limit for Mosaic's own temporaries (the compiler accepts every
# histogram candidate priced inside it at the HIGGS/LRB widths, and
# more — tests/test_tpu_compile.py)
PALLAS_VMEM_BUDGET_BYTES = 72 * 1024 * 1024

# default tiles (the pre-autotuner hardcoded values, kept as the
# fallback for tpu_autotune=off, CPU backends and interpret mode)
DEFAULT_HIST_CHUNK = 8192
DEFAULT_HIST_CHUNK_INT8 = 16384
# largest row chunk any candidate set can offer (the exhaustive tier's
# ceiling) — sharded ingest aligns its shards against THIS bound so
# grower pad adoption (models/gbdt.py) holds for every tunable chunk
MAX_HIST_CHUNK = 65536
DEFAULT_ROW_TILE = 2048


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _nelem(shape) -> int:
    return int(math.prod(shape))


def hist_geometry(*, F: int, B: int, W: int, F_rows: Optional[int] = None
                  ) -> Dict[str, int]:
    """Histogram-kernel tile geometry shared by BOTH wave kernels and
    the VMEM predicates: per-feature bin rows are padded to the
    8-aligned sublane stride Bp, ``group_sz`` features share one
    128-row matmul M-tile, and the accumulator rows pad to gb_pad.
    ``F_rows`` is the HBM bin-matrix row count (ceil(F/2) when 4-bit
    packed)."""
    Bp = _round_up(B, 8)
    group_sz = max(1, 128 // Bp)
    gb = group_sz * Bp
    groups = -(-F // group_sz)
    return dict(Bp=Bp, group_sz=group_sz, gb=gb, groups=groups,
                gb_pad=_round_up(gb, 128), wp=_round_up(W, 8), F=F,
                F_rows=F if F_rows is None else F_rows)


# Feature tiles (ops/hist_wave.py: both histogram kernels walk a grid
# axis of them once one resident block no longer fits). A tile's stored
# bin rows come in whole packed uint8 sublane tiles, and it holds at
# most this many feature groups: the fused kernel's flush unrolls one
# dot a group (8 s of Mosaic compile at 64; the wave kernel rolls its
# loop under tiles), and its accumulators are 128 KiB a group
HIST_TILE_ROW_ALIGN = 32
HIST_TILE_MAX_GROUPS = 64


def hist_feature_tile(*, F: int, B: int, W: int, chunk: int, fused: bool,
                      F_rows: Optional[int] = None, bins_bytes: int = 1,
                      int8: bool = False, count_proxy: bool = False,
                      variant: Optional[str] = None,
                      force: Optional[int] = None,
                      split: Optional[bool] = None,
                      stage_rows: Optional[int] = None) -> int:
    """Stored bin rows of one feature tile of a histogram kernel at this
    row chunk: ALL of them (one tile, the kernel as it was before the
    tile axis) whenever the whole working set is inside the VMEM
    budget; else the widest aligned tile whose accumulators
    (gb_pad x 128 x 4 B a group), double-buffered bin block and
    compaction payload are, under HIST_TILE_MAX_GROUPS; 0 where not
    even the narrowest is (the chunk is then no candidate). A
    trace-time choice from the shapes, no knob; ``force`` (rows, for
    tests and measurements) overrides the pricing only; ``split`` and
    ``stage_rows`` are hist_vmem_bytes' (the fused kernel's alone)."""
    F_rows = F if F_rows is None else F_rows
    if force is not None:
        return min(int(force), F_rows)
    kw = dict(chunk=chunk, W=W, fused=fused, bins_bytes=bins_bytes,
              int8=int8, count_proxy=count_proxy, variant=variant,
              split=split, stage_rows=stage_rows)
    whole = hist_geometry(F=F, B=B, W=W, F_rows=F_rows)
    if fits_vmem(hist_vmem_bytes(geom=whole, **kw)):
        return F_rows
    per_row = -(-F // F_rows)               # 2 where bins are 4-bit packed
    align = math.lcm(HIST_TILE_ROW_ALIGN, whole["group_sz"])
    rows = (HIST_TILE_MAX_GROUPS * whole["group_sz"] // per_row
            // align * align)
    while rows >= align:
        if rows < F_rows and fits_vmem(hist_vmem_bytes(
                geom=hist_geometry(F=rows * per_row, B=B, W=W,
                                   F_rows=rows), tiled=True, **kw)):
            return rows
        rows -= align
    return 0


def hist_feature_tiling(*, F: int, B: int, W: int, chunk: int,
                        F_rows: Optional[int] = None, **kw):
    """(geom, n_tiles) of a histogram kernel call: ``geom`` is the
    hist_geometry of ONE feature tile (hist_feature_tile), of the whole
    matrix where one tile holds it; the kernels build their BlockSpecs
    from it and walk ``n_tiles`` of them. A shape no tile of which fits
    the VMEM budget at this chunk is an error here, before Mosaic is
    asked."""
    F_rows = F if F_rows is None else F_rows
    rows = hist_feature_tile(F=F, B=B, W=W, chunk=chunk, F_rows=F_rows,
                             **kw)
    if rows <= 0:
        raise ValueError(
            f"no feature tile of a histogram kernel at {F} features x "
            f"{B} bins, wave {W}, fits the VMEM budget "
            f"({PALLAS_VMEM_BUDGET_BYTES >> 20} MiB) at a row chunk of "
            f"{chunk}: use a smaller chunk (tpu_hist_chunk)")
    if rows >= F_rows:
        return hist_geometry(F=F, B=B, W=W, F_rows=F_rows), 1
    return (hist_geometry(F=rows * -(-F // F_rows), B=B, W=W, F_rows=rows),
            -(-F_rows // rows))


def wave_hist_block_shapes(*, chunk: int, geom: Dict[str, int]
                           ) -> Dict[str, tuple]:
    """VMEM block shapes of wave_histogram_pallas — the kernel's
    BlockSpecs are built from THESE tuples (under feature tiles an i32
    scratch of the ``bins`` block's shape is allocated beside them)."""
    return {
        "wl": (geom["wp"], 1),                            # f32 const
        "bins": (geom["F_rows"], chunk),                  # grid-indexed
        "ghl": (4, chunk),                                # grid-indexed
        "hist": (geom["groups"], geom["gb_pad"], 128),    # accumulator
    }


# The root pass's two-digit split of the bin axis (ops/hist_wave.py
# root_histogram_pallas): bin b = hi * 2^ROOT_SPLIT_LO_BITS + lo, the
# histogram of a feature the [H, nchan x L] product of its hi one-hot
# and its lo-selected channel rows. L = 8 minimises the rows a feature
# needs (H + nchan x L = 32 + 40 at 256 bins, against 64 + 20 at L = 4
# and 16 + 80 at L = 16, which ran 1.84x / 1.47x slower than L = 8 at
# 67 / 2,000 features: PERF.md section 5, PR 33, call A).
ROOT_SPLIT_LO_BITS = 3
# The root kernel serves the root from this many (padded) bins on: its
# dot spends nchan x L x 128 = 5,120 MACs on a row of a feature whatever
# the bin count, the wave kernel's Bp x 128. Measured on a v5e, the
# kernels alone, hilo5, chunk 16384 (PERF.md section 5, PR 33, call A;
# wave kernel with one live slot against root kernel, ms a pass): 256
# bins 244.8 against 41.06 (67 features, 10,485,760 rows) and 279.9
# against 46.90 (2,000 features, 393,216 rows): 6.0x; 128 bins 50.32
# against 17.11 (67 x 4,194,304): 2.9x; 64 bins 20.33 against 15.51
# (28 x 8,388,608): 1.31x. The lower side of the line is arithmetic,
# not measurement: at 64 bins the MACs stand 8,192 against 5,120 and
# the time 1.31x, so at 48 bins (6,144 against 5,120) there is nothing
# left to buy, and nothing was timed there.
ROOT_SPLIT_MIN_BINS = 64


def root_split_applies(*, B: int, precision: str, count_proxy: bool = False,
                       packed4: bool = False) -> bool:
    """Whether a tree's root pass takes the root kernel of its own
    (ops/hist_wave.py root_histogram_pallas) in place of the wave kernel
    with one live slot: a trace-time choice from the shapes and the
    tier. The bf16 tiers alone (the int8 tiers' operands are not built
    from ops Mosaic lowers at int8: docs/Design.md section 9) and a bin
    count at which the digit split buys MACs and its two digits hold
    (byte bins: over 256 levels the bins are words)."""
    return (precision in ("highest", "default") and not count_proxy
            and not packed4
            and ROOT_SPLIT_MIN_BINS <= _round_up(B, 8) <= 256)


def root_pass_macs(*, B: int, nchan: int, split: bool) -> int:
    """MACs the root pass's dot spends on one row of one feature: the
    root kernel's ``nchan x L`` streamed rows against 128 lanes where
    its digit split serves the root (``split``), the wave kernel's
    ``Bp`` one-hot rows against 128 lanes where it does. The same two
    numbers are what ONE block-dot of the fused kernel's flush spends:
    by the digit split a row meets as many block-dots as its 128-row
    block holds slots, by the one-hot dot against every slot's lanes
    exactly one (gauge hist/wave_macs, ops/wave_grower.py)."""
    return 128 * (nchan << ROOT_SPLIT_LO_BITS if split else _round_up(B, 8))


def root_hist_geometry(*, F: int, B: int, nchan: int) -> Dict[str, int]:
    """Geometry of the root kernel over ``F`` stored bin rows (a feature
    tile's, or all): L = 2^ROOT_SPLIT_LO_BITS low digits, H high digits
    (a packed bf16 sublane tile's multiple that divides 128), ``gf`` =
    128 // H features' hi one-hots side by side as the 128-lane operand
    of one dot, whose other operand streams their ``R`` = ``gf x nchan x
    L`` selected channel rows."""
    Bp = _round_up(B, 8)
    L = 1 << ROOT_SPLIT_LO_BITS
    if Bp > 32 * L:
        raise NotImplementedError(
            f"the root kernel's digit split holds {32 * L} bins, not {B}")
    H = 16 if Bp <= 16 * L else 32
    gf = 128 // H
    return dict(Bp=Bp, L=L, H=H, gf=gf, nchan=nchan, R=gf * nchan * L,
                groups=-(-F // gf), F=F)


def root_hist_block_shapes(*, chunk: int, geom: Dict[str, int]
                           ) -> Dict[str, tuple]:
    """VMEM block shapes of root_histogram_pallas (its BlockSpecs and
    its two i32 scratches, of the ``bins`` block's shape, are built
    from THESE tuples)."""
    return {
        "bins": (geom["F"], chunk),                       # grid-indexed
        "ghl": (4, chunk),                                # grid-indexed
        "hist": (geom["groups"], geom["R"], 128),         # accumulator
    }


def root_hist_vmem_bytes(*, chunk: int, geom: Dict[str, int],
                         bins_bytes: int = 1) -> int:
    """Working-set bytes of one grid step of the root kernel, priced as
    hist_vmem_bytes prices the wave kernels': double-buffered
    grid-indexed blocks, the hi and lo digit scratches with the widened
    block they are cut from, the accumulators, the channel rows
    broadcast over the L sublanes, and one group's operands (the
    selected rows in f32 and bf16, the 128 hi one-hot rows with their
    compare) and matmul result."""
    s = root_hist_block_shapes(chunk=chunk, geom=geom)
    return (2 * _nelem(s["bins"]) * bins_bytes
            + 2 * _nelem(s["ghl"]) * 4
            + 3 * _nelem(s["bins"]) * 4
            + _nelem(s["hist"]) * 4
            + geom["nchan"] * geom["L"] * chunk * 4
            + geom["R"] * chunk * 6
            + 128 * chunk * 6
            + geom["R"] * 128 * 4)


def root_hist_tiling(*, F: int, B: int, nchan: int, chunk: int,
                     bins_bytes: int = 1, force: Optional[int] = None):
    """(chunk, geom, n_tiles) of a root kernel call, given the grower's
    row chunk: the rows a grid step walks and the feature tile it holds,
    both priced against the VMEM budget as hist_feature_tiling prices a
    wave kernel's.

    The chunk first. The grower's is chosen by what the wave and fused
    kernels cost (hist_chunk_candidates prices those two), and the root
    kernel's operands of one group (R + 128 rows of a whole chunk, in
    f32 and bf16) do not shrink with the tile: at 32768, which the tuner
    offers both cells' shapes, 5 channels x 256 bins price 78.3 MB
    against the budget's 75.5 MB at the narrowest tile. So the kernel
    walks the largest ``chunk / 2^k`` at which its narrowest tile fits
    (16384 there: two steps where the waves walk one; its wrapper pads
    the rows itself; at 4096 the kernel alone still read 45.8 ms against
    the wave kernel's 244.8: PERF.md section 5, PR 33, call A8r).

    Then the tile: one wherever the whole working set is inside the
    budget, else the widest tile of whole packed uint8 sublane tiles
    (HIST_TILE_ROW_ALIGN, a multiple of ``gf``) that is. The
    accumulators are ``nchan x L x 128`` floats a feature where a wave
    kernel's are ``Bp x 128``, so the bin block and its digit scratches
    are what a tile is sized by. ``force``: stored rows a tile, for
    tests."""
    def geom(rows):
        return root_hist_geometry(F=rows, B=B, nchan=nchan)

    def fits(rows, c):
        return fits_vmem(root_hist_vmem_bytes(
            chunk=c, geom=geom(rows), bins_bytes=bins_bytes))

    narrowest = min(F, HIST_TILE_ROW_ALIGN)
    while not fits(narrowest, chunk) and chunk % 256 == 0:
        chunk //= 2                     # halves stay whole lane tiles
    if force is not None:
        rows = min(int(force), F)
    elif fits(F, chunk):
        rows = F
    else:
        rows = F // HIST_TILE_ROW_ALIGN * HIST_TILE_ROW_ALIGN
        while rows > 0 and not fits(rows, chunk):
            rows -= HIST_TILE_ROW_ALIGN
        if rows <= 0:
            raise ValueError(
                f"no feature tile of the root histogram kernel at {F} "
                f"features x {B} bins fits the VMEM budget "
                f"({PALLAS_VMEM_BUDGET_BYTES >> 20} MiB) at a row chunk "
                f"of {chunk}")
    return chunk, geom(rows), -(-F // rows)


# rows of one dotted tile, and of one sub-tile of the scan, of the fused
# kernel's row compaction (ops/hist_wave.py _fused_kernel)
HIST_COMPACT_TILE = 512
# The compaction pays where a row through the one-hot dot is dear
# against a row through the scan (then a [2T, T] one-hot and two small
# dots a sub-tile, ~2 ns a row): the dot's MACs a row, groups x gb x 128
# (the int8 tiers' at half, the MXU's int8 rate being twice its bf16
# rate), at or above this. Measured on a v5e (PERF.md section 6, PR 27,
# calls A and B; masked ns a row against scan + dot x share of rows that
# contribute): 2,195,456 MACs (67 features x 255 bins, hilo5) 24.2
# against 2.7 + 22.7 x share; 917,504 (28 x 255) 10.9 against 2.2 +
# 9.6 x share; 229,376 (28 x 63, hilo5) 2.87 against 1.87 + 2.59 x
# share: ahead only under a share of 0.39, which a small tree's waves
# pass; 114,688 (28 x 63, int8) 2.16 against 2.02 + 1.69 x share: behind
# from 0.08 on. The threshold asks for a break-even share of a half or
# more (a wave's smaller children never hold more than half the rows).
# Since PR 31 (a [T, T] one-hot a turn, a second on the turn that fills
# a tile, a 128 x 128 rank triangle) the scan is cheaper: at 67 x 255
# 1.42 + 23.1 x share against the 1.99 + 22.6 x share the [2T, T] route
# read in the same call (no categorical sweep; PERF.md section 6, PR 31,
# call A); the narrow shapes were not timed again, so the threshold
# stands where the old scan put it: re-derive it from a masked-against-
# compacted timing at 28 x 63 before moving it. Since PR 35 a compacted
# row at 57 to 256 bins of a bf16 tier is dotted a slot at a time
# (wave_split_applies) for a quarter to two thirds of the one-hot dot's
# time (6.1 to 15.0 ns against 23.2 at 67 x 255), so the break-even now
# stands on a dearer scan-to-dot ratio than the one above: the same
# re-derivation is owed, and the constant was NOT re-timed or moved.
HIST_COMPACT_MIN_MACS = 1 << 19
# rows of the compaction's payload block ahead of the bin rows (one
# packed bf16 sublane tile; ops/hist_wave.py _PAY_ROWS)
HIST_COMPACT_PAY_ROWS = 16


def hist_compact_tile(*, geom: Dict[str, int], chunk: int,
                      bins_bytes: int = 1, int8: bool = False,
                      force: Optional[bool] = None) -> int:
    """Rows T of the tiles the fused kernel dots after compacting a
    chunk's contributing rows, or 0 where it dots the whole chunk
    under zero weights instead. A trace-time choice from what the
    shapes say — the dot's MACs a row against HIST_COMPACT_MIN_MACS —
    and from what the gather can carry exactly: byte bins of at most
    256 levels (bf16-exact). ``force`` overrides the cost rule only."""
    T = math.gcd(chunk, HIST_COMPACT_TILE)
    if bins_bytes != 1 or geom["Bp"] > 256 or T < 128:
        return 0
    if force is None:
        force = (geom["groups"] * geom["gb"] * 128 // (2 if int8 else 1)
                 >= HIST_COMPACT_MIN_MACS)
    return T if force else 0


# The wave passes' dot by the root's two-digit split (ops/hist_wave.py
# _flush_by_slot): the compacted rows are staged this many at a time,
# put in slot order by one exact gather and dotted a 128-row block at a
# time against the slots the block holds. The ordering costs
# C x WAVE_SPLIT_STAGE_ROWS MACs a dotted row and the (block, slot)
# pairs number at most STAGE_ROWS / 128 + live slots - 1, so a wider
# stage buys fewer pairs a row with a dearer ordering. Measured on a
# v5e, the kernel alone, 67 x 255, hilo5, chunk 16384, a quarter of
# 2,097,152 rows contributing (PERF.md section 5, PR 35, call E; ns a
# dotted row at 1 / 8 / 24 live slots): 1,024 rows 6.13 / 11.63 /
# 21.39, 2,048 6.13 / 10.14 / 15.07, 4,096 5.62 / 11.06 / 13.61 (10% at
# 24 slots, 9% lost at 8, and 15 MB more VMEM priced, which the largest
# offered chunk at 67 features does not have).
WAVE_SPLIT_STAGE_ROWS = 2048
# columns of the ordered tile one gather of the ordering fills (its
# [cols, STAGE_ROWS] one-hot is built a column block at a time)
WAVE_SPLIT_ORDER_COLS = 512
# rows of one block of the ordered tile: the contraction of one dot
# (ops/hist_wave.py COMPACT_TILE_UNIT, the unit its counts are in)
WAVE_SPLIT_BLOCK = 128
# blocks whose builds and first slots' dots stand in one straight line
# (ops/hist_wave.py _flush_by_slot): one [160, 128] x [128, 128] dot is
# one weight load, and a region of 17 of them between two scalar reads
# held 47% of the MXU's peak where 4 x 17 hold 73%. The same call, ns a
# dotted row at 1 / 24 live slots: 2 blocks 6.26 / 15.29, 4 blocks
# 6.09 / 14.95, 8 blocks 5.63 / 15.11 (twice the code for 0.5 ns at one
# slot and nothing at 24: PERF.md section 5, PR 35, call B). A tile of
# more groups unrolls fewer blocks (wave_split_unroll): what is bought
# is dots in a line, and 140 unrolled group bodies cost Mosaic and
# XLA:CPU minutes
WAVE_SPLIT_UNROLL = 4


def wave_split_unroll(groups: int) -> int:
    """Blocks of the ordered tile whose builds and first slots' dots
    stand in one straight line, for a tile of ``groups`` feature
    groups: WAVE_SPLIT_UNROLL up to 18 groups (the 67 features of the
    one-tile cell, the 16 groups of a 64-feature tile), the power of
    two that keeps the line at about as many dots beyond."""
    u = WAVE_SPLIT_UNROLL
    while u > 1 and u * groups > 18 * WAVE_SPLIT_UNROLL:
        u //= 2
    return u


def wave_split_applies(*, B: int, precision: str, compact_tile: int,
                       count_proxy: bool = False,
                       packed4: bool = False) -> bool:
    """Whether the fused kernel's flush dots its staged rows by the
    root's two-digit split, a slot at a time, in place of the one-hot
    dot against every slot's lanes: the root's rule
    (root_split_applies: the bf16 tiers, byte bins, 57 to 256 of them)
    where the kernel compacts (hist_compact_tile), since only compacted
    rows can be put in slot order. A trace-time choice from the shapes
    and the tier, no knob."""
    return compact_tile > 0 and root_split_applies(
        B=B, precision=precision, count_proxy=count_proxy, packed4=packed4)


def fused_hist_block_shapes(*, chunk: int, geom: Dict[str, int],
                            tbl_rows: int, compact_tile: int = 0,
                            tiled: bool = False,
                            split: Optional[Dict[str, int]] = None,
                            W: int = 0, stage_rows: int = 0
                            ) -> Dict[str, tuple]:
    """VMEM block shapes of fused_partition_histogram_pallas; with
    ``compact_tile`` also its compaction scratch, and ``tiled`` (``geom``
    is then one feature tile's) the block of the wave's split columns,
    which lie in other tiles. ``split`` (fused_wave_split, with the
    wave's ``W`` slots and the ``stage_rows`` staged at a time): the
    accumulators hold a slot's histograms by channel and digit, three
    planes of them leave the kernel, the staging buffer is
    ``stage_rows`` wide, and the flush keeps the ordered tile and a few
    blocks' keys and selected weight rows beside it."""
    s = {
        "tbl": (128, tbl_rows),                           # i32 const
        "bins": (geom["F_rows"], chunk),                  # grid-indexed
        "ghm": (4, chunk),                                # grid-indexed
        "leaf": (1, chunk),                               # grid-indexed
        "hist": (geom["groups"], geom["gb_pad"], 128),    # accumulator
        "leaf_out": (1, chunk),                           # grid-indexed
        "cnt": (geom["wp"], 128),                         # accumulator
    }
    if compact_tile:
        c = HIST_COMPACT_PAY_ROWS + _round_up(geom["F_rows"], 16)
        s.update({
            "x": (c, chunk),                 # bf16 payload + bin rows
            "sel": (1, chunk),               # f32 0/1 contributes
            "staged": (c, compact_tile),     # f32, across grid steps
        })
    if split:
        c = s["staged"][0]
        s.update({
            "hist": (W, split["groups"], 3 * split["L"], 128),
            "acc": (W, split["groups"], split["nl"], 128),   # f32
            "staged": (c, stage_rows),       # f32, across grid steps
            "ord": (c, stage_rows),          # f32, the tile by slot
            "key": (wave_split_unroll(split["groups"]),
                    c - HIST_COMPACT_PAY_ROWS,
                    WAVE_SPLIT_BLOCK),       # i32, lane digit by slot
            "p": (wave_split_unroll(split["groups"]), split["groups"],
                  split["R"], WAVE_SPLIT_BLOCK),
        })
    if tiled:
        s["cols"] = (_round_up(geom["wp"], HIST_TILE_ROW_ALIGN), chunk)
    return s


def fused_wave_split(*, geom: Dict[str, int], compact_tile: int,
                     int8: bool = False, count_proxy: bool = False,
                     variant: Optional[str] = None,
                     force: Optional[bool] = None
                     ) -> Optional[Dict[str, int]]:
    """Geometry of the digit split's dot in a fused kernel call whose
    flush takes it, else None: wave_split_applies asked of what the
    kernel's wrapper and the VMEM pricing both hold (4-bit packed bins
    show as more features than stored rows; of the bf16 tiers the
    exact one has a ``variant``). The root kernel's geometry over the
    tile's stored bin rows (root_hist_geometry: L, H, gf, R) with the
    fused kernel's channel multiplicands for ``nchan`` (ops/hist_wave.py
    _channel_rows, the count among them in every layout: "hilo4" rides
    the five rows of "hilo5", one slot's dot has lanes to spare), and
    ``nl`` = nchan x L, the rows of a (slot, group) accumulator once
    the products of two different features' digits are dropped.
    ``force`` = False keeps the one-hot dot, for tests and for the
    measurement beside WAVE_SPLIT_STAGE_ROWS; nothing forces the split
    on what it cannot serve."""
    if force is False or not wave_split_applies(
            B=geom["Bp"], precision="int8" if int8 else "highest",
            compact_tile=compact_tile, count_proxy=count_proxy,
            packed4=geom["F"] != geom["F_rows"]):
        return None
    g = root_hist_geometry(
        F=geom["F_rows"], B=geom["Bp"],
        nchan={"hilo5": 5, "hilo4": 5, "hilo3": 3, None: 4}[variant])
    g["nl"] = g["nchan"] * g["L"]
    return g


def hist_vmem_bytes(*, chunk: int, geom: Dict[str, int], W: int,
                    fused: bool, bins_bytes: int = 1, int8: bool = False,
                    count_proxy: bool = False,
                    tbl_rows: Optional[int] = None,
                    variant: Optional[str] = None,
                    tiled: bool = False, split: Optional[bool] = None,
                    stage_rows: Optional[int] = None) -> int:
    """Working-set bytes of one grid step of a wave-histogram kernel,
    priced from the SAME block shapes the BlockSpecs use: grid-indexed
    blocks double-buffered, plus the in-kernel temporaries (the
    transposed one-hot tile, the 128-row weight matrix, one matmul
    accumulator, and — fused — the [W, chunk] partition intermediates).
    ``variant="hilo4"`` adds the second histogram-shaped count
    accumulator (and its per-group matmul result) the exact-tier
    count dot writes. Where the fused kernel compacts
    (hist_compact_tile) the one-hot tile and weight rows are T wide,
    not chunk wide, and the compaction's scratch and temporaries (the
    payload rows, the two [T, T] one-hots of a turn that fills a tile
    with their i32 compares, the 128 x 128 rank triangle, the gathered
    [C, T] result) are added. ``tiled``: ``geom``
    is one feature tile's (hist_feature_tile), and the fused kernel
    reads the wave's split columns as one more double-buffered block.
    Where the fused kernel's flush dots by the digit split
    (fused_wave_split; ``split`` / ``stage_rows`` override the rule and
    WAVE_SPLIT_STAGE_ROWS as the kernel's wrapper lets a test) the
    one-hot tile, the weight matrix and the hilo4 count accumulator
    give way to the flush's own: the ordered tile and its digits, one
    column block of the ordering's one-hot with its compare, the
    membership rows, one block's selected weight rows and a group's
    operands and product.
    """
    oh_bytes = 1 if int8 else 2                  # int8 / bf16 one-hot
    acc_bytes = 4                                # i32 / f32 accumulator
    n_dot = chunk                                # rows of one dot
    if fused:
        if tbl_rows is None:
            # the kernel's split-table row count is the kernel's to
            # define (lazy: hist_wave imports this module at top level)
            from .hist_wave import TBL_ROWS
            tbl_rows = TBL_ROWS
        T = hist_compact_tile(geom=geom, chunk=chunk,
                              bins_bytes=bins_bytes, int8=int8)
        sg = fused_wave_split(geom=geom, compact_tile=T, int8=int8,
                              count_proxy=count_proxy, variant=variant,
                              force=split)
        T2 = stage_rows or WAVE_SPLIT_STAGE_ROWS
        s = fused_hist_block_shapes(chunk=chunk, geom=geom,
                                    tbl_rows=tbl_rows, compact_tile=T,
                                    tiled=tiled, split=sg, W=W,
                                    stage_rows=T2)
        b = (2 * _nelem(s["bins"]) * bins_bytes
             + (2 * _nelem(s["cols"]) * bins_bytes if tiled else 0)
             + 2 * _nelem(s["ghm"]) * 4
             + 2 * _nelem(s["leaf"]) * 4
             + 2 * _nelem(s["leaf_out"]) * 4
             + _nelem(s["tbl"]) * 4
             + _nelem(s["hist"]) * acc_bytes
             + (_nelem(s["cnt"]) * 4 if count_proxy else 0))
        # partition temporaries: cols / sentinel compares / moved, all
        # [W, chunk] i32-grade, ~4 live at once
        b += 4 * W * chunk * 4
        if T:
            n_dot = T
            b += (_nelem(s["x"]) * 2 + 8 * chunk * 4   # sel: 8 sublanes
                  + _nelem(s["staged"]) * 4
                  # payload rows (f32, then bf16), the bins' i32 + bf16
                  + HIST_COMPACT_PAY_ROWS * chunk * 6
                  + geom["F_rows"] * chunk * 6
                  + 128 * 128 * 6                      # rank triangle
                  + 2 * T * T * 6                      # [T, T] one-hots
                  + _nelem(s["staged"]) * 4)           # gathered result
        if sg:
            oc = min(WAVE_SPLIT_ORDER_COLS, T2)
            return b + (
                _nelem(s["ord"]) * 4 + 3 * _nelem(s["key"]) * 4
                + _nelem(s["acc"]) * 4
                + _nelem(s["staged"]) * 2    # the staged rows in bf16
                + oc * T2 * 6                # the ordering's one-hot
                + 4 * W * T2 * 4             # membership, ranks
                + _nelem(s["p"]) * 2
                + sg["R"] * WAVE_SPLIT_BLOCK * 6      # a group's rows
                + 2 * WAVE_SPLIT_BLOCK * WAVE_SPLIT_BLOCK * 6
                + 2 * sg["R"] * 128 * 4)     # its product, by digit
    else:
        s = wave_hist_block_shapes(chunk=chunk, geom=geom)
        b = (2 * _nelem(s["bins"]) * bins_bytes
             + 2 * _nelem(s["ghl"]) * 4
             + _nelem(s["wl"]) * 4
             + _nelem(s["hist"]) * acc_bytes
             + (_nelem(s["bins"]) * 4 if tiled else 0))  # i32 scratch
    b += (geom["gb"] * n_dot * oh_bytes          # one-hot tile
          + 128 * n_dot * 4                      # weight rows
          + geom["gb_pad"] * 128 * acc_bytes)    # per-group matmul acc
    if variant == "hilo4":
        # the count dot's accumulator ref + per-group result + the
        # f32 membership rows it contracts against
        b += (_nelem((geom["groups"], geom["gb_pad"], 128)) * 4
              + geom["gb_pad"] * 128 * 4
              + 128 * n_dot * 4)
    return b


def forest_block_shapes(*, F: int, Wtot: int, TC: int, Sp: int, Lp: int,
                        K: int, row_tile: int) -> Dict[str, tuple]:
    """VMEM block shapes of the fused forest prediction kernel
    (ops/stacked_predict.py forest_predict_pallas) — its BlockSpecs are
    built from THESE tuples, and _pallas_tc prices the same ones."""
    return {
        "codes": (F, row_tile),                  # i32, row-indexed
        "W": (1, Wtot, TC * Sp),                 # i8, step-indexed
        "P": (1, TC, Sp, Lp),                    # i8, step-indexed
        "tgt": (1, TC, Lp),                      # i32, step-indexed
        "leaf": (1, TC, Lp),                     # f32, step-indexed
        "cls": (1, TC, K),                       # f32, step-indexed
        "acc": (row_tile, K),                    # f32 accumulator
    }


def forest_vmem_bytes(*, F: int, Wtot: int, TC: int, Sp: int, Lp: int,
                      K: int, row_tile: int) -> int:
    """Working-set bytes of one fused-forest grid step: the
    double-buffered step-indexed blocks plus the in-kernel temporaries
    (one-hot tile [Wtot, nt] i8, C int32 + C8 int8 [nt, TC*Sp],
    per-tree E [nt, Lp] i32)."""
    s = forest_block_shapes(F=F, Wtot=Wtot, TC=TC, Sp=Sp, Lp=Lp, K=K,
                            row_tile=row_tile)
    return (2 * _nelem(s["W"])                   # int8, dbl-buffered
            + 2 * _nelem(s["P"])                 # int8, dbl-buffered
            + 2 * _nelem(s["tgt"]) * 4
            + 2 * _nelem(s["leaf"]) * 4
            + 2 * _nelem(s["cls"]) * 4
            + 2 * _nelem(s["codes"]) * 4
            + _nelem(s["acc"]) * 4
            + Wtot * row_tile                    # one-hot tile (i8)
            + row_tile * TC * Sp * 5             # C (i32) + C8 (i8)
            + row_tile * Lp * 4)                 # per-tree E (i32)


def fits_vmem(nbytes: int) -> bool:
    return nbytes <= PALLAS_VMEM_BUDGET_BYTES


def check_vmem_device() -> None:
    """Refuse to price kernels for a TPU whose VMEM nobody looked up:
    the limit/budget above are fractions of the capacities in
    TPU_VMEM_CAPACITY_BYTES. Off-TPU (interpret mode, AOT compiles for
    a described chip) there is nothing to check."""
    from ..utils.device import get_devices
    d = get_devices()[0]
    if d.platform != "tpu":
        return
    if d.device_kind not in TPU_VMEM_CAPACITY_BYTES:
        log.fatal(f"no VMEM capacity on record for TPU device_kind "
                  f"{d.device_kind!r} (known: "
                  f"{sorted(TPU_VMEM_CAPACITY_BYTES)}); add what the "
                  f"compiler reports for it to ops/autotune.py "
                  f"TPU_VMEM_CAPACITY_BYTES")


def tpu_compiler_params(*, vmem_limit_bytes: int = PALLAS_VMEM_LIMIT_BYTES):
    """Mosaic CompilerParams every TPU hot-path kernel passes (the one
    place the scoped-VMEM limit is applied — and checked against the
    chip it is applied to)."""
    from jax.experimental.pallas import tpu as pltpu
    check_vmem_device()
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)


# the capability ladder of the histogram hot loop, best-first; the
# chosen rung rides WaveGrowerConfig.route into the step-cache geometry
# key (different backends = different compiled programs)
HIST_ROUTES = ("pallas-tpu", "fused-xla", "two-pass")


def tune_hist_route(*, use_pallas: Optional[bool] = None,
                    fused_eligible: bool = True) -> str:
    """The histogram hot-loop route, by capability: the Mosaic kernels
    ("pallas-tpu") on a TPU, else the fused single-pass XLA kernel,
    else the legacy two-pass partition+histogram. ``use_pallas`` is
    the config override (None = auto: the device decides);
    ``fused_eligible`` is the caller's structural gate (default kernel
    seams, no EFB bundles, no sparse tier — ops/wave_grower.py owns
    it)."""
    if use_pallas is None:
        from ..utils.device import on_tpu
        use_pallas = on_tpu()
    if use_pallas:
        return "pallas-tpu"
    return "fused-xla" if fused_eligible else "two-pass"


# ---------------------------------------------------------------------------
# Tuning cache (versioned JSON on disk)
# ---------------------------------------------------------------------------

# 3: the fused kernel compacts rows ahead of its dot, and the tuner times
# it over 2^20 rows at a stated share of contributing rows (2 was that
# kernel timed over 65,536 rows: files of it exist on machines PR 27
# measured on, and hold the wrong exact-tier layout).
# 4: the compaction's scan costs about half (a [T, T] gather a turn, a
# block-wise rank): timings of the fused kernel cached before are stale
TUNING_CACHE_VERSION = 4


def default_cache_dir() -> str:
    """The ONE on-disk cache directory of a checkout: the persistent
    XLA compile cache (ensure_compile_cache) and, inside it, the kernel
    tuning cache. Fixed beside the package (``<checkout>/
    .lgbm_tpu_cache``, git-ignored) — never a temp name, pid or time:
    the path is part of the compile cache's key, so a directory that
    moves never hits."""
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".lgbm_tpu_cache")
    os.makedirs(d, exist_ok=True)
    return d


def default_tuning_cache_path() -> str:
    """The tuning JSON sits inside the compile-cache directory in use —
    the operator's (``JAX_COMPILATION_CACHE_DIR``) when one is placed,
    else ``default_cache_dir()`` — so whatever carries one cache from
    run to run carries the other."""
    import jax
    return os.path.join(
        jax.config.jax_compilation_cache_dir or default_cache_dir(),
        f"lgbm_tpu_tuning_v{TUNING_CACHE_VERSION}.json")


class TuningCache:
    """{key -> {choice, timings_ms}} persisted as versioned JSON.

    Likes the dataset binary cache's versioned token (io/dataset.py):
    a file whose ``version`` field doesn't match this reader is ignored
    wholesale (re-tune), never partially trusted. Writes are atomic
    (tmp + rename) so concurrent trainers at worst re-tune."""

    def __init__(self, path: str):
        self.path = path
        self._entries: Optional[Dict[str, dict]] = None

    @staticmethod
    def key_string(kernel: str, key: Dict) -> str:
        return json.dumps({"kernel": kernel, **key}, sort_keys=True)

    def _load(self) -> Dict[str, dict]:
        if self._entries is None:
            self._entries = {}
            try:
                with open(self.path) as fh:
                    d = json.load(fh)
                if (isinstance(d, dict)
                        and d.get("version") == TUNING_CACHE_VERSION
                        and isinstance(d.get("entries"), dict)):
                    self._entries = d["entries"]
                else:
                    log.debug("tuning cache %s has version %r (want %d); "
                              "ignoring it", self.path,
                              d.get("version") if isinstance(d, dict)
                              else None, TUNING_CACHE_VERSION)
            except (OSError, ValueError):
                pass
        return self._entries

    def get(self, key: str) -> Optional[dict]:
        return self._load().get(key)

    def put(self, key: str, record: dict) -> None:
        entries = self._load()
        entries[key] = record
        try:
            from ..utils.fileio import atomic_write
            with atomic_write(self.path) as fh:
                json.dump({"version": TUNING_CACHE_VERSION,
                           "entries": entries}, fh, indent=1)
        except OSError as e:
            log.warning("could not persist tuning cache %s: %s",
                        self.path, e)


# ---------------------------------------------------------------------------
# The autotuner
# ---------------------------------------------------------------------------

class Autotuner:
    """Times candidate tile configurations once per key, then serves the
    winner from the on-disk cache forever."""

    def __init__(self, mode: str = "on",
                 cache_path: Optional[str] = None):
        if mode not in ("on", "off", "exhaustive"):
            log.warning("tpu_autotune=%r is not one of on/off/exhaustive;"
                        " using 'on'", mode)
            mode = "on"
        self.mode = mode
        self.cache = TuningCache(cache_path or default_tuning_cache_path())

    def best(self, kernel: str, key: Dict, candidates: List[dict],
             measure: Callable[[dict], float],
             default: Optional[dict] = None) -> dict:
        """The winning candidate for ``key``.

        ``candidates``: JSON-able config dicts (already VMEM-filtered).
        ``measure(candidate) -> seconds`` (the median-of-k repeat count
        lives in the caller's harness, timing.measure). A cached choice
        is only honored while it is still a member of the current
        candidate set — a changed candidate generation (new VMEM
        budget, new kernel rev bumping TUNING_CACHE_VERSION) re-tunes.
        Callers whose candidate sets vary with non-key inputs must fold
        a candidate fingerprint into ``key``, or differently-shaped
        runs would perpetually overwrite each other's entries.
        A candidate that fails to compile or run is never silent: it
        is counted (``autotune/candidates_failed``) and, on a TPU
        backend, warned about with the compiler's text — there the
        candidate set was priced against this chip's VMEM, so a Mosaic
        rejection is a defect to report, and a key whose EVERY
        candidate failed is fatal (training on a default nobody could
        compile would only fail later, or worse, run as something
        else). Off-TPU (injected timers) the default is still
        served."""
        if not candidates:
            return default
        if self.mode == "off":
            return default if default is not None else candidates[0]
        ck = self.cache.key_string(kernel, key)
        hit = self.cache.get(ck)
        if hit is not None and hit.get("choice") in candidates:
            obs.counter("autotune/cache_hits").add(1)
            return hit["choice"]
        from ..utils.device import on_tpu
        tpu = on_tpu()
        timings_ms: Dict[str, float] = {}
        best_c, best_t = None, float("inf")
        with timing.phase(f"autotune/{kernel}"):
            for cand in candidates:
                try:
                    t = measure(cand)
                except Exception as e:        # noqa: BLE001 — reported,
                    # counted and (every candidate, on TPU) fatal below
                    obs.counter("autotune/candidates_failed").add(1)
                    (log.warning if tpu else log.info)(
                        "autotune[%s]: candidate %s failed: %s: %s",
                        kernel, cand, type(e).__name__, e)
                    continue
                timings_ms[json.dumps(cand, sort_keys=True)] = round(
                    t * 1e3, 4)
                if t < best_t:
                    best_c, best_t = cand, t
        if best_c is None:
            if tpu:
                log.fatal(f"autotune[{kernel}]: every candidate "
                          f"{candidates} failed on the TPU backend for "
                          f"key {key} (see the warnings above for the "
                          f"compiler's text)")
            log.warning("autotune[%s]: every candidate failed; using the"
                        " default %s", kernel, default)
            return default if default is not None else candidates[0]
        self.cache.put(ck, {"choice": best_c, "timings_ms": timings_ms})
        obs.counter("autotune/tuned_keys").add(1)
        log.info("autotune[%s]: chose %s (%.3f ms; %d candidates timed)",
                 kernel, best_c, best_t * 1e3, len(timings_ms))
        return best_c


# module-level tuner, configured from Config (models/gbdt.py init);
# prediction (ops/stacked_predict.py) shares whatever was last configured
_mode = "on"
_cache_path: Optional[str] = None
_tuner: Optional[Autotuner] = None


def configure(mode: str = "on", cache_path: Optional[str] = None) -> None:
    """Install the process-wide tuning mode + cache path
    (config.tpu_autotune / config.tpu_tuning_cache)."""
    global _mode, _cache_path, _tuner
    if mode != _mode or (cache_path or None) != _cache_path:
        _mode, _cache_path = mode, (cache_path or None)
        _tuner = None


def tuner() -> Autotuner:
    global _tuner
    if _tuner is None:
        _tuner = Autotuner(_mode, _cache_path)
    return _tuner


def device_kind() -> str:
    """Cache-key device identity: the string the device itself reports
    (a v5e says 'TPU v5 lite'; the CPU backend 'cpu')."""
    from ..utils.device import get_devices
    d = get_devices()[0]
    return str(getattr(d, "device_kind", None) or d.platform)


# ---------------------------------------------------------------------------
# Persistent XLA compile cache
# ---------------------------------------------------------------------------

_compile_cache_done = False


def ensure_compile_cache(mode: int = -1) -> None:
    """Wire jax's persistent compilation cache so the grower/predict
    kernels compile once per machine, not once per process (~tens of
    seconds per distinct shape on TPU). Idempotent, and the ONLY place
    this repo sets ``jax_compilation_cache_dir``: when the operator
    placed the cache (``JAX_COMPILATION_CACHE_DIR`` — jax reads it into
    its own config — or an explicit config update) it is used as is and
    nothing else is set; otherwise the cache goes to the one fixed
    ``default_cache_dir()`` (the path is part of the cache key, so it
    must never move between runs).

    ``mode`` is config.tpu_compile_cache's tri-state. The policy
    matrix:

    ========  ==========  =======  ========
    backend   -1 (auto)   0 (off)  1 (on)
    ========  ==========  =======  ========
    tpu       on          off      on
    other     off         off      on
    ========  ==========  =======  ========

    The TPU auto-enables: that is where the expensive Mosaic compiles
    live. Every other backend stays opt-in — the CPU test suite
    compiles hundreds of small programs whose cache writes cost more
    than they save."""
    global _compile_cache_done
    if _compile_cache_done:
        return
    import jax
    if jax.config.jax_compilation_cache_dir:
        _compile_cache_done = True       # operator already placed it
        return
    from ..utils.device import on_tpu
    if mode == 0 or (not on_tpu() and mode != 1):
        # NOT a terminal decision: a later booster may opt in
        # (tpu_compile_cache=1), so leave the flag unset
        return
    _compile_cache_done = True
    jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


# ---------------------------------------------------------------------------
# Histogram-kernel chunk tuning (training hot path)
# ---------------------------------------------------------------------------

def hist_chunk_candidates(*, F: int, B: int, W: int, fused: bool,
                          bins_bytes: int = 1, int8: bool = False,
                          count_proxy: bool = False, packed4: bool = False,
                          n_rows: int = 0, exhaustive: bool = False,
                          variant: Optional[str] = None) -> List[dict]:
    """VMEM-feasible row-chunk candidates for the wave/fused histogram
    kernels, largest-first. Chunks beyond the dataset's rows are
    pointless (the kernel would pad the whole matrix up); the int8 tier
    additionally keeps the padded row count under the int32 histogram
    overflow guard. A chunk at which the kernel walks feature tiles
    (hist_feature_tile) carries the tile's stored bin rows beside it:
    the pair is what is timed, and the kernel derives the same tile
    from the same shapes at trace time."""
    F_rows = (F + 1) // 2 if packed4 else F
    base = ((1024, 2048, 4096, 8192, 16384, 32768, MAX_HIST_CHUNK)
            if exhaustive else (4096, 8192, 16384, 32768))
    out = []
    for c in base:
        if n_rows and c > max(n_rows, base[0]):
            continue
        if int8 and n_rows and 127 * (n_rows + (-n_rows) % c) >= 2 ** 31:
            continue
        tile = hist_feature_tile(
            F=F, B=B, W=W, chunk=c, fused=fused, F_rows=F_rows,
            bins_bytes=bins_bytes, int8=int8, count_proxy=count_proxy,
            variant=variant)
        if tile >= F_rows:
            out.append({"chunk": c})
        elif tile:
            out.append({"chunk": c, "tile": tile})
    return out[::-1]


def tune_hist_chunk(*, fused: bool, F: int, B: int, W: int,
                    precision: str = "highest", count_proxy: bool = False,
                    packed4: bool = False, any_cat: bool = False,
                    bins_bytes: int = 1, n_rows: int = 0,
                    variant: Optional[str] = None, _measure=None) -> int:
    """The row chunk the histogram hot path should run with — tuned on
    first encounter of this (kernel, F, B, tier, device) key, cached
    thereafter. Off the TPU (and with tpu_autotune=off) this returns
    the measured per-tier default untouched; ``_measure`` injects a
    fake timer so the decision logic unit-tests without a chip."""
    int8 = precision == "int8"
    default = DEFAULT_HIST_CHUNK_INT8 if int8 else DEFAULT_HIST_CHUNK
    t = tuner()
    from ..utils.device import on_tpu
    if t.mode == "off" or (not on_tpu() and _measure is None):
        return default
    variant = variant if precision == "highest" else None
    cands = hist_chunk_candidates(
        F=F, B=B, W=W, fused=fused, bins_bytes=bins_bytes, int8=int8,
        count_proxy=count_proxy, packed4=packed4, n_rows=n_rows,
        exhaustive=t.mode == "exhaustive", variant=variant)
    if not cands:
        # every chunk was priced and none fits, not even walking the
        # narrowest feature tile: nothing to hand Mosaic
        raise ValueError(
            f"no row chunk of the {'fused' if fused else 'wave'} "
            f"histogram kernel fits the VMEM budget at {F} features x "
            f"{B} bins, wave {W} ({precision})")
    if len(cands) == 1:
        return int(cands[0]["chunk"])
    tier = precision + ("+proxy" if count_proxy else "") \
        + ("+packed4" if packed4 else "") \
        + (f"+{variant}" if variant not in (None, "hilo5") else "")
    key = {"F": F, "B": B, "W": W, "tier": tier, "fused": fused,
           "cat": bool(any_cat), "bins_bytes": bins_bytes,
           "device": device_kind(),
           # the candidate set varies with n_rows (dataset-size cap +
           # int8 overflow guard): folding it into the key keeps
           # different-sized datasets from overwriting each other's
           # entries on every alternation
           "chunks": [c["chunk"] for c in cands]}
    measure = _measure or _hist_measure_fn(
        fused=fused, F=F, B=B, W=W, precision=precision,
        count_proxy=count_proxy, packed4=packed4, any_cat=any_cat,
        bins_bytes=bins_bytes,
        n_meas=_hist_measure_rows(cands, F, bins_bytes),
        variant=variant or "hilo5")
    choice = t.best("fused_hist" if fused else "wave_hist", key, cands,
                    measure, default={"chunk": default})
    return int(choice["chunk"])


# ---------------------------------------------------------------------------
# Exact-tier (precision="highest") channel-layout selection
# ---------------------------------------------------------------------------

# wave-width cap each exact-tier layout buys (128 MXU lanes / channel
# count, floor'd to a multiple of 8 for sublane alignment — see
# ops/hist_wave.py _wave_hist_kernel): the cap is what a variant is FOR
# (fewer full-data passes per tree), so it doubles as the off-TPU
# analytic preference order
EXACT_TIER_CAPS = {"hilo5": 24, "hilo4": 32, "hilo3": 40}


def exact_tier_candidates(*, constant_hessian: bool,
                          by_slot: bool = False) -> List[dict]:
    """Feasible exact-tier layouts, widest wave first. ``hilo3`` (the
    fused hess/count plane) is only sound when the hessian plane is
    identically the sample mask — constant-unit-hessian objectives
    without row weights (models/gbdt.py gates this). ``by_slot``: the
    fused kernel's flush dots a slot at a time (wave_split_applies);
    "hilo4" then rides hilo5's five rows through the same dot (its
    second, count dot is gone) and differs from it by its wave cap
    alone, and how many leaves a wave splits is the grower's (passes a
    tree, the order the leaves are split in), not a layout's to move:
    it is not timed against hilo5 there (tpu_exact_tier=hilo4 still
    asks for it; ROADMAP S1(f): the wave's width under the split)."""
    out = [{"variant": "hilo5"}] if by_slot \
        else [{"variant": "hilo4"}, {"variant": "hilo5"}]
    if constant_hessian:
        out.insert(0, {"variant": "hilo3"})
    return out


def tune_exact_tier(*, F: int, B: int, n_rows: int = 0,
                    constant_hessian: bool = False,
                    any_cat: bool = False, bins_bytes: int = 1,
                    requested: str = "", _measure=None) -> str:
    """The exact-semantics (hi/lo) histogram layout this geometry
    should run — "hilo5" / "hilo4" / "hilo3" (ops/hist_wave.py).

    ``requested`` is config.tpu_exact_tier ("" = auto). The choice is
    per (F, B, device) like tune_hist_chunk: on a real TPU the
    feasible layouts are timed once (fused kernel at each layout's own
    wave cap, wall NORMALIZED PER SPLIT — t/W — because the layouts
    trade MXU dots per pass against passes per tree) and the winner is
    cached; off-TPU the choice is ANALYTIC — the XLA oracle is
    layout-free, so the variant only sets the wave-width cap and the
    widest feasible wave wins (fewer full-data scatter passes per tree
    — the measured off-TPU win). tpu_autotune=off pins the pre-variant
    "hilo5".
    ``_measure`` injects a fake timer (unit tests; it forces the timed
    arm on any backend — the key's device field keeps entries
    apart)."""
    if requested:
        if requested == "hilo3" and not constant_hessian:
            log.warning(
                "tpu_exact_tier=hilo3 needs a constant-unit-hessian "
                "objective without row weights (the fused hess/count "
                "plane would misread varying hessians); using hilo4")
            return "hilo4"
        return requested
    cands = exact_tier_candidates(constant_hessian=constant_hessian)
    t = tuner()
    if t.mode == "off":
        return "hilo5"
    from ..utils.device import on_tpu
    if not on_tpu() and _measure is None:
        # the analytic arm (see docstring)
        return cands[0]["variant"]
    # the timed arm knows the kernel it times: where its flush dots by
    # slot, hilo4 is no layout of its own
    geom = hist_geometry(F=F, B=B, W=EXACT_TIER_CAPS["hilo5"])
    cands = exact_tier_candidates(
        constant_hessian=constant_hessian,
        by_slot=wave_split_applies(
            B=B, precision="highest", compact_tile=hist_compact_tile(
                geom=geom, chunk=DEFAULT_HIST_CHUNK,
                bins_bytes=bins_bytes)))
    if len(cands) == 1:
        return str(cands[0]["variant"])
    key = {"F": F, "B": B, "cat": bool(any_cat),
           "bins_bytes": bins_bytes, "device": device_kind(),
           "variants": [c["variant"] for c in cands]}
    measure = _measure or _exact_tier_measure_fn(
        F=F, B=B, any_cat=any_cat, bins_bytes=bins_bytes,
        n_rows=n_rows)
    choice = t.best("exact_tier", key, cands, measure,
                    default={"variant": "hilo5"})
    return str(choice["variant"])


def _exact_tier_measure_fn(*, F, B, any_cat, bins_bytes, n_rows):
    """measure(candidate) for the exact-tier layouts: the fused kernel
    at the candidate's own wave cap, per-split-normalized (wall / W) —
    a layout that spends 1.5x the MXU per pass but buys 1.33x the wave
    width must win or lose on the quotient, not the raw wall. The rows
    that contribute are W / 96 of all (a quarter at hilo5's 24): a
    tree's smaller children hold the same rows whatever the wave
    width, so a wider wave's pass holds proportionally more of them,
    and wall / W then reads scan / W + dot x rows / 96."""
    def measure(cand):
        v = cand["variant"]
        W = EXACT_TIER_CAPS[v]
        chunk_c = [{"chunk": DEFAULT_HIST_CHUNK}]
        fn = _hist_measure_fn(
            fused=True, F=F, B=B, W=W, precision="highest",
            count_proxy=False, packed4=False, any_cat=any_cat,
            bins_bytes=bins_bytes,
            n_meas=_hist_measure_rows(chunk_c, F, bins_bytes),
            variant=v, share=W / 96.0)
        return fn(chunk_c[0]) / W
    return measure


# ---------------------------------------------------------------------------
# Histogram-tier selection (dense one-hot pass vs sparse scatter)
# ---------------------------------------------------------------------------

# auto-tier density ceiling: the sparse scatter touches ~nnz * W slot
# compares + 3 scatters per channel where the dense pass touches N * F
# one-hot work regardless of density — below ~1/8 density the sparse
# side wins with margin on every backend measured; the cost model is a
# rule (not a timed sweep) because the tier also changes EXACTNESS
# (see tune_hist_tier), so auto only engages where it is bit-equal
SPARSE_TIER_MAX_DENSITY = 0.125


def tune_hist_tier(*, requested: int, density: float, nnz: int,
                   F: int, B: int, W: int, quant: bool) -> bool:
    """True = the sparse histogram tier (ops/hist_wave.py
    wave_histogram_sparse, scatter over nnz) serves this booster;
    False = the dense one-hot tier. Selected per (density, geometry)
    like the other kernel tiers — the caller (models/gbdt.py) has
    already checked the structural gates (serial learner, no EFB
    bundles, coordinates present).

    ``requested`` is config.tpu_sparse (-1 auto / 0 off / 1 force).
    The auto rule is exactness-first: integer (quantized) accumulation
    is order-free, so the sparse completion subtraction is BIT-equal
    to the dense tier — auto therefore requires ``quant`` AND density
    under SPARSE_TIER_MAX_DENSITY.
    tpu_sparse=1 forces the tier for f32 histograms too (final-ulp
    reassociation drift vs the dense tier is possible; logged)."""
    if requested == 0:
        return False
    if requested == 1:
        if not quant:
            log.info("tpu_sparse=1 with f32 histograms: the sparse "
                     "tier's default-bin completion reassociates "
                     "sums — final-ulp drift vs the dense tier is "
                     "possible (tpu_quantized_hist makes it bit-exact)")
        return True
    if not quant:
        return False
    return float(density) <= SPARSE_TIER_MAX_DENSITY


# ---------------------------------------------------------------------------
# Histogram-psum wire-format tuning (data-parallel reduction)
# ---------------------------------------------------------------------------

def tune_hist_psum(*, mesh, W: int, F: int, B: int, channels: int,
                   n_rows_global: int, requested: int = -1) -> bool:
    """Wire format of the data-parallel wave-histogram reduction:
    True = psum the RAW int32 quantized histogram and dequantize after
    the collective (exact integer addition across shards, and — with
    the count-proxy tier — a 2-channel payload instead of 3);
    False = psum dequantized f32 sums (the pre-quantized-psum wire).

    ``requested`` is config.tpu_quantized_psum (-1 auto / 0 off /
    1 force). The int32 wire is only sound while the GLOBAL padded row
    count keeps 127 * n under int32 wrap — beyond that the f32 wire is
    used regardless (f32 rounds but never wraps). Inside the bound the
    auto choice is timed once per (mesh size, payload shape, device)
    key on real TPU meshes and cached; off-TPU (and with
    tpu_autotune=off) the analytic default — int32 — is used."""
    if requested == 0:
        return False
    from ..utils.device import on_tpu
    tpu = on_tpu()
    # off-TPU the "quantized wire" is the XLA oracle's integer-VALUED
    # f32 sums (hist_wave.wave_histogram), which stay exact only below
    # 2^24 — the int32 Pallas wire holds to 2^31. Past the applicable
    # bound the deferred-dequant reduction could round/wrap, so the
    # dequantize-first f32 wire (rounds, never wraps) is used instead.
    bound = 2 ** 31 if tpu else 2 ** 24
    safe = 127 * max(int(n_rows_global), 1) < bound
    if not safe:
        if requested == 1:
            log.warning("tpu_quantized_psum=1 requested but %d global "
                        "rows could overflow the quantized wire; using "
                        "the f32 reduction", n_rows_global)
        return False
    if requested == 1:
        return True
    t = tuner()
    if t.mode == "off" or not tpu:
        return True
    D = int(mesh.devices.size)
    key = {"D": D, "W": W, "F": F, "B": B, "C": channels,
           "device": device_kind()}
    cands = [{"wire": "int32"}, {"wire": "f32"}]
    choice = t.best("hist_psum", key, cands,
                    _psum_measure_fn(mesh, (W, F, B, channels)),
                    default={"wire": "int32"})
    return choice["wire"] == "int32"


def _psum_measure_fn(mesh, shape):
    """measure(candidate) for the histogram-reduction wire formats: a
    jitted shard_map psumming a dummy payload of the real [W, F, B, C]
    block in the candidate's dtype."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    # lazy: parallel.learners imports ops.wave_grower which imports
    # this module at top level
    from ..parallel.learners import AXIS

    def build(dtype):
        def body(x):
            return jax.lax.psum(x, AXIS)
        # jit-capture: ok(*) — throwaway psum microbenchmark body,
        # closes over nothing but the mesh axis; never cached
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                  out_specs=P(), check_vma=False))
        x = jnp.ones(shape, dtype)
        return functools.partial(f, x)

    fns = {"int32": build(jnp.int32), "f32": build(jnp.float32)}
    return lambda cand: timing.measure(fns[cand["wire"]])


# packed-wire wrap bounds: the quantized per-shard histogram entry is a
# sum of int8 values in [-127, 127], so |entry| <= 127 * n_rows_global
# and the GLOBAL psum result obeys the same bound — when it fits the
# narrow signed range, the narrowing cast, the integer psum and the
# widening cast are all exact (BIT-identical to the int32 wire). The
# int32 bound itself is tune_hist_psum's concern (it gates quant_psum).
PSUM_WIRE_BOUNDS = (("int8", 2 ** 7), ("int16", 2 ** 15))


def tune_psum_wire(*, n_rows_global: int, requested: int = -1) -> str:
    """Wire dtype of the quantized histogram collective
    (config.tpu_psum_wire): "int8"/"int16" when the 127*N wrap bound
    proves the narrow sum cannot overflow, else "int32" (the legacy
    wire). ``requested``: 0 = legacy int32; 1 = force-narrow (warns
    and falls back to int32 where the bound refuses); -1 = auto
    (narrowest provably-safe width — a pure bound check, no timing:
    narrower is never slower and always bit-identical)."""
    if requested == 0:
        return "int32"
    n = max(int(n_rows_global), 1)
    for wire, bound in PSUM_WIRE_BOUNDS:
        if 127 * n < bound:
            return wire
    if requested == 1:
        log.warning("tpu_psum_wire=1 requested but %d global rows "
                    "exceed every narrow wrap bound (127*N < 2^15 "
                    "needed for int16); using the int32 wire", n)
    return "int32"


def tune_hist_psum_async(*, mesh, W: int, F: int, B: int,
                         channels: int, wire: str = "f32",
                         requested: int = -1) -> int:
    """Slot count of the wave-histogram collective
    (config.tpu_async_psum): 1 = one monolithic psum (sync);
    2 = double-buffered slot collectives split along the feature axis
    (parallel/learners.py make_hist_reduce), which XLA can overlap
    with local compute. The split is BIT-identical for every wire
    (psum is elementwise across shards), so the choice is purely a
    scheduling/perf arm: -1 = auto (slots on multi-device meshes; the
    async-vs-sync arm is timed once per (mesh, payload, device) key on
    real TPUs, analytic default — async — elsewhere); 0 = sync;
    1 = force async."""
    if requested == 0:
        return 1
    if F < 2:
        # nothing to split; the monolithic psum IS the slot psum
        if requested == 1:
            log.info("tpu_async_psum=1 with a single feature column: "
                     "the collective has one slot either way")
        return 1
    if requested == 1:
        return 2
    if int(mesh.devices.size) < 2:
        return 1
    from ..utils.device import on_tpu
    t = tuner()
    if t.mode == "off" or not on_tpu():
        return 2
    key = {"D": int(mesh.devices.size), "W": W, "F": F, "B": B,
           "C": channels, "wire": wire, "device": device_kind()}
    cands = [{"slots": 1}, {"slots": 2}]
    choice = t.best("hist_psum_async", key, cands,
                    _psum_slots_measure_fn(mesh, (W, F, B, channels),
                                           wire),
                    default={"slots": 2})
    return int(choice["slots"])


def _psum_slots_measure_fn(mesh, shape, wire: str):
    """measure(candidate) for the async-vs-sync arm: the real slot
    split (parallel/learners.py) over a dummy payload, per slot
    count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.learners import _slot_psum

    dtype = {"int8": jnp.int8, "int16": jnp.int16,
             "int32": jnp.int32}.get(wire, jnp.float32)

    def build(slots):
        def body(x):
            return _slot_psum(x, slots)
        # jit-capture: ok(*) — throwaway psum microbenchmark body,
        # closes over nothing but the mesh axis; never cached
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                  out_specs=P(), check_vma=False))
        x = jnp.ones(shape, dtype)
        return functools.partial(f, x)

    fns = {1: build(1), 2: build(2)}
    return lambda cand: timing.measure(fns[cand["slots"]])


def measure_psum_s(mesh, shape, dtype) -> float:
    """Measured seconds per histogram-collective pass on THIS mesh for
    the given payload — the stall-time estimate behind the
    ``comm/psum_stall_s`` accounting (models/gbdt.py): per-pass
    collective wall x pass count. A real measurement of the real
    collective (not a bandwidth model), but taken outside the training
    step — in-step timing would require host callbacks on the
    compiled path."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.learners import AXIS

    def body(x):
        return jax.lax.psum(x, AXIS)
    # jit-capture: ok(*) — throwaway psum microbenchmark body, closes
    # over nothing but the mesh axis; never cached
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))
    x = jnp.ones(shape, dtype)
    return float(timing.measure(functools.partial(f, x)))


def _hist_measure_rows(cands: List[dict], F: int, bins_bytes: int) -> int:
    """Measurement row count: a multiple of every candidate chunk,
    capped so the synthetic bin matrix stays small — and 2^20 rows
    where that allows, so that what a pass costs a row outweighs what
    it costs once (the accumulators' write-back and re-layout): at
    65,536 rows the exact-tier layouts timed within noise of each
    other and a cold tune took the one that runs an iteration 1.74x
    slower (PERF.md section 6, PR 27, call C)."""
    top = max(c["chunk"] for c in cands)
    n = max(top, 1 << 20)
    while n > top and F * n * bins_bytes > (512 << 20):
        n //= 2
    return n


def _hist_measure_fn(*, fused: bool, F: int, B: int, W: int,
                     precision: str, count_proxy: bool, packed4: bool,
                     any_cat: bool, bins_bytes: int, n_meas: int,
                     variant: str = "hilo5", share: float = 0.25):
    """Build measure(candidate) for the histogram kernels: synthetic
    data of the real (F, B, tier) shape, one warm-up call per candidate
    (compiles; the persistent compile cache makes reruns cheap), then
    median-of-k wall time with a device-sync readback. ``share`` of
    the rows contribute to the fused pass's histograms."""
    import numpy as np

    import jax.numpy as jnp

    from .hist_wave import (fused_partition_histogram_pallas,
                            wave_histogram_pallas)

    rng = np.random.default_rng(0)
    int8 = precision == "int8"
    F_rows = (F + 1) // 2 if packed4 else F
    bdt = np.uint8 if bins_bytes == 1 else np.int32
    bmax = 255 if packed4 else max(B - 1, 1)
    bins = jnp.asarray(rng.integers(0, bmax + 1, (F_rows, n_meas),
                                    dtype=np.int64).astype(bdt))
    if int8:
        g = jnp.asarray(rng.integers(-127, 128, n_meas).astype(np.float32))
        h = jnp.asarray(rng.integers(0, 128, n_meas).astype(np.float32))
        gh_scale = (1.0, 1.0)
    else:
        g = jnp.asarray(rng.normal(size=n_meas).astype(np.float32))
        h = jnp.asarray(np.abs(rng.normal(size=n_meas)).astype(np.float32))
        gh_scale = None
    leaf_ids = jnp.zeros(n_meas, jnp.int32)
    if fused:
        mask = jnp.ones(n_meas, jnp.float32)
        # one active slot splitting leaf 0 ``share`` of the way up the
        # (uniform) bins; the left child keeps the parent's id and is
        # the smaller one, so about that share of the rows contribute:
        # where the kernel compacts, its time hangs on it (a 255-leaf
        # tree's wave passes average 0.19 at 24 slots, its first 0.5)
        col = np.full(W, -1, np.int32)
        tbl = np.zeros((18, W), np.int32)
        tbl[0] = col                     # TBL_PARENT
        tbl[1] = col                     # TBL_NEW
        tbl[0, 0], tbl[1, 0] = 0, 1
        tbl[3, 0] = max(int(B * share) - 1, 0)   # TBL_BIN
        tbl[7] = B                       # TBL_NUMBIN
        tbl[8] = col                     # TBL_SMALL
        tbl[8, 0] = 0
        tbl_d = jnp.asarray(tbl)

        def run(chunk):
            return fused_partition_histogram_pallas(
                bins, g, h, mask, leaf_ids, tbl_d, num_bins=B,
                chunk=chunk, precision=precision, gh_scale=gh_scale,
                any_cat=any_cat, count_proxy=count_proxy,
                packed4=packed4, num_features=F if packed4 else None,
                variant=variant)
    else:
        wl = jnp.asarray(np.concatenate(
            [np.zeros(1, np.int32), np.full(W - 1, -1, np.int32)])
            if W > 1 else np.zeros(1, np.int32))

        def run(chunk):
            return wave_histogram_pallas(
                bins, g, h, leaf_ids, wl, num_bins=B, chunk=chunk,
                precision=precision, gh_scale=gh_scale,
                count_proxy=count_proxy, packed4=packed4,
                num_features=F if packed4 else None, variant=variant)

    return lambda cand: timing.measure(
        functools.partial(run, int(cand["chunk"])))
