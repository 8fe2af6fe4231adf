"""Wave-batched on-device leaf-wise tree grower.

TPU-native counterpart of SerialTreeLearner::Train (reference:
src/treelearner/serial_tree_learner.cpp:157-221), round-2 redesign.

Round 1 compiled the whole leaf-wise loop as ``num_leaves - 1``
shape-static steps, each paying one full-data histogram pass for ONE
leaf — O(N * L) row-histogram work per tree. The reference avoids that
with smaller-child construction + subtraction, but its per-split
histogram still touches the split leaf's rows via gather — a
random-access pattern TPUs do poorly.

The round-2 answer is the **wave**: one ``lax.while_loop`` step splits
the top-``W`` leaves by gain simultaneously, and ONE full-data Pallas
pass (ops/hist_wave.py) produces all ``W`` smaller-child histograms at
the cost of one pass — the idle MXU output lanes of a single-leaf pass
carry the other leaves' channels. Sibling histograms come from
parent - smaller subtraction (feature_histogram.hpp:68) out of a
preallocated HBM pool. Row-histogram work per tree drops to
O(N * L / W), a ~W x win, with no gathers anywhere.

``wave_size=1`` reproduces the reference's exact leaf-wise semantics
(split strictly one best leaf at a time). For larger W the tree can
differ from strict leaf-wise only when the leaf budget runs out
mid-wave; quality is leaf-wise-grade because waves split in gain order.

Leaf numbering matches Tree::Split: each split's left child keeps the
parent's leaf index, the right child takes the next free index; within
a wave, new indices are assigned in gain-rank order.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import registry as obs
from .autotune import DEFAULT_HIST_CHUNK
from .grower import TreeRecord
from .hist_wave import (fused_partition_histogram_pallas,
                        root_histogram_pallas, root_nchan, take_rows,
                        wave_histogram, wave_histogram_pallas)
from .partition import member_column, row_goes_right
from .split import (FeatureMeta, SplitParams, SplitResult, KMIN_SCORE,
                    calculate_leaf_output, find_best_split)


class WaveGrowerConfig(NamedTuple):
    """Static compile-time configuration of one wave grower."""
    num_leaves: int
    num_bins: int          # padded global B
    wave_size: int = 16
    max_depth: int = -1
    chunk: int = 0         # rows per kernel step (0 = impl default)
    hp: SplitParams = SplitParams()
    use_pallas: bool | None = None   # None = auto by backend
    # histogram accumulation: "highest" = bf16 hi/lo exact-product
    # decomposition (f32-grade sums, W <= 25), "default" = single bf16
    # (W <= 42/32). Plumbed from config.tpu_use_dp.
    precision: str = "highest"
    # exact-tier channel layout (precision="highest" only; autotuned
    # per geometry, ops/autotune.py tune_exact_tier): "hilo5" = the
    # original 5-channel hi/lo rows (W <= 24); "hilo4" = 4 channels +
    # a second count dot (W <= 32); "hilo3" = the fused hess/count
    # plane for constant-unit-hessian objectives (W <= 40). All three
    # reconstruct identical f32-grade sums (ops/hist_wave.py); the
    # wave-width cap — passes per tree — is what they trade.
    exact_variant: str = "hilo5"
    # fused partition+histogram kernel (ONE data pass per wave instead
    # of W partition passes + a histogram pass). None = auto: on
    # whenever the Pallas path is on and W fits; interpret mode is used
    # off-TPU so tests exercise the same code path.
    fused: bool | None = None
    # forced splits (forcedsplits_filename, serial_tree_learner.cpp:546
    # ForceSplits): BFS-ordered ((parent_leaf, inner_feature, bin), ...)
    # applied as a fixed prefix before gain-driven growth
    forced: tuple = ()
    # count-proxy (int8 only): drop the count channel from the MXU
    # histogram dot so 2 channels x W <= 128 lanes buys waves up to 64
    # leaves wide (fewer full-data passes per tree). Per-bin counts are
    # synthesized as hessian-proportional estimates (they only gate
    # min_data_in_leaf during candidate evaluation); per-LEAF counts
    # stay EXACT — each wave's kernel counts the rows it moved, so
    # leaf_count/internal_count in the model match the exact path.
    count_proxy: bool = False
    # 4-bit packed HBM bins (count-proxy tier only, max_bin <= 16):
    # grow() receives bins_t as [ceil(F/2), N] bytes with two features'
    # nibbles per byte (reference Dense4bitsBin, dense_nbits_bin.hpp);
    # the fused kernel unpacks in VMEM, halving HBM residency. The
    # non-fused fallback unpacks once up front.
    packed4: bool = False
    # quantized histogram reduction (int8 + data-parallel only,
    # config.tpu_quantized_psum): the hist_reduce_fn collective sees
    # the RAW int32 quantized histogram and dequantization happens
    # AFTER the psum — exact integer addition on the wire (LightGBM's
    # communication-compression analog). Sound because the
    # quantization scales are GLOBAL (max_reduce_fn = pmax), so the
    # scale factors commute with the cross-shard sum.
    quant_psum: bool = False
    # packed psum wire (config.tpu_psum_wire, quant_psum only): dtype
    # the quantized histogram payload crosses the collective in.
    # "int32" is the legacy wire; "int16"/"int8" engage when the
    # 127 * n_rows_global wrap bound proves the narrow sum exact
    # (ops/autotune.py tune_psum_wire — the narrowing/widening casts
    # and the integer psum are then all BIT-identical to int32). The
    # field lives here, not just in the reduce closure, so the
    # step-cache geometry key (models/gbdt.py _step_geometry_key)
    # separates programs compiled for different wires.
    psum_wire: str = "int32"
    # overlap-structured collective (config.tpu_async_psum): number of
    # independent slot psums the wave-histogram collective is split
    # into along the feature axis (parallel/learners.py
    # make_hist_reduce). 1 = one monolithic psum; 2 = double-buffered
    # slots XLA can schedule against local compute. psum is
    # elementwise across shards, so any slot count is bit-identical.
    psum_slots: int = 1
    # sparse histogram tier (config.tpu_sparse, CSR-native datasets):
    # grow() receives ``bins_t`` as a TUPLE (dense [F, N] bins,
    # (codes, feat, row, zero_bins) coordinate planes) and wave
    # histograms accumulate by scatter over the nnz explicit entries
    # plus a default-bin completion (ops/hist_wave.py
    # wave_histogram_sparse) instead of the dense one-hot pass; the
    # dense matrix stays resident for the partition. Serial learner
    # only; excludes the fused kernel, count-proxy, packed4 and
    # injected seams.
    sparse_hist: bool = False
    # resolved histogram route (ops/autotune.py tune_hist_route):
    # "pallas-tpu" | "fused-xla" | "two-pass"; "" = auto
    # by backend. models/gbdt.py stamps the resolved value here so the
    # step-cache geometry key separates per-backend programs — a
    # checkpoint restored onto a different device kind re-resolves and
    # recompiles instead of replaying the wrong kernel family.
    route: str = ""


class _State(NamedTuple):
    leaf_ids: jax.Array        # [N]
    hist: jax.Array            # [L, F_hist, B, 3] pool
    # per-leaf best-split table (SplitResult fields, [L] each)
    t_gain: jax.Array
    t_feature: jax.Array
    t_bin: jax.Array
    t_default_left: jax.Array
    t_left_output: jax.Array
    t_right_output: jax.Array
    t_left_count: jax.Array
    t_right_count: jax.Array
    t_left_sum_g: jax.Array
    t_left_sum_h: jax.Array
    t_right_sum_g: jax.Array
    t_right_sum_h: jax.Array
    t_is_cat: jax.Array        # [L] bool
    t_cat_words: jax.Array     # [L, 8] int32 left-set bin bitset
    # per-leaf aggregates
    leaf_output: jax.Array
    leaf_count: jax.Array
    leaf_sum_g: jax.Array
    leaf_sum_h: jax.Array
    leaf_depth: jax.Array
    num_leaves: jax.Array      # scalar int32
    n_splits: jax.Array        # scalar int32 (= num_leaves - 1)
    go_on: jax.Array           # scalar bool
    rec: TreeRecord


_SUM_BLOCK = 8192


def _stable_sum(v: jax.Array) -> jax.Array:
    """Shape-stable f32 row reduction: fixed-width blocks reduced
    per-block, then the block sums added PAIRWISE, padded with zeros to
    a power of two. A zero-padded tail (the step cache's row bucketing,
    ops/step_cache.py) then cannot perturb rounding — every real block
    meets the same partner whatever the padded width and the appended
    blocks add exact zeros — so bucket-padded training reproduces the
    exact-shape run's root aggregates bit-for-bit. A plain ``jnp.sum``
    re-shapes its reduction tree with the array length, changing
    last-bit rounding when only the padded width changed (observed as
    1-ulp root internal_value drift).

    Pairwise, not a running total: in a booster's FIRST tree every
    row's hessian is the same number, so every block sum is, and a
    running total rounds each addition the same way: 13..29 of 2.6 M
    over 1,280 blocks (1e-5 of the total; PERF.md, PR 36). The root's
    total then disagrees with its histogram, every ``total - cumsum``
    hands that absolute error to the child on its side, and a leaf of a
    few hundred rows at the end of such a chain got a hessian total of
    a few rows' worth (the ``correct`` false of seeds 3000000101,
    2600000431 and 2147483659)."""
    n = v.shape[0]
    pad = (-n) % _SUM_BLOCK
    if pad:
        v = jnp.concatenate([v, jnp.zeros(pad, v.dtype)])
    bs = jnp.sum(v.reshape(-1, _SUM_BLOCK), axis=1)
    width = 1 << (bs.shape[0] - 1).bit_length()
    if width > bs.shape[0]:
        bs = jnp.concatenate(
            [bs, jnp.zeros(width - bs.shape[0], bs.dtype)])
    while bs.shape[0] > 1:
        bs = bs[0::2] + bs[1::2]
    return bs[0]


def _mix32(x: jax.Array) -> jax.Array:
    """lowbias32 integer finalizer (uint32 -> well-mixed uint32) — the
    stochastic-rounding hash. Wrapping uint32 arithmetic everywhere."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _hash_uniform(idx: jax.Array, salt: jax.Array) -> jax.Array:
    """Per-row uniform draws in [0, 1) keyed by GLOBAL row index +
    salt. Position-deterministic: the draw of row i is the same no
    matter how rows are sharded across devices, so quantized training
    gives identical trees on 1 chip and on a row-sharded mesh (a
    positional PRNG stream like jax.random.uniform(key, (n,)) would
    not — its counter layout depends on the local shard length)."""
    return (_mix32(idx ^ salt) >> jnp.uint32(8)).astype(
        jnp.float32) * jnp.float32(2.0 ** -24)


def _store_batch(table, idx, vals, active):
    """Masked scatter of per-slot values into a table.

    Inactive slots are sent to index ``len(table)`` — out of bounds HIGH,
    which ``mode="drop"`` discards. (A -1 sentinel would NOT be dropped:
    jax wraps negative scatter indices python-style, silently writing the
    last element.)
    """
    idx = jnp.where(active, idx, table.shape[0])
    return table.at[idx].set(vals, mode="drop")


def make_wave_grower(cfg: WaveGrowerConfig, meta: FeatureMeta,
                     hist_fn=None, split_fn=None, partition_fn=None,
                     reduce_fn=None, hist_reduce_fn=None,
                     max_reduce_fn=None, row_offset_fn=None, jit=True):
    """Build ``grow(bins_t, grad, hess, sample_mask, feature_mask,
    meta=None)``.

    bins_t is FEATURE-MAJOR [F, N] (see ops/hist_wave.py).

    ``meta``: optional TRACED FeatureMeta overriding the factory-time
    constant — the compiled-step registry (ops/step_cache.py) passes
    the per-booster bin metadata as an argument so two boosters binned
    on different data share one compiled program. Omitted (the legacy
    call shape), the factory meta embeds as trace constants exactly as
    before. The default split/partition seams thread it; INJECTED
    seams keep their own closure meta (the learners that inject them
    are not cacheable).

    Injection seams for the parallel learners (SURVEY §2.2):
      hist_fn(bins_t, g, h, leaf_ids, wave_leaves) -> [W, F_hist, B, 3]
        (feature-parallel: local feature slice; voting: local hist,
        election in split_fn)
      split_fn(hists [M,F,B,3], sg [M], sh [M], nd [M], fmask, can [M])
        -> SplitResult of [M] arrays with GLOBAL feature indices
      partition_fn(bins_t, leaf_ids, wl, new_ids, feat, tbin, dleft,
                   active) -> new leaf_ids  (local rows)
      reduce_fn(x) -> global sum of a locally-summed scalar
      hist_reduce_fn(hist) -> cross-device sum of a wave histogram
        (data-parallel psum). Unlike hist_fn, this seam COMPOSES with
        the fused partition+histogram kernel: each shard partitions and
        histograms its own rows in one Pallas pass and only the [W, F,
        B, 3] result rides the collective — the multi-chip path keeps
        the single-chip kernel. With ``cfg.quant_psum`` the seam sees
        the RAW int32 quantized histogram (dequantization runs after
        the collective).
      row_offset_fn(n_local) -> this shard's first GLOBAL row index
        (data/voting: axis_index * n_local; default 0). Feeds the
        stochastic-rounding hash so the quantization draw of a row is
        identical no matter how rows are sharded.

    All default to serial single-device implementations. ``jit=False``
    returns the raw traceable fn for wrapping in shard_map.
    """
    L = cfg.num_leaves
    W = min(cfg.wave_size, max(L - 1, 1))
    B = cfg.num_bins
    hp = cfg.hp
    meta_const = FeatureMeta(*[jnp.asarray(x) for x in meta])

    # fused partition+histogram path (serial mode only: the parallel
    # learners inject their own hist/partition seams)
    default_seams = (hist_fn is None and partition_fn is None)
    quant = cfg.precision == "int8"
    proxy = bool(cfg.count_proxy)
    if proxy and not quant:
        raise ValueError("count_proxy requires precision='int8' "
                         "(tpu_quantized_hist)")
    if proxy and cfg.forced:
        raise ValueError("count_proxy does not compose with forced "
                         "splits; disable tpu_count_proxy")
    if proxy and (hist_fn is not None or partition_fn is not None):
        raise ValueError("count_proxy does not compose with injected "
                         "histogram/partition seams")
    if cfg.packed4 and not (proxy or cfg.precision == "highest"):
        raise ValueError("packed4 bins require the count-proxy or "
                         "hi/lo exact tier")
    if cfg.packed4 and cfg.forced:
        raise ValueError("packed4 does not compose with forced splits "
                         "(the forced prefix reads unpacked bins); "
                         "disable tpu_packed_bins")
    if cfg.sparse_hist and (proxy or cfg.packed4 or cfg.quant_psum):
        raise ValueError("sparse_hist does not compose with "
                         "count_proxy/packed4/quant_psum")
    if cfg.sparse_hist and (hist_fn is not None
                            or partition_fn is not None):
        raise ValueError("sparse_hist does not compose with injected "
                         "histogram/partition seams")
    if quant and hist_fn is not None:
        # an injected histogram seam must understand quantized g/h —
        # silently dropping gh_scale would produce garbage histograms
        import inspect
        if "gh_scale" not in inspect.signature(hist_fn).parameters:
            raise ValueError(
                "int8 quantized histograms need a hist_fn that "
                "accepts gh_scale (see the EFB bundle seam, "
                "models/gbdt.py)")
    defer = bool(cfg.quant_psum)
    if defer and not quant:
        raise ValueError("quant_psum requires precision='int8' "
                         "(tpu_quantized_hist)")
    if defer and (hist_fn is not None or partition_fn is not None):
        # an injected seam returns DEQUANTIZED f32 histograms; psumming
        # those as if they were the int32 wire would double-scale
        raise ValueError("quant_psum does not compose with injected "
                         "histogram/partition seams")
    # the packed-wire/slot fields are CONSUMED by the data-parallel
    # reduce closure (parallel/learners.py make_hist_reduce); they are
    # validated here because this factory owns the config contract and
    # the step-cache geometry key carries them
    if cfg.psum_wire not in ("int8", "int16", "int32"):
        raise ValueError(f"unknown psum_wire {cfg.psum_wire!r} "
                         f"(want one of int8/int16/int32)")
    if cfg.psum_wire != "int32" and not defer:
        raise ValueError("a psum_wire narrower than int32 rides the "
                         "quantized collective (quant_psum=True); the "
                         "f32 wire cannot be narrowed exactly")
    if cfg.psum_slots < 1:
        raise ValueError(f"psum_slots={cfg.psum_slots} must be >= 1")
    if cfg.exact_variant not in ("hilo5", "hilo4", "hilo3"):
        raise ValueError(f"unknown exact_variant {cfg.exact_variant!r}")
    if cfg.exact_variant != "hilo5":
        if cfg.precision != "highest":
            raise ValueError("exact_variant applies to the exact tier "
                             "(precision='highest') only")
        if hist_fn is not None or partition_fn is not None \
                or cfg.sparse_hist:
            # injected seams build their own histogram layout; the
            # sparse tier scatters (layout-free) but the grower's wave
            # cap must then stay at the injected seam's contract
            raise ValueError("exact_variant does not compose with "
                             "injected histogram/partition seams or "
                             "the sparse tier")
    bundled = jnp.ndim(meta_const.bundle) != 0
    # resolve the histogram route once: an explicit cfg.route pins the
    # kernel family (and rode the step-cache geometry key to get here);
    # otherwise the device kind decides (autotune.tune_hist_route)
    from . import autotune
    if cfg.route and cfg.route not in autotune.HIST_ROUTES:
        raise ValueError(f"unknown hist route {cfg.route!r} "
                         f"(want one of {autotune.HIST_ROUTES})")
    route = cfg.route or autotune.tune_hist_route(
        use_pallas=cfg.use_pallas,
        fused_eligible=cfg.fused is not False)
    pallas_hist = route == "pallas-tpu"
    use_fused = cfg.fused
    if use_fused is None:
        from .hist_wave import (FUSED_MAX_WAVE, FUSED_MAX_WAVE_HILO,
                                FUSED_MAX_WAVE_HILO3,
                                FUSED_MAX_WAVE_HILO4,
                                FUSED_MAX_WAVE_INT8,
                                FUSED_MAX_WAVE_INT8_NC)
        fused_cap = (FUSED_MAX_WAVE_INT8_NC if quant and proxy
                     else FUSED_MAX_WAVE_INT8 if quant
                     else {"hilo5": FUSED_MAX_WAVE_HILO,
                           "hilo4": FUSED_MAX_WAVE_HILO4,
                           "hilo3": FUSED_MAX_WAVE_HILO3}[
                               cfg.exact_variant]
                     if cfg.precision == "highest" else FUSED_MAX_WAVE)
        use_fused = (default_seams and W <= fused_cap
                     and not bundled and not cfg.sparse_hist
                     and pallas_hist)
    if use_fused:
        from ..utils.device import on_tpu
        # interpret mode runs the kernel off the chip (the tier-1
        # parity suite drives it on the CPU)
        fused_interpret = not on_tpu()
        fused_chunk = cfg.chunk or DEFAULT_HIST_CHUNK
    # off-TPU twin of the fused kernel (ops/hist_wave.py
    # fused_partition_histogram_xla): partition + smaller-child
    # histogram in one traced region, reusing the leaf-membership
    # compares between the two and riding ONE combined scatter —
    # bit-identical to [partition_fn -> hist_fn], so it is the default
    # off-TPU route wherever the Pallas fused kernel would be the
    # on-TPU one. cfg.fused=False or a pinned route="two-pass" opts out
    # (the legacy two-pass pipeline, kept as the parity oracle).
    use_fused_xla = (not use_fused and cfg.fused is not False
                     and default_seams and not bundled
                     and not cfg.sparse_hist
                     and route == "fused-xla")
    if use_fused_xla:
        from .hist_wave import fused_partition_histogram_xla

    # what this grower's shapes come to, for whoever reads the registry
    # (benchmark/readers/): the pool of every leaf's [F, B, 3] f32
    # histogram, kept for the subtraction, and the feature tiles a
    # histogram pass walks (1: one resident block holds every feature),
    # priced as the kernels price them at trace time. Set here and not
    # in grow(): a step served by the registry is not traced again
    F_meta = int(meta_const.num_bin.shape[0])
    obs.gauge("mem/hist_pool_bytes").set(float(L * F_meta * B * 3 * 4))
    # MACs one block-dot of a wave pass spends on a row of a feature
    # (autotune.root_pass_macs; times hist/blocks_dotted over
    # hist/rows_dotted it is what a dotted row met): the fused
    # kernel's, 0 on every other path, as hist/root_macs below
    wave_macs = 0
    # the fused kernel under feature tiles takes the wave's split
    # columns out of the bins a pass (take_rows; hist/row_take_bytes)
    takes_cols = False
    if route == "pallas-tpu" and default_seams and not cfg.sparse_hist:
        bins_bytes = 1 if B <= 256 else 4
        tier = dict(int8=quant, count_proxy=proxy,
                    variant=(cfg.exact_variant
                             if cfg.precision == "highest" else None))
        chunk = (fused_chunk if use_fused
                 else cfg.chunk or DEFAULT_HIST_CHUNK)
        geom, n_tiles = autotune.hist_feature_tiling(
            F=F_meta, B=B, W=W, fused=bool(use_fused), chunk=chunk,
            F_rows=(-(-F_meta // 2) if cfg.packed4 and use_fused
                    else F_meta), bins_bytes=bins_bytes, **tier)
        obs.gauge("hist/feature_tiles").set(float(n_tiles))
        takes_cols = bool(use_fused) and n_tiles > 1
        if use_fused:
            split = autotune.fused_wave_split(
                geom=geom, compact_tile=autotune.hist_compact_tile(
                    geom=geom, chunk=chunk, bins_bytes=bins_bytes,
                    int8=quant), **tier)
            wave_macs = autotune.root_pass_macs(
                B=B, nchan=split["nchan"] if split else 0,
                split=bool(split))
    obs.gauge("hist/wave_macs").set(float(wave_macs))
    # the root pass: a kernel of its own wherever the shapes and the
    # tier say its digit split pays (autotune.root_split_applies), else
    # the wave kernel with one live slot; an injected hist_fn (the
    # parallel learners', the EFB seam's) keeps its own root. The gauge
    # is set on every path, so it never carries an earlier grower's
    # value: 0 where the root's dot is not this grower's to price (an
    # injected hist_fn, the XLA scatter, the sparse tier)
    root_macs = 0
    use_root_kernel = False
    if pallas_hist and hist_fn is None and not cfg.sparse_hist:
        use_root_kernel = autotune.root_split_applies(
            B=B, precision=cfg.precision, count_proxy=proxy,
            packed4=cfg.packed4)
        root_macs = autotune.root_pass_macs(
            B=B, nchan=root_nchan(cfg.precision, cfg.exact_variant),
            split=use_root_kernel)
    obs.gauge("hist/root_macs").set(float(root_macs))

    if hist_fn is None and cfg.sparse_hist:
        # sparse tier: the histogram source is the (dense bins, sparse
        # planes) tuple grow() unpacks — scatter over nnz instead of
        # the dense pass (ops/hist_wave.py)
        from .hist_wave import wave_histogram_sparse

        def hist_fn(src, g, h, leaf_ids, wave_leaves, gh_scale=None):
            bt, sp = src
            return wave_histogram_sparse(
                sp, g, h, leaf_ids, wave_leaves, num_bins=B,
                num_features=bt.shape[0], gh_scale=gh_scale)
    elif hist_fn is None:
        # the two-pass wave histogram rides the resolved route too —
        # "two-pass" maps to the layout-free XLA scatter inside the
        # dispatcher, the pallas tiers to their device kernel
        hist_route = ("two-pass" if route == "fused-xla" else route)

        def hist_fn(bins_t, g, h, leaf_ids, wave_leaves, gh_scale=None):
            return wave_histogram(bins_t, g, h, leaf_ids, wave_leaves,
                                  num_bins=B, chunk=cfg.chunk,
                                  use_pallas=cfg.use_pallas,
                                  precision=cfg.precision,
                                  gh_scale=gh_scale,
                                  dequant=not defer,
                                  variant=cfg.exact_variant,
                                  route=hist_route)

    # default split/partition seams take meta as a CALL parameter (the
    # compiled-step registry passes a traced override); injected seams
    # keep their original signature and closure meta — the learners
    # that inject them never cache-share across boosters
    user_split_fn, user_partition_fn = split_fn, partition_fn

    def split_fn(hists, sg, sh, nd, fmask, can, meta):
        if user_split_fn is not None:
            return user_split_fn(hists, sg, sh, nd, fmask, can)
        return jax.vmap(
            lambda hh, a, b, c, d: find_best_split(
                hh, a, b, c, fmask, meta, hp, d)
        )(hists, sg, sh, nd, can)

    def partition_fn(bins_t, leaf_ids, wl, new_ids, feat, tbin,
                     dleft, active, meta, iscat=None, catw=None):
        if user_partition_fn is not None:
            return user_partition_fn(bins_t, leaf_ids, wl, new_ids,
                                     feat, tbin, dleft, active, iscat,
                                     catw)
        return apply_wave_splits(bins_t, leaf_ids, wl, new_ids, feat,
                                 tbin, dleft, active, meta,
                                 iscat, catw)

    if reduce_fn is None:
        def reduce_fn(x):
            return x

    # a row-sharding learner injects its cross-shard max with its sum:
    # a tree's wave_work then carries two scalars more, the FULLEST
    # shard's dotted rows beside all shards' (how far the slowest shard
    # holds the others at each sum) and the wave passes that grew the
    # tree (a histogram sum each); a serial record stays [3]
    n_work = 3 if max_reduce_fn is None else 5

    if hist_reduce_fn is None:
        def hist_reduce_fn(h, scope=None):
            return h
    else:
        user_hist_reduce = hist_reduce_fn

        def hist_reduce_fn(h, scope="lgbm/wave/hist_psum"):
            # the learner's collective under a scope of its own: a
            # trace viewer then tells the sum from the kernel before it
            with jax.named_scope(scope):
                return user_hist_reduce(h)

    if max_reduce_fn is None:
        def max_reduce_fn(x):
            return x

    if row_offset_fn is None:
        def row_offset_fn(n_local):
            return jnp.int32(0)

    def depth_ok(depth):
        if cfg.max_depth > 0:
            return depth < cfg.max_depth
        return jnp.ones_like(depth, dtype=bool)

    def bound_counts(h2, gh_scale):
        """count-proxy: fill the count channel with per-bin LOWER
        BOUNDS derived from the quantized g/h sums themselves —
        |g_q| <= 127 and h_q <= 127 per row, so
        count_bin >= max(|sum g_q|, sum h_q) / 127. Bounds are LOCAL
        per bin (valid under prefix/suffix summation and histogram
        subtraction is never applied to them — callers recompute the
        channel from each child's own g/h). With hp.count_lb the
        min_data_in_leaf gate consumes these conservatively: it can
        over-prune but never admits a split the exact gate would
        reject. Per-LEAF totals stay exact via partition-mask counts."""
        h2 = h2[..., :2]
        sg, sh = gh_scale
        lb = jnp.maximum(jnp.abs(h2[..., 0]) / jnp.float32(sg),
                         h2[..., 1] / jnp.float32(sh)) / 127.0
        return jnp.concatenate([h2, lb[..., None]], axis=-1)

    def grow(bins_t, grad, hess, sample_mask, feature_mask, meta=None):
        """Grow one tree.

        bins_t: [F, N] int bins (feature-major); grad/hess: [N] f32;
        sample_mask: [N] f32 0/1 bagging membership;
        feature_mask: [F] bool usable features this tree;
        meta: optional traced FeatureMeta override (step_cache path) —
        None keeps the factory-time constants.
        Returns (TreeRecord, leaf_ids[N]) — leaf_ids of ALL rows
        (out-of-bag included) for score updates.
        """
        meta = meta_const if meta is None else meta
        _sparse_planes = None
        if cfg.sparse_hist:
            # (dense bins, sparse coordinate planes): the dense matrix
            # serves the partition, the planes the histogram scatters;
            # hist call sites pass the pair through ``hsrc``
            bins_t, _sparse_planes = bins_t
        F, n = bins_t.shape
        f32 = jnp.float32
        if cfg.packed4:
            F = int(feature_mask.shape[0])       # logical features
            if not use_fused:
                # oracle/fallback path: unpack nibbles once up front
                # (row 2p = low nibble of byte row p)
                lo = jnp.bitwise_and(bins_t, jnp.uint8(15))
                hi = jnp.right_shift(bins_t, jnp.uint8(4))
                bins_t = jnp.stack([lo, hi], axis=1).reshape(
                    -1, bins_t.shape[1])[:F]
        # histogram source — bound AFTER the packed4 unpack above may
        # have reassigned bins_t
        hsrc = ((bins_t, _sparse_planes) if cfg.sparse_hist
                else bins_t)
        # the grower's own g/h: bagged, and quantized on the int8 tiers
        with jax.named_scope("lgbm/gradients"):
            grad = grad.astype(f32) * sample_mask
            hess = hess.astype(f32) * sample_mask
            in_bag = sample_mask > 0

            if quant:
                # gradient quantization (tpu_quantized_hist): integer-valued
                # g/h in [-127, 127] make every MXU histogram product an
                # exact int8 op at 2x the bf16 rate.
                # GLOBAL quantization scales (max_reduce_fn = pmax in data
                # mode): shard-local scales would make the dequantized psum
                # sums correct but leave count-proxy bounds computed on the
                # GLOBAL histogram invalid (divided by a local scale) and
                # shard-divergent — every shard must see one (sg, sh).
                # max is order-independent, so the pmax of shard maxima
                # equals the single-chip max EXACTLY.
                sg_s = jnp.maximum(max_reduce_fn(jnp.max(jnp.abs(grad))),
                                   1e-30) / 127.0
                sh_s = jnp.maximum(max_reduce_fn(jnp.max(hess)),
                                   1e-30) / 127.0
                # stochastic rounding keyed by GLOBAL row index (shard
                # offset + local position) and a per-tree salt: unbiased
                # per-bin sums and — unlike a positional PRNG stream —
                # the same draw for the same row under ANY row sharding,
                # so quantized data-parallel training reproduces the
                # single-chip quantized trees. The salt mixes the scale
                # bits with a WRAPPING int32 sum of the raw gradient bits:
                # mod-2^32 adds commute, so the psum of shard-local bit
                # sums equals the single-chip sum exactly (layout
                # invariance), and the stream re-rolls whenever ANY row's
                # gradient moves — scale bits alone would freeze it for
                # constant-bound objectives (L1-family: max|g| and max h
                # never change between trees).
                bg = jax.lax.bitcast_convert_type(
                    sg_s.astype(f32), jnp.uint32)
                bh = jax.lax.bitcast_convert_type(
                    sh_s.astype(f32), jnp.uint32)
                gbits_sum = reduce_fn(jnp.sum(
                    jax.lax.bitcast_convert_type(grad, jnp.int32),
                    dtype=jnp.int32))
                salt = (bg ^ ((bh << jnp.uint32(16)) | (bh >> jnp.uint32(16)))
                        ^ _mix32(gbits_sum.astype(jnp.uint32)))
                gidx = (row_offset_fn(n)
                        + jnp.arange(n, dtype=jnp.int32)).astype(jnp.uint32)
                u_g = _hash_uniform(gidx, salt)
                u_h = _hash_uniform(gidx, salt ^ jnp.uint32(0x9E3779B9))
                gq = jnp.clip(jnp.floor(grad / sg_s + u_g), -127.0, 127.0)
                hq = jnp.clip(jnp.floor(hess / sh_s + u_h), 0.0, 127.0)
                gh_scale = (sg_s, sh_s)
                hg, hh = gq, hq            # what histogram passes consume

                def call_hist(bt, lids, wl):
                    return hist_fn(bt, hg, hh, lids, wl, gh_scale)
            else:
                gh_scale = None
                hg, hh = grad, hess

                def call_hist(bt, lids, wl):
                    return hist_fn(bt, hg, hh, lids, wl)

        def dq(hsum):
            """Dequantize a reduced quantized-wire histogram — identity
            unless cfg.quant_psum deferred the scaling past the
            collective. Handles both the 2-channel proxy wire and the
            3-channel wire (the XLA oracle keeps 3 channels)."""
            if not defer:
                return hsum
            hsum = hsum.astype(f32)
            sgf = jnp.float32(gh_scale[0])
            shf = jnp.float32(gh_scale[1])
            if hsum.shape[-1] == 2:
                return hsum * jnp.stack([sgf, shf])
            return hsum * jnp.stack([sgf, shf, jnp.float32(1.0)])

        # Bagging: leaf_ids tracks ALL rows (out-of-bag rows partition
        # too — scores need their leaf), but histogram passes see
        # out-of-bag rows as leaf -1 so no wave slot counts them.
        def bag_mask_ids(leaf_ids):
            return jnp.where(in_bag, leaf_ids, -1)

        # root: wave histogram with one active slot = leaf 0
        with jax.named_scope("lgbm/root_hist"):
            root_wl = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.full(W - 1, -1, jnp.int32)])
            leaf0 = jnp.zeros(n, jnp.int32)
            if use_root_kernel:
                from ..utils.device import on_tpu
                local_root = root_histogram_pallas(
                    bins_t, hg, hh, bag_mask_ids(leaf0), num_bins=B,
                    chunk=cfg.chunk or DEFAULT_HIST_CHUNK,
                    interpret=not on_tpu(), precision=cfg.precision,
                    variant=cfg.exact_variant)       # [1, F, B, 3]
            elif use_fused and (proxy or cfg.packed4):
                # proxy/packed4 root: the partition-free wave kernel in the
                # matching tier — no partition logic to pay for on an
                # unsplit tree, and (packed4) the default hist_fn never
                # sees the packed byte rows the fused path keeps in HBM
                local_root = wave_histogram_pallas(
                    bins_t, hg, hh, bag_mask_ids(leaf0), root_wl,
                    num_bins=B, chunk=fused_chunk,
                    interpret=fused_interpret, precision=cfg.precision,
                    gh_scale=gh_scale, count_proxy=proxy,
                    packed4=cfg.packed4,
                    num_features=F if cfg.packed4 else None,
                    dequant=not defer, variant=cfg.exact_variant)
            else:
                local_root = call_hist(hsrc, bag_mask_ids(leaf0),
                                       root_wl)              # [W, F, B, 3]
            root_hist = dq(hist_reduce_fn(local_root,
                                          scope="lgbm/root_hist/psum"))
            F_h = root_hist.shape[1]
            # the bytes one wave pass's row takes read (take_rows): the W
            # parents' rows of the pool, and the W split columns where
            # the fused kernel walks feature tiles. Set as the step is
            # traced, where the rows are known
            obs.gauge("hist/row_take_bytes").set(float(
                W * F_h * B * 3 * 4
                + (W * n * bins_t.dtype.itemsize if takes_cols else 0)))
            if quant:
                # root aggregates as dequantized sums of the SAME integer
                # g/h the histogram passes consume, so later subtractions
                # stay internally consistent — computed directly from
                # hg/hq rather than a histogram column: a hist_fn that
                # zero-pads unowned features (the EFB x feature-parallel
                # seam expands only the local bundle slice) would make a
                # column-derived sum device-dependent. Local sum then the
                # scalar reducer: one collective in every mode. The LOCAL
                # sum accumulates in int32 when the shard's row count
                # provably cannot wrap it (|v| <= 127 so the total is
                # bounded by 127*n < 2^31 — the same bound the Pallas
                # kernels' overflow guard enforces; the XLA fallback path
                # has no such guard, so bigger shards keep the old f32
                # sum, which rounds but never wraps). The exact per-shard
                # total converts to f32 BEFORE the reducer: an int32 psum
                # across D shards could wrap even when every shard is
                # within bound, while the f32 psum of D already-exact
                # totals rounds only D-1 additions.
                if 127 * n < 2 ** 31:
                    def acc(v):
                        return jnp.sum(v.astype(jnp.int32)).astype(f32)
                else:
                    acc = _stable_sum
                root_g = reduce_fn(acc(hg)) * gh_scale[0]
                root_h = reduce_fn(acc(hh)) * gh_scale[1]
            else:
                # shape-stable sums: bucket-padded and exact-shape boosters
                # must agree bit-for-bit (ops/step_cache.py row bucketing)
                root_g = reduce_fn(_stable_sum(grad))
                root_h = reduce_fn(_stable_sum(hess))
            root_c = reduce_fn(jnp.sum(sample_mask))
            if proxy:
                root_hist = bound_counts(root_hist, gh_scale)
        with jax.named_scope("lgbm/wave/split_find"):
            root_split = split_fn(
                root_hist[:1], root_g[None], root_h[None], root_c[None],
                feature_mask, depth_ok(jnp.zeros(1, jnp.int32)), meta)

        def set0(arr, v):
            return arr.at[0].set(v[0] if v.ndim else v)

        with jax.named_scope("lgbm/root_hist"):
            # the pool, the root's histogram in its first slot
            pool0 = jnp.zeros((L, F_h, B, 3), f32).at[0].set(root_hist[0])
        with jax.named_scope("lgbm/wave/bookkeep"):
            state = _State(
                leaf_ids=leaf0,
                hist=pool0,
                t_gain=set0(jnp.full(L, KMIN_SCORE, f32), root_split.gain),
                t_feature=set0(jnp.zeros(L, jnp.int32), root_split.feature),
                t_bin=set0(jnp.zeros(L, jnp.int32), root_split.threshold_bin),
                t_default_left=set0(jnp.zeros(L, bool),
                                    root_split.default_left),
                t_left_output=set0(jnp.zeros(L, f32), root_split.left_output),
                t_right_output=set0(jnp.zeros(L, f32),
                                    root_split.right_output),
                t_left_count=set0(jnp.zeros(L, f32), root_split.left_count),
                t_right_count=set0(jnp.zeros(L, f32), root_split.right_count),
                t_left_sum_g=set0(jnp.zeros(L, f32), root_split.left_sum_g),
                t_left_sum_h=set0(jnp.zeros(L, f32), root_split.left_sum_h),
                t_right_sum_g=set0(jnp.zeros(L, f32), root_split.right_sum_g),
                t_right_sum_h=set0(jnp.zeros(L, f32), root_split.right_sum_h),
                t_is_cat=set0(jnp.zeros(L, bool), root_split.is_cat),
                t_cat_words=jnp.zeros((L, 8), jnp.int32).at[0].set(
                    root_split.cat_words[0] if root_split.cat_words.ndim > 1
                    else root_split.cat_words),
                leaf_output=jnp.zeros(L, f32),
                leaf_count=jnp.zeros(L, f32).at[0].set(root_c),
                leaf_sum_g=jnp.zeros(L, f32).at[0].set(root_g),
                leaf_sum_h=jnp.zeros(L, f32).at[0].set(root_h),
                leaf_depth=jnp.zeros(L, jnp.int32),
                num_leaves=jnp.int32(1),
                n_splits=jnp.int32(0),
                go_on=jnp.bool_(True),
                rec=TreeRecord(
                    num_leaves=jnp.int32(1),
                    split_leaf=jnp.full(L - 1, -1, jnp.int32),
                    split_feature=jnp.full(L - 1, -1, jnp.int32),
                    split_bin=jnp.zeros(L - 1, jnp.int32),
                    split_gain=jnp.zeros(L - 1, f32),
                    split_default_left=jnp.zeros(L - 1, bool),
                    leaf_output=jnp.zeros(L, f32),
                    leaf_count=jnp.zeros(L, f32),
                    leaf_sum_g=jnp.zeros(L, f32),
                    leaf_sum_h=jnp.zeros(L, f32),
                    internal_value=jnp.zeros(L - 1, f32),
                    internal_count=jnp.zeros(L - 1, f32),
                    split_is_cat=jnp.zeros(L - 1, bool),
                    split_cat_words=jnp.zeros((L - 1, 8), jnp.int32),
                    wave_work=jnp.zeros(n_work, jnp.int32),
                ),
            )

        def body(state: _State) -> _State:
            f32 = jnp.float32
            with jax.named_scope("lgbm/wave/bookkeep"):
                # 1. elect the wave: top-W leaves by gain, capped by budget
                top_gain, wl = jax.lax.top_k(state.t_gain, W)   # [W]
                wl = wl.astype(jnp.int32)
                budget = (L - state.num_leaves).astype(jnp.int32)
                rank = jnp.arange(W, dtype=jnp.int32)
                active = (top_gain > 0.0) & (rank < budget)
                n_act = jnp.sum(active.astype(jnp.int32))
                prefix = jnp.cumsum(active.astype(jnp.int32)) - active
                new_ids = jnp.where(active, state.num_leaves + prefix, -1)
                wl = jnp.where(active, wl, -1)
                # scatter-safe slot indices: OOB-high sentinel so that
                # mode="drop" really drops inactive slots (negative indices
                # would wrap python-style and corrupt the last entries)
                wl_s = jnp.where(active, wl, L)
                new_s = jnp.where(active, new_ids, L)

                # 2. per-slot split params from the table (drop-safe gathers)
                feat = state.t_feature[wl]
                tbin = state.t_bin[wl]
                dleft = state.t_default_left[wl]
                iscat = state.t_is_cat[wl]
                catw = state.t_cat_words[wl]               # [W, 8]
                lcnt = state.t_left_count[wl]
                rcnt = state.t_right_count[wl]
                lg, lh = state.t_left_sum_g[wl], state.t_left_sum_h[wl]
                rg, rh = state.t_right_sum_g[wl], state.t_right_sum_h[wl]
                lo, ro = state.t_left_output[wl], state.t_right_output[wl]

            with jax.named_scope("lgbm/wave/hist"):
                # 3+4. partition, then smaller-child histograms; siblings by
                # subtraction from the pooled parent histogram. The fused
                # Pallas path does both in ONE data pass (ocl/histogram256's
                # partition-then-accumulate per workgroup, without the W
                # separate partition passes).
                left_smaller = lcnt <= rcnt
                small_ids = jnp.where(left_smaller, wl, new_ids)
                small_ids = jnp.where(active, small_ids, -1)
                # rows this pass scanned / put through the dot, and the
                # block-dots they met: the fused TPU kernel counts
                # them, no other route does
                wave_work = jnp.zeros(3, jnp.int32)
                work_max = wave_work[1:2]
                if use_fused:
                    safe_feat = jnp.maximum(feat, 0)
                    tbl = jnp.concatenate([jnp.stack([
                        wl, new_ids, safe_feat, tbin,
                        dleft.astype(jnp.int32),
                        meta.missing_type[safe_feat],
                        meta.default_bin[safe_feat],
                        meta.num_bin[safe_feat], small_ids,
                        iscat.astype(jnp.int32)]), catw.T])      # [18, W]
                    fused_out = fused_partition_histogram_pallas(
                        bins_t, hg, hh, sample_mask,
                        state.leaf_ids, tbl, num_bins=B,
                        chunk=fused_chunk,
                        interpret=fused_interpret,
                        precision=cfg.precision, gh_scale=gh_scale,
                        any_cat=bool(hp.has_cat), count_proxy=proxy,
                        packed4=cfg.packed4,
                        num_features=F if cfg.packed4 else None,
                        dequant=not defer, variant=cfg.exact_variant)
                    leaf_ids, hist_small = fused_out[0], fused_out[1]
                    hist_small = dq(hist_reduce_fn(hist_small))
                    if proxy:
                        cnt_r = reduce_fn(fused_out[2])
                    wave_work = reduce_fn(fused_out[-1])   # all shards'
                    work_max = max_reduce_fn(fused_out[-1][1:2])
                    # out-of-bag rows partition too; their g/h are pre-masked
                    # and the count channel rides on sample_mask
                elif use_fused_xla:
                    # off-TPU fused route: one traced partition+histogram
                    # region reusing the membership compares and the
                    # combined 3-channel scatter — bit-identical to the
                    # legacy [partition_fn -> call_hist] pipeline below
                    safe_feat = jnp.maximum(feat, 0)
                    fx = fused_partition_histogram_xla(
                        bins_t, hg, hh, sample_mask, state.leaf_ids,
                        wl, new_ids, feat, tbin, dleft, iscat, catw,
                        small_ids,
                        meta.missing_type[safe_feat],
                        meta.default_bin[safe_feat],
                        meta.num_bin[safe_feat],
                        num_bins=B, count_proxy=proxy,
                        gh_scale=gh_scale if quant else None,
                        dequant=not defer)
                    leaf_ids = fx[0]
                    hist_small = dq(hist_reduce_fn(fx[1]))
                    if proxy:
                        cnt_r = reduce_fn(fx[2])
                else:
                    leaf_ids = partition_fn(bins_t, state.leaf_ids, wl,
                                            new_ids, feat, tbin, dleft,
                                            active, meta, iscat, catw)
                    hist_small = dq(hist_reduce_fn(
                        call_hist(hsrc, bag_mask_ids(leaf_ids),
                                  small_ids)))
                    if proxy:
                        # exact in-bag right-child counts (XLA fallback for
                        # the Pallas kernel's partition-mask counting)
                        cnt_r = reduce_fn(jnp.sum(
                            ((leaf_ids[None, :] == new_ids[:, None])
                             & in_bag[None, :]).astype(jnp.float32),
                            axis=1))
                if proxy:
                    parent_cnt = state.leaf_count[wl]
                    lcnt_x = parent_cnt - cnt_r          # exact (partition)
                    rcnt_x = cnt_r
                    hist_small = bound_counts(hist_small, gh_scale)
                else:
                    lcnt_x, rcnt_x = lcnt, rcnt
                parent_hist = take_rows(state.hist, wl)      # [W, F, B, 3]
                hist_large = parent_hist - hist_small
                if proxy:
                    # the count channel holds lower bounds, which do NOT
                    # survive subtraction — recompute from the large
                    # child's own (exact) g/h sums
                    hist_large = bound_counts(hist_large, gh_scale)
                ls4 = left_smaller[:, None, None, None]
                hist_left = jnp.where(ls4, hist_small, hist_large)
                hist_right = jnp.where(ls4, hist_large, hist_small)
                pool = state.hist
                pool = pool.at[wl_s].set(hist_left, mode="drop")
                pool = pool.at[new_s].set(hist_right, mode="drop")

            with jax.named_scope("lgbm/wave/bookkeep"):
                # 5. record the wave's splits at positions n_splits + prefix
                pos = jnp.where(active, state.n_splits + prefix, L - 1)
                parent_out = calculate_leaf_output(
                    state.leaf_sum_g[wl], state.leaf_sum_h[wl],
                    hp.lambda_l1, hp.lambda_l2, hp.max_delta_step)
                rec = state.rec
                if n_work > 3:
                    # ... the fullest shard's dotted rows, and this pass
                    wave_work = jnp.concatenate(
                        [wave_work, work_max, jnp.ones(1, jnp.int32)])
                rec = rec._replace(
                    wave_work=rec.wave_work + wave_work,
                    num_leaves=rec.num_leaves + n_act,
                    split_leaf=rec.split_leaf.at[pos].set(wl, mode="drop"),
                    split_feature=rec.split_feature.at[pos].set(
                        feat, mode="drop"),
                    split_bin=rec.split_bin.at[pos].set(tbin, mode="drop"),
                    split_gain=rec.split_gain.at[pos].set(
                        jnp.where(active, top_gain, 0.0), mode="drop"),
                    split_default_left=rec.split_default_left.at[pos].set(
                        dleft, mode="drop"),
                    split_is_cat=rec.split_is_cat.at[pos].set(
                        iscat, mode="drop"),
                    split_cat_words=rec.split_cat_words.at[pos].set(
                        catw, mode="drop"),
                    internal_value=rec.internal_value.at[pos].set(
                        parent_out, mode="drop"),
                    internal_count=rec.internal_count.at[pos].set(
                        state.leaf_count[wl], mode="drop"),
                )

                # 6. per-leaf aggregate updates (left child keeps parent id)
                child_depth = state.leaf_depth[wl] + 1

                def upd(arr, lvals, rvals):
                    arr = arr.at[wl_s].set(lvals, mode="drop")
                    return arr.at[new_s].set(rvals, mode="drop")

                leaf_output = upd(state.leaf_output, lo, ro)
                # proxy mode: lcnt_x/rcnt_x are the partition-mask EXACT
                # counts, so per-leaf bookkeeping (and the model file's
                # leaf_count/internal_count) matches the exact path
                leaf_count = upd(state.leaf_count, lcnt_x, rcnt_x)
                leaf_sum_g = upd(state.leaf_sum_g, lg, rg)
                leaf_sum_h = upd(state.leaf_sum_h, lh, rh)
                leaf_depth = upd(state.leaf_depth, child_depth, child_depth)

            with jax.named_scope("lgbm/wave/split_find"):
                # 7. best splits for the 2W children
                hists2 = jnp.concatenate([hist_left, hist_right], axis=0)
                sg2 = jnp.concatenate([lg, rg])
                sh2 = jnp.concatenate([lh, rh])
                nd2 = jnp.concatenate([lcnt_x, rcnt_x])
                can2 = jnp.concatenate([active & depth_ok(child_depth)] * 2)
                res = split_fn(hists2, sg2, sh2, nd2, feature_mask, can2,
                               meta)
                gain2 = jnp.where(jnp.isfinite(res.gain), res.gain,
                                  KMIN_SCORE)
            with jax.named_scope("lgbm/wave/bookkeep"):
                idx2 = jnp.concatenate([wl_s, new_s])
                act2 = jnp.concatenate([active] * 2)

                st = lambda tbl, v: _store_batch(tbl, idx2, v, act2)
                state = state._replace(
                    leaf_ids=leaf_ids,
                    hist=pool,
                    t_gain=st(state.t_gain, gain2),
                    t_feature=st(state.t_feature, res.feature),
                    t_bin=st(state.t_bin, res.threshold_bin),
                    t_default_left=st(state.t_default_left, res.default_left),
                    t_left_output=st(state.t_left_output, res.left_output),
                    t_right_output=st(state.t_right_output, res.right_output),
                    t_left_count=st(state.t_left_count, res.left_count),
                    t_right_count=st(state.t_right_count, res.right_count),
                    t_left_sum_g=st(state.t_left_sum_g, res.left_sum_g),
                    t_left_sum_h=st(state.t_left_sum_h, res.left_sum_h),
                    t_right_sum_g=st(state.t_right_sum_g, res.right_sum_g),
                    t_right_sum_h=st(state.t_right_sum_h, res.right_sum_h),
                    t_is_cat=st(state.t_is_cat, res.is_cat),
                    t_cat_words=st(state.t_cat_words, res.cat_words),
                    leaf_output=leaf_output,
                    leaf_count=leaf_count,
                    leaf_sum_g=leaf_sum_g,
                    leaf_sum_h=leaf_sum_h,
                    leaf_depth=leaf_depth,
                    num_leaves=state.num_leaves + n_act,
                    n_splits=state.n_splits + n_act,
                    go_on=(n_act > 0) & (state.num_leaves + n_act < L),
                    rec=rec,
                )
            return state

        # ---- forced-split prefix (ForceSplits) ----
        # Each forced split is applied like a single-slot wave with the
        # (feature, bin) CHOSEN instead of elected; children then get
        # their gain tables so gain-driven growth continues from leaf
        # numbering identical to the reference's BFS application.
        # (This intentionally mirrors body() steps 3-7 with the
        # election replaced — keep the two in sync.)
        for fs_leaf, fs_feat, fs_bin in cfg.forced:
            wl = jnp.concatenate([jnp.full(1, fs_leaf, jnp.int32),
                                  jnp.full(W - 1, -1, jnp.int32)])
            new_ids = jnp.concatenate(
                [state.num_leaves[None].astype(jnp.int32),
                 jnp.full(W - 1, -1, jnp.int32)])
            feat = jnp.full(W, fs_feat, jnp.int32)
            tbin = jnp.full(W, fs_bin, jnp.int32)
            dleft = jnp.zeros(W, bool)
            active = wl >= 0
            iscat0 = jnp.zeros(W, bool)
            catw0 = jnp.zeros((W, 8), jnp.int32)
            with jax.named_scope("lgbm/wave/hist"):
                leaf_ids = partition_fn(bins_t, state.leaf_ids, wl, new_ids,
                                        feat, tbin, dleft, active, meta,
                                        iscat0, catw0)
                # left child keeps the parent id: histogram it directly,
                # sibling by subtraction (sizes don't matter here)
                hist_left = dq(hist_reduce_fn(
                    call_hist(hsrc, bag_mask_ids(leaf_ids), wl)))
                parent_hist = take_rows(state.hist, wl)
                hist_right = parent_hist - hist_left
                wl_s = jnp.where(active, wl, L)
                new_s = jnp.where(active, new_ids, L)
                pool = state.hist.at[wl_s].set(hist_left, mode="drop")
                pool = pool.at[new_s].set(hist_right, mode="drop")
            with jax.named_scope("lgbm/wave/bookkeep"):
                # child sums from any one feature's bins (every row lands
                # in exactly one bin per feature)
                lg = hist_left[:, 0, :, 0].sum(axis=1)
                lh = hist_left[:, 0, :, 1].sum(axis=1)
                lcnt = hist_left[:, 0, :, 2].sum(axis=1)
                rg = state.leaf_sum_g[wl] - lg
                rh = state.leaf_sum_h[wl] - lh
                rcnt = state.leaf_count[wl] - lcnt
                parent_out = calculate_leaf_output(
                    state.leaf_sum_g[wl], state.leaf_sum_h[wl],
                    hp.lambda_l1, hp.lambda_l2, hp.max_delta_step)
                # real gain like the reference's GatherInfoForThreshold:
                # children's split gains minus the parent's
                from .split import leaf_split_gain
                forced_gain = (
                    leaf_split_gain(lg, lh + 1e-15, hp.lambda_l1,
                                    hp.lambda_l2, hp.max_delta_step)
                    + leaf_split_gain(rg, rh + 1e-15, hp.lambda_l1,
                                      hp.lambda_l2, hp.max_delta_step)
                    - leaf_split_gain(state.leaf_sum_g[wl],
                                      state.leaf_sum_h[wl] + 2e-15,
                                      hp.lambda_l1, hp.lambda_l2,
                                      hp.max_delta_step))
                pos = jnp.where(active, state.n_splits, L - 1)
                rec = state.rec
                rec = rec._replace(
                    num_leaves=rec.num_leaves + 1,
                    split_leaf=rec.split_leaf.at[pos].set(wl, mode="drop"),
                    split_feature=rec.split_feature.at[pos].set(
                        feat, mode="drop"),
                    split_bin=rec.split_bin.at[pos].set(tbin, mode="drop"),
                    split_gain=rec.split_gain.at[pos].set(
                        forced_gain, mode="drop"),
                    split_default_left=rec.split_default_left.at[pos].set(
                        dleft, mode="drop"),
                    internal_value=rec.internal_value.at[pos].set(
                        parent_out, mode="drop"),
                    internal_count=rec.internal_count.at[pos].set(
                        state.leaf_count[wl], mode="drop"),
                )
                child_depth = state.leaf_depth[wl] + 1

                def updf(arr, lv, rv):
                    arr = arr.at[wl_s].set(lv, mode="drop")
                    return arr.at[new_s].set(rv, mode="drop")
                # empty-child guard: the reference refuses degenerate
                # forced splits (ForceSplits count checks); here the empty
                # side just gets a zero output instead of -0/0 = NaN
                lo = jnp.where(lcnt > 0, calculate_leaf_output(
                    lg, lh + 1e-15, hp.lambda_l1, hp.lambda_l2,
                    hp.max_delta_step), 0.0)
                ro = jnp.where(rcnt > 0, calculate_leaf_output(
                    rg, rh + 1e-15, hp.lambda_l1, hp.lambda_l2,
                    hp.max_delta_step), 0.0)
            with jax.named_scope("lgbm/wave/split_find"):
                hists2 = jnp.concatenate([hist_left, hist_right], axis=0)
                sg2 = jnp.concatenate([lg, rg])
                sh2 = jnp.concatenate([lh, rh])
                nd2 = jnp.concatenate([lcnt, rcnt])
                can2 = jnp.concatenate([active & depth_ok(child_depth)] * 2)
                res = split_fn(hists2, sg2, sh2, nd2, feature_mask, can2,
                               meta)
                gain2 = jnp.where(jnp.isfinite(res.gain), res.gain,
                                  KMIN_SCORE)
            with jax.named_scope("lgbm/wave/bookkeep"):
                idx2 = jnp.concatenate([wl_s, new_s])
                act2 = jnp.concatenate([active] * 2)
                st = lambda tbl, v: _store_batch(tbl, idx2, v, act2)
                state = state._replace(
                    leaf_ids=leaf_ids,
                    hist=pool,
                    t_gain=st(state.t_gain, gain2),
                    t_feature=st(state.t_feature, res.feature),
                    t_bin=st(state.t_bin, res.threshold_bin),
                    t_default_left=st(state.t_default_left,
                                      res.default_left),
                    t_left_output=st(state.t_left_output, res.left_output),
                    t_right_output=st(state.t_right_output,
                                      res.right_output),
                    t_left_count=st(state.t_left_count, res.left_count),
                    t_right_count=st(state.t_right_count, res.right_count),
                    t_left_sum_g=st(state.t_left_sum_g, res.left_sum_g),
                    t_left_sum_h=st(state.t_left_sum_h, res.left_sum_h),
                    t_right_sum_g=st(state.t_right_sum_g, res.right_sum_g),
                    t_right_sum_h=st(state.t_right_sum_h, res.right_sum_h),
                    t_is_cat=st(state.t_is_cat, res.is_cat),
                    t_cat_words=st(state.t_cat_words, res.cat_words),
                    leaf_output=updf(state.leaf_output, lo, ro),
                    leaf_count=updf(state.leaf_count, lcnt, rcnt),
                    leaf_sum_g=updf(state.leaf_sum_g, lg, rg),
                    leaf_sum_h=updf(state.leaf_sum_h, lh, rh),
                    leaf_depth=updf(state.leaf_depth, child_depth,
                                    child_depth),
                    num_leaves=state.num_leaves + 1,
                    n_splits=state.n_splits + 1,
                    rec=rec,
                )

        with jax.named_scope("lgbm/wave/loop"):
            # the loop's own: its condition and the carry's copies
            state = jax.lax.while_loop(lambda s: s.go_on, body, state)
        leaf_count = state.leaf_count
        if n_work > 3 or n > 2 ** 24:
            # past 2^24 rows a float32 count is no integer any more: a
            # split's left count rounds in its cumsum, the right one
            # comes by subtraction, and the error goes down the tree to
            # leaves of any size. The rows' own leaf ids say how many
            # each leaf holds, as integers, summed over the shards
            # (which this grower cannot count: every row-sharding
            # learner takes this path)
            with jax.named_scope("lgbm/leaf_counts"):
                ids = jnp.where(in_bag, state.leaf_ids, -1)
                held = jnp.sum(
                    ids[None, :] == jnp.arange(L, dtype=ids.dtype)[:, None],
                    axis=1, dtype=jnp.int32)
                leaf_count = reduce_fn(held).astype(f32)
        rec = state.rec._replace(
            leaf_output=state.leaf_output,
            leaf_count=leaf_count,
            leaf_sum_g=state.leaf_sum_g,
            leaf_sum_h=state.leaf_sum_h,
        )
        return rec, state.leaf_ids

    # jit-capture: ok(B, hp, cfg, quant, use_fused, use_fused_xla,
    # use_root_kernel, takes_cols,
    # fused_chunk, fused_interpret, fused_partition_histogram_xla,
    # meta_const,
    # bound_counts, depth_ok, hist_fn, hist_reduce_fn, reduce_fn,
    # max_reduce_fn, row_offset_fn, split_fn, partition_fn, n_work) —
    # factory-scoped jit: every capture derives from this factory
    # call's WaveGrowerConfig/meta/seam callables. meta_const is the
    # LEGACY 5-arg fallback only; registry-path callers pass meta as
    # the traced 6th argument (PR 5), and the step-cache geometry key
    # covers cfg + the meta signature, so a registry hit can never
    # see another booster's meta_const.
    out = jax.jit(grow) if jit else grow
    # what this factory RESOLVED (not what the config asked for): the
    # route, whether the fused Pallas kernel serves the wave passes and
    # whether it runs interpreted — GBDT.device_report() reads it so a
    # smoke run can assert the kernels were compiled, not stood in for
    out.resolved = {"route": route, "fused_pallas": bool(use_fused),
                    "fused_xla": bool(use_fused_xla),
                    "interpret": bool(use_fused and fused_interpret),
                    # slots of the ROOT pass's histogram (what a
                    # row-sharding learner sums there): the root kernel
                    # makes one, every other root a whole wave's
                    "root_slots": 1 if use_root_kernel else W,
                    # the width of a record's wave_work (n_work above)
                    "work_len": n_work}
    return out


def apply_wave_splits(bins_t, leaf_ids, wl, new_ids, feat, tbin, dleft,
                      active, meta: FeatureMeta, iscat=None, catw=None):
    """Apply up to W splits to the row partition in one fused pass.

    For each wave slot k: rows with ``leaf_ids == wl[k]`` whose binned
    feature value goes right move to ``new_ids[k]``
    (DataPartition::Split + Bin::Split semantics,
    src/treelearner/data_partition.hpp:109-166). ``iscat``/``catw``
    carry per-slot categorical flags + left-set bitsets.
    """
    W = wl.shape[0]
    out = leaf_ids
    for k in range(W):
        col = member_column(bins_t, feat[k], meta)   # EFB-decoded
        right = row_goes_right(
            col, tbin[k], dleft[k],
            meta.missing_type[feat[k]], meta.default_bin[feat[k]],
            meta.num_bin[feat[k]],
            is_cat=(False if iscat is None else iscat[k]),
            cat_words=(None if catw is None else catw[k]))
        move = (leaf_ids == wl[k]) & right & active[k]
        out = jnp.where(move, new_ids[k], out)
    return out
