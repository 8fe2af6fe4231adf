"""On-device leaf-wise tree grower.

TPU-native counterpart of SerialTreeLearner::Train
(reference: src/treelearner/serial_tree_learner.cpp:157-221). The
reference's outer split loop runs on the host with pointer-juggled
histogram pools; here the ENTIRE tree build is one compiled XLA program:
a ``lax.fori_loop`` of ``num_leaves - 1`` shape-static steps, each doing

  1. pick the leaf with max split gain         (argmax over leaf table)
  2. apply the split to the partition          (masked select, O(N))
  3. build the histogram of the SMALLER child  (one-hot MXU contraction)
  4. sibling histogram by subtraction          (parent - smaller; hpp:68)
  5. best-split search for both children       (vectorized cumsum scan)

No host round-trips during growth; the histogram "pool"
(feature_histogram.hpp:655) becomes a preallocated HBM tensor
``[num_leaves, F, B, 3]`` indexed by leaf id.

Leaf numbering matches Tree::Split: at split ``i`` the left child keeps
the parent's leaf index and the right child becomes leaf ``i + 1``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .histogram import build_histogram
from .partition import apply_split
from .split import (FeatureMeta, SplitParams, SplitResult, KMIN_SCORE,
                    calculate_leaf_output, find_best_split)


class GrowerConfig(NamedTuple):
    """Static compile-time configuration of one grower."""
    num_leaves: int
    num_bins: int          # padded global B
    max_depth: int = -1
    chunk: int = 16384
    hp: SplitParams = SplitParams()


class TreeRecord(NamedTuple):
    """Device-side record of one grown tree (host builds a Tree from it)."""
    num_leaves: jax.Array          # scalar int32 — actual leaves
    split_leaf: jax.Array          # [L-1] parent leaf id per split (-1 unused)
    split_feature: jax.Array       # [L-1]
    split_bin: jax.Array           # [L-1] threshold in bin space
    split_gain: jax.Array          # [L-1]
    split_default_left: jax.Array  # [L-1] bool
    leaf_output: jax.Array         # [L] raw output (no shrinkage)
    leaf_count: jax.Array          # [L]
    leaf_sum_g: jax.Array          # [L]
    leaf_sum_h: jax.Array          # [L]
    internal_value: jax.Array      # [L-1] parent raw output at split time
    internal_count: jax.Array      # [L-1]
    split_is_cat: jax.Array        # [L-1] bool categorical split flag
    split_cat_words: jax.Array     # [L-1, 8] int32 left-set bin bitset
    # [3] int32: rows the fused TPU kernel's wave passes scanned, rows
    # they put through the dot, both in units of
    # hist_wave.COMPACT_TILE_UNIT rows, and the block-dots those rows
    # met (the (block, slot) pairs where the flush dots a slot at a
    # time, one a unit else), for this tree (zeros on every other
    # route). Not part of the model: pack_record leaves it out; the
    # stop check reads it beside num_leaves (models/gbdt.py).
    wave_work: jax.Array


@jax.jit
def pack_record(rec: TreeRecord) -> jax.Array:
    """Flatten a TreeRecord into ONE [P] float32 array.

    Device→host transfers in this environment have high fixed latency per
    buffer, so the host materializes trees from a single stacked download
    (``jnp.stack([pack_record(r) for r in recs])``) instead of 12 small
    transfers per tree. float32 holds counts/bins exactly below 2^24.
    """
    f32 = jnp.float32
    # cat words carry full 32-bit patterns: split into exact 16-bit
    # halves (f32 holds ints < 2^24 exactly; a raw int32 would round).
    # Counts are split the same way: leaf_count can reach N, and above
    # 2^24 rows a single f32 would round it.
    w = rec.split_cat_words.astype(jnp.uint32)
    w_lo = jnp.bitwise_and(w, jnp.uint32(0xFFFF)).astype(f32)
    w_hi = jnp.right_shift(w, jnp.uint32(16)).astype(f32)

    def cnt_split(c):
        # round before the int cast: counts are f32 sums of ones and
        # can sit at 99.99999 (cast alone would truncate to 99)
        ci = jnp.round(c).astype(jnp.uint32)
        return (jnp.bitwise_and(ci, jnp.uint32(0xFFFF)).astype(f32),
                jnp.right_shift(ci, jnp.uint32(16)).astype(f32))
    lc_lo, lc_hi = cnt_split(rec.leaf_count)
    ic_lo, ic_hi = cnt_split(rec.internal_count)
    return jnp.concatenate([
        rec.num_leaves[None].astype(f32) if rec.num_leaves.ndim == 0
        else rec.num_leaves.astype(f32),
        rec.split_leaf.astype(f32),
        rec.split_feature.astype(f32),
        rec.split_bin.astype(f32),
        rec.split_gain.astype(f32),
        rec.split_default_left.astype(f32),
        rec.leaf_output.astype(f32),
        lc_lo, lc_hi,
        rec.leaf_sum_g.astype(f32),
        rec.leaf_sum_h.astype(f32),
        rec.internal_value.astype(f32),
        ic_lo, ic_hi,
        rec.split_is_cat.astype(f32),
        w_lo.reshape(-1),
        w_hi.reshape(-1),
    ])


def unpack_record(arr, num_leaves_cap: int) -> dict:
    """Inverse of pack_record on a host numpy [P] row -> dict of arrays."""
    L = num_leaves_cap
    s = L - 1
    import numpy as _np

    def cnt_join(lo, hi):
        return (_np.asarray(lo).astype(_np.int64)
                + (_np.asarray(hi).astype(_np.int64) << 16)).astype(
                    _np.float64)
    parts = {}
    off = 0
    parts["num_leaves"] = int(round(float(arr[0]))); off = 1
    for name in ("split_leaf", "split_feature", "split_bin", "split_gain",
                 "split_default_left"):
        parts[name] = arr[off:off + s]; off += s
    parts["leaf_output"] = arr[off:off + L]; off += L
    lc_lo = arr[off:off + L]; off += L
    lc_hi = arr[off:off + L]; off += L
    parts["leaf_count"] = cnt_join(lc_lo, lc_hi)
    for name in ("leaf_sum_g", "leaf_sum_h"):
        parts[name] = arr[off:off + L]; off += L
    parts["internal_value"] = arr[off:off + s]; off += s
    ic_lo = arr[off:off + s]; off += s
    ic_hi = arr[off:off + s]; off += s
    parts["internal_count"] = cnt_join(ic_lo, ic_hi)
    parts["split_is_cat"] = arr[off:off + s] > 0.5; off += s
    w_lo = _np.asarray(arr[off:off + s * 8]).reshape(s, 8); off += s * 8
    w_hi = _np.asarray(arr[off:off + s * 8]).reshape(s, 8); off += s * 8
    parts["split_cat_words"] = (
        w_lo.astype(_np.int64)
        + (w_hi.astype(_np.int64) << 16)).astype(_np.uint32).astype(
            _np.int32)
    return parts


class _State(NamedTuple):
    leaf_ids: jax.Array
    hist: jax.Array            # [L, F, B, 3]
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
    # per-leaf aggregates
    leaf_output: jax.Array
    leaf_count: jax.Array
    leaf_sum_g: jax.Array
    leaf_sum_h: jax.Array
    leaf_depth: jax.Array
    # split records
    rec: TreeRecord


def make_tree_grower(cfg: GrowerConfig, meta: FeatureMeta,
                     hist_fn=None, split_fn=None, col_fn=None,
                     reduce_fn=None, jit=True):
    """NOTE: this legacy strict leaf-wise grower is the numerical-only
    correctness oracle (tests/test_wave_ops.py W=1 parity); it does not
    thread categorical splits, so the search is compiled out."""
    cfg = cfg._replace(hp=cfg.hp._replace(has_cat=False))
    """Build a ``grow(bins, grad, hess, sample_mask, feature_mask)``.

    Injection seams for the parallel learners (SURVEY §2.2):
      hist_fn(bins, w) -> [F_hist, B, 3]    histogram of one leaf's rows
        (data-parallel: local hist + psum; feature-parallel: local
        feature slice only; voting: local hist, election in split_fn)
      split_fn(hist, sg, sh, nd, fmask, can) -> SplitResult with GLOBAL
        feature indices (feature-parallel: cross-device argmax; voting:
        top-k vote + elected psum + argmax)
      col_fn(bins, feat) -> [N_local] bin column for a global feature id
      reduce_fn(x) -> global sum of a locally-summed scalar
        (data/voting-parallel: lax.psum over the data axis)

    All default to the serial single-device implementations. ``jit=False``
    returns the raw traceable fn for wrapping in shard_map.
    """
    L = cfg.num_leaves
    B = cfg.num_bins
    hp = cfg.hp
    # device copies: numpy arrays can't be indexed by traced scalars
    meta = FeatureMeta(*[jnp.asarray(x) for x in meta])

    if hist_fn is None:
        def hist_fn(bins, w):
            return build_histogram(bins, w, num_bins=B, chunk=cfg.chunk)
    if split_fn is None:
        def split_fn(hist, sg, sh, nd, fmask, can):
            return find_best_split(hist, sg, sh, nd, fmask, meta, hp, can)
    if col_fn is None:
        def col_fn(bins, feat):
            return jnp.take(bins, feat, axis=1).astype(jnp.int32)
    if reduce_fn is None:
        def reduce_fn(x):
            return x

    def depth_ok(depth):
        if cfg.max_depth > 0:
            return depth < cfg.max_depth
        return jnp.bool_(True)

    def _store_split(state: _State, leaf, res: SplitResult):
        return state._replace(
            t_gain=state.t_gain.at[leaf].set(res.gain),
            t_feature=state.t_feature.at[leaf].set(res.feature),
            t_bin=state.t_bin.at[leaf].set(res.threshold_bin),
            t_default_left=state.t_default_left.at[leaf].set(res.default_left),
            t_left_output=state.t_left_output.at[leaf].set(res.left_output),
            t_right_output=state.t_right_output.at[leaf].set(res.right_output),
            t_left_count=state.t_left_count.at[leaf].set(res.left_count),
            t_right_count=state.t_right_count.at[leaf].set(res.right_count),
            t_left_sum_g=state.t_left_sum_g.at[leaf].set(res.left_sum_g),
            t_left_sum_h=state.t_left_sum_h.at[leaf].set(res.left_sum_h),
            t_right_sum_g=state.t_right_sum_g.at[leaf].set(res.right_sum_g),
            t_right_sum_h=state.t_right_sum_h.at[leaf].set(res.right_sum_h),
        )

    def grow(bins, grad, hess, sample_mask, feature_mask):
        """Grow one tree.

        bins: [N, F] int bins; grad/hess: [N] f32 (already weighted);
        sample_mask: [N] f32 0/1 bagging membership;
        feature_mask: [F] bool usable features this tree.
        Returns (TreeRecord, leaf_ids[N]).
        """
        n, F = bins.shape
        f32 = jnp.float32
        grad = grad.astype(f32) * sample_mask
        hess = hess.astype(f32) * sample_mask
        w = jnp.stack([grad, hess, sample_mask.astype(f32)], axis=-1)

        # root
        root_hist = hist_fn(bins, w)
        root_g = reduce_fn(jnp.sum(grad))
        root_h = reduce_fn(jnp.sum(hess))
        root_c = reduce_fn(jnp.sum(sample_mask))
        root_split = split_fn(root_hist, root_g, root_h, root_c,
                              feature_mask, depth_ok(jnp.int32(0)))
        F_h = root_hist.shape[0]   # features held in the histogram pool

        state = _State(
            leaf_ids=jnp.zeros(n, jnp.int32),
            hist=jnp.zeros((L, F_h, B, 3), f32).at[0].set(root_hist),
            t_gain=jnp.full(L, KMIN_SCORE, f32).at[0].set(root_split.gain),
            t_feature=jnp.zeros(L, jnp.int32).at[0].set(root_split.feature),
            t_bin=jnp.zeros(L, jnp.int32).at[0].set(root_split.threshold_bin),
            t_default_left=jnp.zeros(L, bool).at[0].set(root_split.default_left),
            t_left_output=jnp.zeros(L, f32).at[0].set(root_split.left_output),
            t_right_output=jnp.zeros(L, f32).at[0].set(root_split.right_output),
            t_left_count=jnp.zeros(L, f32).at[0].set(root_split.left_count),
            t_right_count=jnp.zeros(L, f32).at[0].set(root_split.right_count),
            t_left_sum_g=jnp.zeros(L, f32).at[0].set(root_split.left_sum_g),
            t_left_sum_h=jnp.zeros(L, f32).at[0].set(root_split.left_sum_h),
            t_right_sum_g=jnp.zeros(L, f32).at[0].set(root_split.right_sum_g),
            t_right_sum_h=jnp.zeros(L, f32).at[0].set(root_split.right_sum_h),
            leaf_output=jnp.zeros(L, f32),
            leaf_count=jnp.zeros(L, f32).at[0].set(root_c),
            leaf_sum_g=jnp.zeros(L, f32).at[0].set(root_g),
            leaf_sum_h=jnp.zeros(L, f32).at[0].set(root_h),
            leaf_depth=jnp.zeros(L, jnp.int32),
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
                wave_work=jnp.zeros(3, jnp.int32),
            ),
        )

        def body(i, state: _State):
            leaf = jnp.argmax(state.t_gain).astype(jnp.int32)
            gain = state.t_gain[leaf]
            can = gain > 0.0
            new = (i + 1).astype(jnp.int32)

            feat = state.t_feature[leaf]
            tbin = state.t_bin[leaf]
            dleft = state.t_default_left[leaf]
            bin_col = col_fn(bins, feat)
            leaf_ids = apply_split(
                state.leaf_ids, bin_col, leaf, new, tbin, dleft,
                meta.missing_type[feat], meta.default_bin[feat],
                meta.num_bin[feat], enabled=can)

            left_cnt = state.t_left_count[leaf]
            right_cnt = state.t_right_count[leaf]
            left_smaller = left_cnt <= right_cnt
            small_id = jnp.where(left_smaller, leaf, new)

            small_mask = (leaf_ids == small_id) & can
            w_small = w * small_mask[:, None].astype(f32)
            hist_small = hist_fn(bins, w_small)
            parent_hist = state.hist[leaf]
            hist_large = parent_hist - hist_small
            hist_left = jnp.where(left_smaller, hist_small, hist_large)
            hist_right = jnp.where(left_smaller, hist_large, hist_small)

            # child aggregates from the split record (leaf_splits.hpp:37)
            lg, lh = state.t_left_sum_g[leaf], state.t_left_sum_h[leaf]
            rg, rh = state.t_right_sum_g[leaf], state.t_right_sum_h[leaf]
            lo, ro = state.t_left_output[leaf], state.t_right_output[leaf]
            child_depth = state.leaf_depth[leaf] + 1

            # record the split
            rec = state.rec._replace(
                num_leaves=state.rec.num_leaves + can.astype(jnp.int32),
                split_leaf=state.rec.split_leaf.at[i].set(
                    jnp.where(can, leaf, -1)),
                split_feature=state.rec.split_feature.at[i].set(
                    jnp.where(can, feat, -1)),
                split_bin=state.rec.split_bin.at[i].set(tbin),
                split_gain=state.rec.split_gain.at[i].set(
                    jnp.where(can, gain, 0.0)),
                split_default_left=state.rec.split_default_left.at[i].set(dleft),
                internal_value=state.rec.internal_value.at[i].set(
                    calculate_leaf_output(
                        state.leaf_sum_g[leaf], state.leaf_sum_h[leaf],
                        hp.lambda_l1, hp.lambda_l2, hp.max_delta_step)),
                internal_count=state.rec.internal_count.at[i].set(
                    state.leaf_count[leaf]),
            )

            state = state._replace(
                leaf_ids=leaf_ids,
                hist=jnp.where(
                    can,
                    state.hist.at[leaf].set(hist_left).at[new].set(hist_right),
                    state.hist),
                leaf_output=jnp.where(
                    can,
                    state.leaf_output.at[leaf].set(lo).at[new].set(ro),
                    state.leaf_output),
                leaf_count=jnp.where(
                    can,
                    state.leaf_count.at[leaf].set(left_cnt).at[new].set(right_cnt),
                    state.leaf_count),
                leaf_sum_g=jnp.where(
                    can,
                    state.leaf_sum_g.at[leaf].set(lg).at[new].set(rg),
                    state.leaf_sum_g),
                leaf_sum_h=jnp.where(
                    can,
                    state.leaf_sum_h.at[leaf].set(lh).at[new].set(rh),
                    state.leaf_sum_h),
                leaf_depth=jnp.where(
                    can,
                    state.leaf_depth.at[leaf].set(child_depth)
                         .at[new].set(child_depth),
                    state.leaf_depth),
                rec=rec,
            )

            # child best splits
            can_l = can & depth_ok(child_depth)
            res_l = split_fn(hist_left, lg, lh, left_cnt, feature_mask, can_l)
            res_r = split_fn(hist_right, rg, rh, right_cnt, feature_mask, can_l)

            state = _store_split(state, leaf, SplitResult(
                *[jnp.where(can, a, b) for a, b in
                  zip(res_l, SplitResult(
                      gain=state.t_gain[leaf] * 0 + KMIN_SCORE,
                      feature=state.t_feature[leaf],
                      threshold_bin=state.t_bin[leaf],
                      default_left=state.t_default_left[leaf],
                      left_output=state.t_left_output[leaf],
                      right_output=state.t_right_output[leaf],
                      left_count=state.t_left_count[leaf],
                      right_count=state.t_right_count[leaf],
                      left_sum_g=state.t_left_sum_g[leaf],
                      left_sum_h=state.t_left_sum_h[leaf],
                      right_sum_g=state.t_right_sum_g[leaf],
                      right_sum_h=state.t_right_sum_h[leaf]))]))
            # note: when !can the leaf's gain is forced to -inf so the loop
            # terminates (all remaining gains <= 0 stay no-ops)
            res_r_guard = SplitResult(
                *[jnp.where(can, a, b) for a, b in
                  zip(res_r, SplitResult(
                      gain=jnp.asarray(KMIN_SCORE, f32),
                      feature=state.t_feature[new],
                      threshold_bin=state.t_bin[new],
                      default_left=state.t_default_left[new],
                      left_output=state.t_left_output[new],
                      right_output=state.t_right_output[new],
                      left_count=state.t_left_count[new],
                      right_count=state.t_right_count[new],
                      left_sum_g=state.t_left_sum_g[new],
                      left_sum_h=state.t_left_sum_h[new],
                      right_sum_g=state.t_right_sum_g[new],
                      right_sum_h=state.t_right_sum_h[new]))])
            state = _store_split(state, new, res_r_guard)
            return state

        state = jax.lax.fori_loop(0, L - 1, body, state)
        rec = state.rec._replace(
            leaf_output=state.leaf_output,
            leaf_count=state.leaf_count,
            leaf_sum_g=state.leaf_sum_g,
            leaf_sum_h=state.leaf_sum_h,
        )
        return rec, state.leaf_ids

    # jit-capture: ok(B, hp, meta, col_fn, hist_fn, reduce_fn,
    # split_fn, _store_split, depth_ok) — factory-scoped jit: every
    # capture derives from THIS factory call's cfg/meta/seam
    # callables, and callers cache per (booster, geometry); the
    # shared-step registry reaches this grower only through
    # build_train_step, whose geometry key covers cfg and meta.
    return jax.jit(grow) if jit else grow
