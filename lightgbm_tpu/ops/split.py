"""Vectorized best-split search over histograms.

TPU-native counterpart of FeatureHistogram::FindBestThreshold*
(reference: src/treelearner/feature_histogram.hpp:76-653). The reference
scans each feature's bins twice (right-to-left with missing-default-left,
left-to-right with missing-default-right); here both scans over every
feature are evaluated at once as cumulative sums + masked argmax — an
ideal XLA workload (no data-dependent control flow).

Semantics preserved from the reference:
- L1-thresholded leaf outputs and gains (ThresholdL1 /
  CalculateSplittedLeafOutput / GetLeafSplitGainGivenOutput,
  feature_histogram.hpp:442-504).
- kEpsilon hessian regularization on each accumulated side and
  ``sum_hessian + 2*kEpsilon`` at the parent (feature_histogram.hpp:76-80).
- Missing handling: two-direction scans when ``num_bin > 2`` and missing
  is not None; NaN bin excluded from accumulation (rides with the default
  side); zero(default)-bin skipped when missing type is Zero
  (feature_histogram.hpp:87-110,506-653).
- min_data_in_leaf / min_sum_hessian_in_leaf / min_gain_to_split gates and
  monotone-constraint zeroing (GetSplitGains, feature_histogram.hpp:458).
- Tie-breaking: the flattened argmax order reproduces the reference's
  scan order (feature-major; dir=-1 before dir=+1; within dir=-1 larger
  thresholds win, within dir=+1 smaller thresholds win).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

KEPSILON = 1e-15            # meta.h:38
KMIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

NCAT_WORDS = 8              # 256-bin bitset for categorical left-sets


class SplitParams(NamedTuple):
    """Static (per-training-run) split hyperparameters."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    # categorical search (feature_histogram.hpp:112-234)
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    min_data_per_group: float = 100.0
    # static trace-time gate: False compiles the categorical search out
    # entirely (set per-dataset; numerical-only runs pay nothing)
    has_cat: bool = True
    # count-proxy mode (tpu_count_proxy): the histogram count channel
    # carries per-bin LOWER BOUNDS, not exact counts. Both sides of the
    # min_data_in_leaf gate must then come from prefix/suffix sums of
    # the channel itself (a sum of lower bounds is a lower bound) —
    # deriving one side as num_data - other_side would turn an
    # under-estimate into an over-estimate and let min_data violations
    # through. Conservative: never under-prunes, may over-prune.
    count_lb: bool = False


class FeatureMeta(NamedTuple):
    """Per-feature bin metadata as device arrays (host numpy accepted)."""
    num_bin: jax.Array       # [F] int32
    missing_type: jax.Array  # [F] int32
    default_bin: jax.Array   # [F] int32
    monotone: jax.Array      # [F] int32 (-1, 0, +1)
    penalty: jax.Array       # [F] float32 (feature_contri; 1.0 default)
    # 1 = categorical (bin.h BinType); scalar-0 default broadcasts so
    # numerical-only constructors don't need the field
    is_cat: jax.Array = np.zeros((), np.int32)
    # EFB (io/efb.py): member feature -> bundle column + bin offset.
    # Scalar sentinel = identity (no bundling); shapes are trace-static
    # so the decode compiles away entirely when unbundled.
    bundle: jax.Array = np.zeros((), np.int32)
    offset: jax.Array = np.zeros((), np.int32)

    @classmethod
    def from_mappers(cls, mappers, monotone_constraints=None,
                     feature_contri=None) -> "FeatureMeta":
        f = len(mappers)
        mono = np.zeros(f, np.int32)
        if monotone_constraints:
            mono[:len(monotone_constraints)] = monotone_constraints
        pen = np.ones(f, np.float32)
        if feature_contri:
            pen[:len(feature_contri)] = feature_contri
        return cls(
            num_bin=np.array([m.num_bin for m in mappers], np.int32),
            missing_type=np.array([m.missing_type for m in mappers], np.int32),
            default_bin=np.array([m.default_bin for m in mappers], np.int32),
            monotone=mono,
            penalty=pen,
            is_cat=np.array([1 if m.bin_type == 1 else 0
                             for m in mappers], np.int32),
        )


class SplitResult(NamedTuple):
    """Best split for one leaf — all scalars except the categorical
    left-set bitset (SplitInfo analog, src/treelearner/split_info.hpp:17;
    cat_threshold split_info.hpp:28)."""
    gain: jax.Array
    feature: jax.Array
    threshold_bin: jax.Array
    default_left: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    left_count: jax.Array
    right_count: jax.Array
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    is_cat: jax.Array = np.zeros((), bool)
    # [NCAT_WORDS] int32 bitset over BIN ids: set bit = bin goes LEFT
    cat_words: jax.Array = np.zeros(NCAT_WORDS, np.int32)


def threshold_l1(s, l1):
    """ThresholdL1 (feature_histogram.hpp:442)."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def calculate_leaf_output(sum_g, sum_h, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:447)."""
    ret = -threshold_l1(sum_g, l1) / (sum_h + l2)
    if max_delta_step > 0.0:
        ret = jnp.clip(ret, -max_delta_step, max_delta_step)
    return ret


def leaf_split_gain_given_output(sum_g, sum_h, l1, l2, output):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp:500)."""
    sg_l1 = threshold_l1(sum_g, l1)
    return -(2.0 * sg_l1 * output + (sum_h + l2) * output * output)


def leaf_split_gain(sum_g, sum_h, l1, l2, max_delta_step):
    """GetLeafSplitGain (feature_histogram.hpp:495)."""
    out = calculate_leaf_output(sum_g, sum_h, l1, l2, max_delta_step)
    return leaf_split_gain_given_output(sum_g, sum_h, l1, l2, out)


def _prefix_sums(contrib: jax.Array) -> jax.Array:
    """Inclusive prefix sums of ``contrib`` [F, B, C] along the bin
    axis as a lower-triangular matmul (prefix-sum = tril @ x): one MXU
    pass instead of a lane-shift cumsum chain.

    NOT pad-stable on every backend: a dot's accumulation order is the
    backend's choice, and XLA:CPU (jax 0.9) picks a different
    micro-kernel — a different K-axis grouping — as the operand shape
    changes, so zero-padding the bin axis (the step registry's pow2
    bin bucket) can move a prefix sum, and with it a leaf value, in
    its last bits (observed: B 36 -> 64; 63 -> 64 happens not to). The
    bound that holds is f32 rounding of a <= 256-term sum; see
    docs/Design.md §5d and
    tests/test_step_cache.py::test_geometry_bucketing_shares_across_data_shapes.
    Whether the MXU's accumulation is pad-stable is what chip_smoke.py's
    registry phase observes on the chip."""
    B = contrib.shape[1]
    tril = jnp.tril(jnp.ones((B, B), jnp.float32))
    return jnp.einsum("bk,fkc->fbc", tril, contrib,
                      precision=jax.lax.Precision.HIGHEST)


def _candidate_tables(hist: jax.Array, sum_g, sum_h, num_data,
                      feature_mask: jax.Array, meta: FeatureMeta,
                      hp: SplitParams, can_split=True):
    """Gain tables for every (feature, direction, threshold) candidate.

    Returns (g2, g1, min_gain_shift, ctx) where g2/g1 are the masked
    gain tables [F, B] for dir=-1 / dir=+1 and ctx carries the
    left-accumulation arrays needed to reconstruct a SplitResult.
    """
    f32 = jnp.float32
    F, B, _ = hist.shape
    nb = meta.num_bin.astype(jnp.int32)            # [F]
    mt = meta.missing_type.astype(jnp.int32)       # [F]
    db = meta.default_bin.astype(jnp.int32)        # [F]
    mono = meta.monotone.astype(jnp.int32)         # [F]

    l1 = f32(hp.lambda_l1)
    l2 = f32(hp.lambda_l2)
    mds = float(hp.max_delta_step)

    sum_g = jnp.asarray(sum_g, f32)
    sum_h2 = jnp.asarray(sum_h, f32) + 2.0 * KEPSILON   # hpp:80
    num_data = jnp.asarray(num_data, f32)

    gain_shift = leaf_split_gain(sum_g, sum_h2, l1, l2, mds)
    min_gain_shift = gain_shift + f32(hp.min_gain_to_split)

    bidx = jnp.arange(B, dtype=jnp.int32)[None, :]  # [1, B]
    nb_c = nb[:, None]
    two_scan = (nb > 2) & (mt != MISSING_NONE)      # [F]
    use_na = two_scan & (mt == MISSING_NAN)
    skip_db = two_scan & (mt == MISSING_ZERO)

    # --- contributions entering the cumulative scans --------------------
    valid_bin = bidx < nb_c
    zero_bin = (skip_db[:, None] & (bidx == db[:, None]))
    nan_bin = (use_na[:, None] & (bidx == nb_c - 1))
    contrib_mask = (valid_bin & ~zero_bin & ~nan_bin).astype(f32)  # [F, B]
    contrib = hist * contrib_mask[:, :, None]                      # [F, B, 3]

    cum = _prefix_sums(contrib)                     # [F, B, 3]
    tot = cum[:, -1, :]                             # [F, 3]

    # --- dir = +1 : left accumulates from bin 0 (default right) ---------
    l_g1 = cum[:, :, 0]
    l_h1 = cum[:, :, 1] + KEPSILON
    l_c1 = cum[:, :, 2]
    r_g1 = sum_g - l_g1
    r_h1 = sum_h2 - l_h1
    # count_lb: the right-side count must be the SUFFIX sum of the
    # (lower-bound) channel, not num_data - prefix (see SplitParams)
    r_c1 = (tot[:, None, 2] - l_c1) if hp.count_lb else num_data - l_c1
    valid1 = (two_scan[:, None]
              & (bidx <= nb_c - 2)
              & ~(skip_db[:, None] & (bidx == db[:, None])))

    # --- dir = -1 : right accumulates from the top (default left) ------
    r_g2 = tot[:, None, 0] - cum[:, :, 0]
    r_h2 = tot[:, None, 1] - cum[:, :, 1] + KEPSILON
    r_c2 = tot[:, None, 2] - cum[:, :, 2]
    l_g2 = sum_g - r_g2
    l_h2 = sum_h2 - r_h2
    l_c2 = cum[:, :, 2] if hp.count_lb else num_data - r_c2
    max_t2 = jnp.where(use_na, nb - 3, nb - 2)[:, None]  # dir=-1 can't emit nb-2
    valid2 = ((bidx <= max_t2)
              & (bidx >= 0)
              & ~(skip_db[:, None] & (bidx == db[:, None] - 1)))

    def side_gains(lg, lh, rg, rh):
        lo = calculate_leaf_output(lg, lh, l1, l2, mds)
        ro = calculate_leaf_output(rg, rh, l1, l2, mds)
        bad_mono = (((mono[:, None] > 0) & (lo > ro))
                    | ((mono[:, None] < 0) & (lo < ro)))
        g = (leaf_split_gain_given_output(lg, lh, l1, l2, lo)
             + leaf_split_gain_given_output(rg, rh, l1, l2, ro))
        return jnp.where(bad_mono, 0.0, g)

    def constraints(lc, lh, rc, rh):
        return ((lc >= hp.min_data_in_leaf) & (rc >= hp.min_data_in_leaf)
                & (lh >= hp.min_sum_hessian_in_leaf)
                & (rh >= hp.min_sum_hessian_in_leaf))

    gains1 = side_gains(l_g1, l_h1, r_g1, r_h1)
    ok1 = valid1 & constraints(l_c1, l_h1, r_c1, r_h1) & (gains1 > min_gain_shift)
    gains2 = side_gains(l_g2, l_h2, r_g2, r_h2)
    ok2 = valid2 & constraints(l_c2, l_h2, r_c2, r_h2) & (gains2 > min_gain_shift)

    ic = jnp.broadcast_to(jnp.asarray(meta.is_cat, jnp.int32), (F,)) > 0
    fmask = feature_mask[:, None] & can_split & ~ic[:, None]
    g1 = jnp.where(ok1 & fmask, gains1, KMIN_SCORE)
    g2 = jnp.where(ok2 & fmask, gains2, KMIN_SCORE)
    ctx = dict(l_g1=l_g1, l_h1=l_h1, l_c1=l_c1,
               l_g2=l_g2, l_h2=l_h2, l_c2=l_c2,
               sum_g=sum_g, sum_h2=sum_h2, num_data=num_data,
               two_scan=two_scan, mt=mt, l1=l1, l2=l2, mds=mds)
    return g2, g1, min_gain_shift, ctx


def _categorical_tables(hist: jax.Array, sum_g, sum_h2, num_data,
                        feature_mask, meta: FeatureMeta, hp: SplitParams,
                        can_split, min_gain_shift):
    """Categorical split candidates (FindBestThresholdCategorical,
    feature_histogram.hpp:112-234), fully vectorized.

    Returns (gc1, gc2, cat_ctx): gc1 = dir=+1 sorted-prefix gains (and
    the one-hot gains for small-cardinality features), gc2 = dir=-1,
    both [F, B] with -inf where invalid. A feature is one-hot when
    ``num_bin <= max_cat_to_onehot``; otherwise bins with
    ``count >= cat_smooth`` are sorted by g/(h + cat_smooth) and
    prefixes of up to ``max_cat_threshold`` bins are candidates, with
    ``min_data_per_group`` chunking between emitted candidates.
    """
    f32 = jnp.float32
    F, B, _ = hist.shape
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]
    nb = meta.num_bin.astype(jnp.int32)
    mt = meta.missing_type.astype(jnp.int32)
    ic = jnp.broadcast_to(jnp.asarray(meta.is_cat, jnp.int32), (F,)) > 0
    bidx = jnp.arange(B, dtype=jnp.int32)[None, :]

    l1 = f32(hp.lambda_l1)
    l2c = f32(hp.lambda_l2 + hp.cat_l2)
    l2n = f32(hp.lambda_l2)
    mds = float(hp.max_delta_step)
    mdl = f32(hp.min_data_in_leaf)
    msh = f32(hp.min_sum_hessian_in_leaf)
    mdpg = f32(hp.min_data_per_group)

    # candidate category bins (hpp:125-126: the trailing missing bin is
    # excluded unless the feature is "full" / MissingType::None)
    used_bin = nb - 1 + (mt == MISSING_NONE).astype(jnp.int32)  # [F]
    bin_ok = bidx < used_bin[:, None]

    def pair_gain(lg, lh, rg, rh, l2):
        return (leaf_split_gain(lg, lh, l1, l2, mds)
                + leaf_split_gain(rg, rh, l1, l2, mds))

    use_onehot = nb <= hp.max_cat_to_onehot                      # [F]
    fmask = feature_mask & can_split

    # ---- one-hot: left = single bin t (hpp:133-163, plain l2) ----
    lg_o, lh_o, lc_o = g, h + KEPSILON, c
    rg_o = sum_g - g
    rh_o = sum_h2 - lh_o
    rc_o = num_data - c
    gain_o = pair_gain(lg_o, lh_o, rg_o, rh_o, l2n)
    ok_o = (bin_ok & (c >= mdl) & (h >= msh) & (rc_o >= mdl)
            & (rh_o >= msh) & (gain_o > min_gain_shift)
            & ic[:, None] & use_onehot[:, None] & fmask[:, None])
    gain_o = jnp.where(ok_o, gain_o, KMIN_SCORE)

    # ---- sorted k-vs-rest (hpp:164-234, l2 + cat_l2) ----
    elig = bin_ok & (c >= f32(hp.cat_smooth))      # hpp:166 count gate
    ratio = g / (h + f32(hp.cat_smooth))
    ratio = jnp.where(elig, ratio, jnp.inf)        # ineligible sort last
    order = jnp.argsort(ratio, axis=1)             # [F, B]
    rank = jnp.argsort(order, axis=1)              # bin -> sorted pos
    used = jnp.sum(elig.astype(jnp.int32), axis=1)  # [F]
    pos = jnp.arange(B, dtype=jnp.int32)[None, :]
    in_use = pos < used[:, None]

    def sorted_of(x):
        return jnp.where(in_use, jnp.take_along_axis(x, order, axis=1),
                         0.0)
    gs, hs, cs = sorted_of(g), sorted_of(h), sorted_of(c)

    max_num_cat = jnp.minimum(hp.max_cat_threshold,
                              (used + 1) // 2)[:, None]          # [F,1]

    def direction(gd, hd, cd):
        """Candidates for one scan direction over pre-sorted arrays."""
        lg = jnp.cumsum(gd, axis=1)
        lh = jnp.cumsum(hd, axis=1) + KEPSILON
        lc = jnp.cumsum(cd, axis=1)
        rg = sum_g - lg
        rh = sum_h2 - lh
        rc = num_data - lc
        left_ok = (lc >= mdl) & (lh >= msh)
        # right-side failures BREAK the reference scan; both quantities
        # shrink monotonically with i, so the break is a prefix mask
        right_ok = (rc >= mdl) & (rc >= mdpg) & (rh >= msh)
        right_ok = jnp.cumprod(right_ok.astype(jnp.int32),
                               axis=1).astype(bool)
        # min_data_per_group chunking: accumulate counts, emit when the
        # current group reaches mdpg AND the left checks pass, reset on
        # emission (hpp:196-216)
        def step(cnt, xs):
            cn, lok = xs
            cnt = cnt + cn
            emit = lok & (cnt >= mdpg)
            return jnp.where(emit, 0.0, cnt), emit
        _, emits = jax.lax.scan(step, jnp.zeros(F, f32),
                                (cd.T, left_ok.T))
        emit = emits.T
        gain = pair_gain(lg, lh, rg, rh, l2c)
        ok = (emit & right_ok & in_use & (pos < max_num_cat)
              & (gain > min_gain_shift)
              & ic[:, None] & ~use_onehot[:, None] & fmask[:, None])
        return jnp.where(ok, gain, KMIN_SCORE), lg, lh, lc

    gain_p, lg_p, lh_p, lc_p = direction(gs, hs, cs)
    # dir=-1 scans from the LAST eligible position backwards: reverse
    # the eligible block (positions used-1..0). Reversing the masked
    # arrays then re-masking keeps ineligible tail at zero.
    def rev_use(x):
        full = jnp.take_along_axis(
            x, jnp.clip(used[:, None] - 1 - pos, 0, B - 1), axis=1)
        return jnp.where(in_use, full, 0.0)
    gain_m, lg_m, lh_m, lc_m = direction(rev_use(gs), rev_use(hs),
                                         rev_use(cs))

    # one-hot candidates ride the dir=+1 table (a feature is in exactly
    # one mode, so the slots never collide)
    gc1 = jnp.maximum(gain_p, gain_o)
    gc2 = gain_m
    ctx = dict(order=order, rank=rank, used=used, elig=elig,
               use_onehot=use_onehot,
               lg_o=lg_o, lh_o=lh_o, lc_o=lc_o,
               lg_p=lg_p, lh_p=lh_p, lc_p=lc_p,
               lg_m=lg_m, lh_m=lh_m, lc_m=lc_m, l2c=l2c, l2n=l2n)
    return gc1, gc2, ctx


def _cat_left_bitset(fi, t, is_p1, ctx, B):
    """Left-set bitset [NCAT_WORDS] for the winning categorical split."""
    onehot = ctx["use_onehot"][fi]
    rank = ctx["rank"][fi]                 # [B] bin -> sorted pos
    used = ctx["used"][fi]
    elig = ctx["elig"][fi]
    bidx = jnp.arange(B, dtype=jnp.int32)
    member_oh = bidx == t
    member_p1 = (rank <= t) & elig
    member_m1 = (rank >= used - 1 - t) & elig
    member = jnp.where(onehot, member_oh,
                       jnp.where(is_p1, member_p1, member_m1))
    word = bidx // 32
    bit = jnp.left_shift(jnp.uint32(1), (bidx % 32).astype(jnp.uint32))
    contrib = jnp.where(member, bit, jnp.uint32(0))
    words = jnp.zeros(NCAT_WORDS, jnp.uint32).at[word].add(
        contrib, mode="drop")
    return words.astype(jnp.int32)


def best_gain_per_feature(hist, sum_g, sum_h, num_data, feature_mask,
                          meta: FeatureMeta, hp: SplitParams,
                          can_split=True) -> jax.Array:
    """Per-feature best split gain [F] (-inf where no valid split) — the
    local-vote input of the voting-parallel learner
    (VotingParallelTreeLearner, voting_parallel_tree_learner.cpp:166)."""
    g2, g1, min_gain_shift, ctx = _candidate_tables(
        hist, sum_g, sum_h, num_data, feature_mask, meta, hp, can_split)
    best = jnp.maximum(g2.max(axis=1), g1.max(axis=1))
    if hp.has_cat:
        gc1, gc2, _ = _categorical_tables(
            hist, ctx["sum_g"], ctx["sum_h2"], ctx["num_data"],
            feature_mask, meta, hp, can_split, min_gain_shift)
        best = jnp.maximum(best,
                           jnp.maximum(gc1.max(axis=1), gc2.max(axis=1)))
    return jnp.where(jnp.isfinite(best),
                     (best - min_gain_shift) * meta.penalty, KMIN_SCORE)


def find_best_split(hist: jax.Array, sum_g, sum_h, num_data,
                    feature_mask: jax.Array, meta: FeatureMeta,
                    hp: SplitParams, can_split=True) -> SplitResult:
    """Find the best (feature, threshold, direction) for one leaf.

    Args:
      hist: [F, B, 3] histogram (grad, hess, count).
      sum_g/sum_h/num_data: leaf totals (scalars; num_data = bagged count).
      feature_mask: [F] bool — usable features (feature_fraction sampling,
        trivial-feature exclusion).
      can_split: scalar bool gate (e.g. max_depth reached) — forces -inf gain.
    """
    F, B, _ = hist.shape
    g2, g1, min_gain_shift, ctx = _candidate_tables(
        hist, sum_g, sum_h, num_data, feature_mask, meta, hp, can_split)
    if hp.has_cat:
        gc1, gc2, cctx = _categorical_tables(
            hist, ctx["sum_g"], ctx["sum_h2"], ctx["num_data"],
            feature_mask, meta, hp, can_split, min_gain_shift)
        # flatten [F, 4, B]: numerical dir=-1 first with REVERSED
        # thresholds (so larger t wins ties), numerical dir=+1
        # ascending, then the categorical dir=+1 / dir=-1 candidate
        # tables (a feature is either numerical or categorical, so the
        # blocks never compete within one feature). argmax = first max.
        cand = jnp.stack([g2[:, ::-1], g1, gc1, gc2], axis=1)
        nbranch = 4
    else:
        # numerical-only: the 2-branch table of the original design
        # (half the argmax scan; the cat machinery is compiled out)
        cand = jnp.stack([g2[:, ::-1], g1], axis=1)
        cctx = None
        nbranch = 2
    flat = cand.reshape(-1)
    idx = jnp.argmax(flat)
    best_gain = flat[idx]
    fi = idx // (nbranch * B)
    rem = idx % (nbranch * B)
    d = rem // B                  # 0 num dir=-1, 1 num dir=+1, 2/3 cat
    tb = rem % B
    t = jnp.where(d == 0, B - 1 - tb, tb)            # undo reversal

    is_dir2 = d == 0
    is_cat = d >= 2
    cat_p1 = d == 2
    lg = jnp.where(is_dir2, ctx["l_g2"][fi, t], ctx["l_g1"][fi, t])
    lh = jnp.where(is_dir2, ctx["l_h2"][fi, t], ctx["l_h1"][fi, t])
    lc = jnp.where(is_dir2, ctx["l_c2"][fi, t], ctx["l_c1"][fi, t])
    sum_g = ctx["sum_g"]
    sum_h2 = ctx["sum_h2"]
    l1, l2, mds = ctx["l1"], ctx["l2"], ctx["mds"]
    l2_eff = l2
    if hp.has_cat:
        # categorical left sums: one-hot rides the dir=+1 slot
        onehot = cctx["use_onehot"][fi]
        lg_c = jnp.where(cat_p1,
                         jnp.where(onehot, cctx["lg_o"][fi, t],
                                   cctx["lg_p"][fi, t]),
                         cctx["lg_m"][fi, t])
        lh_c = jnp.where(cat_p1,
                         jnp.where(onehot, cctx["lh_o"][fi, t],
                                   cctx["lh_p"][fi, t]),
                         cctx["lh_m"][fi, t])
        lc_c = jnp.where(cat_p1,
                         jnp.where(onehot, cctx["lc_o"][fi, t],
                                   cctx["lc_p"][fi, t]),
                         cctx["lc_m"][fi, t])
        lg = jnp.where(is_cat, lg_c, lg)
        lh = jnp.where(is_cat, lh_c, lh)
        lc = jnp.where(is_cat, lc_c, lc)
        # categorical sorted mode uses l2 + cat_l2 (hpp:233-246)
        l2_eff = jnp.where(is_cat & ~onehot, cctx["l2c"], l2)
        cat_words = _cat_left_bitset(fi, t, cat_p1, cctx, B)
    else:
        cat_words = jnp.zeros(NCAT_WORDS, jnp.int32)
    rg = sum_g - lg
    rh = sum_h2 - lh
    rc = ctx["num_data"] - lc

    # single-scan NaN edge: report default_left = False (hpp:103-106)
    single_nan = (~ctx["two_scan"][fi]) & (ctx["mt"][fi] == MISSING_NAN)
    default_left = is_dir2 & ~single_nan & ~is_cat

    has = jnp.isfinite(best_gain)
    out = SplitResult(
        gain=jnp.where(has, best_gain - min_gain_shift, KMIN_SCORE)
             * meta.penalty[fi],
        feature=jnp.where(has, fi, -1).astype(jnp.int32),
        threshold_bin=jnp.where(has, t, 0).astype(jnp.int32),
        default_left=default_left & has,
        left_output=calculate_leaf_output(lg, lh, l1, l2_eff, mds),
        right_output=calculate_leaf_output(rg, rh, l1, l2_eff, mds),
        left_count=lc,
        right_count=rc,
        left_sum_g=lg,
        left_sum_h=lh - KEPSILON,    # hpp: stores sum - kEpsilon
        right_sum_g=rg,
        right_sum_h=rh - KEPSILON,
        is_cat=is_cat & has,
        cat_words=jnp.where(is_cat & has, cat_words,
                            jnp.zeros(NCAT_WORDS, jnp.int32)),
    )
    return out
