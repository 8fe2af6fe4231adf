"""Boosting variants: GOSS, DART, RF.

TPU-native counterparts of the reference boosting subclasses
(reference: src/boosting/goss.hpp:26-216, src/boosting/dart.hpp:17-190,
src/boosting/rf.hpp:18-172, factory src/boosting/boosting.cpp:57-83).

Design notes vs the reference:

- GOSS runs entirely in-jit as a gradient-sample hook inside the fused
  training step (gbdt.py:_get_step_fn): the top-rate threshold is an
  exact device sort, the other-rate draw is i.i.d. Bernoulli with the
  same expected count as the reference's sequential exact-count sampler
  (goss.hpp:89-133) — a deliberate TPU-native substitution: the exact
  sampler is a sequential scan over rows, the Bernoulli draw is one
  fused elementwise pass.
- The DEFAULT Bernoulli stream (``tpu_goss_hash != 0``) is the
  shard-invariant lowbias32 hash of (global row index, per-tree salt)
  — the PR-4 bagging scheme: each row's draw depends only on its
  global index, never on the padded width or the mesh layout, and the
  real row count rides the traced ``rvalid`` mask. That makes hashed
  GOSS step-cache ELIGIBLE (ops/step_cache.py): sliding-window GOSS
  retrains hit the process-wide registry at 0 compile. The legacy
  positional-PRNG sampler (``tpu_goss_hash=0``) is kept verbatim as
  the parity/repro oracle and stays per-booster-jitted.
- DART keeps the reference's host-driven drop bookkeeping (tree weights,
  skip/max/uniform drop, normalization algebra dart.hpp:86-190) but all
  score adjustments replay device TreeRecords — no host transfer.
- RF replaces the base class's fused step with an averaging step
  (scores = running mean of tree outputs, rf.hpp:112-151) and fixed
  bagged targets (g = -label / one-hot, h = 1, rf.hpp:81-107).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.predict import add_leaf_outputs, replay_partition
from ..utils import log
from .gbdt import GBDT


def create_boosting(boosting_type: str) -> GBDT:
    """Boosting::CreateBoosting (boosting.cpp:57-83)."""
    return {"gbdt": GBDT, "goss": GOSS, "dart": DART, "rf": RF}[
        boosting_type]()


# stream-separation salt for the hashed GOSS draw: the step's PRNG
# seed also salts the grower's stochastic-rounding streams (salt and
# salt ^ 0x9E3779B9, ops/wave_grower.py), so GOSS xors a third
# constant to keep its uniform draws independent of the rounding
_GOSS_SALT = 0x27D4EB2F


class GOSS(GBDT):
    """Gradient-based One-Side Sampling (goss.hpp:26-216)."""

    # class default covers the legacy positional-PRNG oracle
    # (tpu_goss_hash=0): its jax.random.uniform stream depends on the
    # padded width, so bucket-padded it would not be bit-exact. The
    # hashed sampler flips the gate per-instance in init().
    _step_cache_ok = False

    def init(self, config, train_data, objective, training_metrics=()):
        # must precede super().init(): eligibility is snapshotted
        # during grower setup
        self._step_cache_ok = config.tpu_goss_hash != 0
        super().init(config, train_data, objective, training_metrics)
        self._reset_goss()

    def _sample_static_key(self):
        """Everything the hashed hook closes over (geometry-key
        component): the sampling rates. The legacy oracle never
        reaches the registry, so its closure ints don't ride here."""
        if self.config.tpu_goss_hash == 0:
            return ("goss_legacy",)
        return ("goss_hash", float(self.config.top_rate),
                float(self.config.other_rate))

    def _reset_goss(self):
        cfg = self.config
        if not (cfg.top_rate + cfg.other_rate <= 1.0):
            log.fatal("top_rate + other_rate cannot be larger than 1.0")
        if not (cfg.top_rate > 0.0 and cfg.other_rate > 0.0):
            log.fatal("top_rate and other_rate should be larger than 0")
        if cfg.bagging_freq > 0 and cfg.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        log.info("Using GOSS%s",
                 "" if cfg.tpu_goss_hash != 0 else " (legacy sampler)")
        self._hook_rng = np.random.default_rng(cfg.bagging_seed)
        # GOSS starts after 1/learning_rate warmup iterations
        # (goss.hpp:137-139); traced as a flag so the step doesn't
        # retrace when it switches on
        self._goss_warmup = int(1.0 / max(cfg.learning_rate, 1e-12))
        self._sample_hook = (self._hash_hook() if cfg.tpu_goss_hash != 0
                             else self._legacy_hook())
        self._step_key = None

    def _hash_hook(self):
        """The shard-invariant sampler: top-gradient threshold from an
        exact device sort over VALID rows, uniform-rest draw from the
        lowbias32 hash of (global row index, per-tree salt). Closes
        only over the two rates (covered by _sample_static_key), so
        the hook rides the process-wide shared step; the real row
        count, threshold index and amplification factor are all TRACED
        from ``rvalid`` — boosters with different N share one compiled
        step."""
        from ..ops.wave_grower import _hash_uniform
        top_rate = float(self.config.top_rate)
        other_rate = float(self.config.other_rate)

        def hook(g_all, h_all, mask, key, rvalid):
            # PRNGKey stores the seed in word 1 (word 0 is the high
            # half, zero for any sub-2^32 seed); the warmup dummy is
            # PRNGKey(0) and real seeds are drawn from [1, 2^31)
            on = key[1] != jnp.uint32(0)
            score = jnp.sum(jnp.abs(g_all * h_all), axis=0)  # [width]
            width = score.shape[0]
            if rvalid is None:
                # legacy routing (tpu_step_cache=0): exact row shapes,
                # every row real
                nf = jnp.float32(width)
                score_v = score
            else:
                nf = jnp.sum(rvalid.astype(jnp.float32))
                # pad rows sort to the bottom and never enter the top
                # set (real scores are >= 0)
                score_v = jnp.where(rvalid, score, -1.0)
            top_k = jnp.maximum(jnp.floor(nf * jnp.float32(top_rate)),
                                1.0)
            other_k = jnp.maximum(
                jnp.floor(nf * jnp.float32(other_rate)), 1.0)
            multiply = (nf - top_k) / other_k
            sorted_desc = -jnp.sort(-score_v)
            thr = jnp.take(sorted_desc, top_k.astype(jnp.int32) - 1)
            is_top = score_v >= thr
            p = other_k / jnp.maximum(nf - top_k, 1.0)
            u = _hash_uniform(jnp.arange(width, dtype=jnp.uint32),
                              key[1] ^ jnp.uint32(_GOSS_SALT))
            sampled = (u < p) & ~is_top
            if rvalid is not None:
                sampled = sampled & rvalid
            amp = jnp.where(sampled, multiply, 1.0)
            keep = (is_top | sampled).astype(jnp.float32)
            keep = jnp.where(on, keep, 1.0)
            amp = jnp.where(on, amp, 1.0)
            # tail = alignment pad + any valid-set passenger rows; its
            # mask is already zero, keep it that way
            tail = mask.shape[0] - width
            if tail:
                keep = jnp.concatenate(
                    [keep, jnp.zeros(tail, jnp.float32)])
            return g_all * amp, h_all * amp, mask * keep
        return hook

    def _legacy_hook(self):
        """The pre-hash positional-PRNG sampler, kept VERBATIM as the
        parity/repro oracle (tpu_goss_hash=0): its uniform stream is
        positional (padded-width dependent) and its count scalars are
        closure ints, so it stays per-booster-jitted and step-cache
        ineligible."""
        cfg = self.config
        n = self._n
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        multiply = (n - top_k) / other_k

        def hook(g_all, h_all, mask, key, rvalid=None):
            on = key[1] != jnp.uint32(0)
            score = jnp.sum(jnp.abs(g_all * h_all), axis=0)   # [N]
            thr = jax.lax.top_k(score, top_k)[0][-1]
            is_top = score >= thr
            p = other_k / max(n - top_k, 1)
            sampled = (jax.random.uniform(key, (n,)) < p) & ~is_top
            amp = jnp.where(sampled, jnp.float32(multiply), 1.0)
            keep = (is_top | sampled).astype(jnp.float32)
            keep = jnp.where(on, keep, 1.0)
            amp = jnp.where(on, amp, 1.0)
            # tail = alignment pad + any valid-set passenger rows; its
            # mask is already zero, keep it that way (read off the
            # traced mask shape so added valid sets retrace correctly)
            tail = mask.shape[0] - n
            if tail:
                keep = jnp.concatenate(
                    [keep, jnp.zeros(tail, jnp.float32)])
            g_all = g_all * amp
            h_all = h_all * amp
            return g_all, h_all, mask * keep
        return hook

    def train_one_iter(self, grad=None, hess=None):
        # during warmup, signal the hook off through a zeroed key
        if self.iter_ < self._goss_warmup:
            rng_state = self._hook_rng
            self._hook_rng = _ZeroKeyRng()
            try:
                return super().train_one_iter(grad, hess)
            finally:
                self._hook_rng = rng_state
        return super().train_one_iter(grad, hess)


class _ZeroKeyRng:
    """Stands in for the GOSS RNG during warmup: a zero key tells the
    in-jit hook to pass gradients through unsampled."""

    def integers(self, *_args, **_kw):
        return 0


class DART(GBDT):
    """Dropouts meet Multiple Additive Regression Trees
    (dart.hpp:17-190)."""

    def init(self, config, train_data, objective, training_metrics=()):
        super().init(config, train_data, objective, training_metrics)
        self._drop_rng = np.random.default_rng(config.drop_seed)
        self._tree_weight = []          # per iteration (uniform_drop off)
        self._sum_weight = 0.0
        self._drop_index = []

    def train_one_iter(self, grad=None, hess=None):
        """TrainOneIter (dart.hpp:52-66): drop, train on adjusted
        scores, normalize."""
        self._dropping_trees()
        ret = super().train_one_iter(grad, hess)
        if ret:
            return ret
        self._normalize()
        if not self.config.uniform_drop:
            self._tree_weight.append(self.shrinkage_rate)
            self._sum_weight += self.shrinkage_rate
        return False

    def _select_drops(self):
        cfg = self.config
        drops = []
        if self._drop_rng.random() < cfg.skip_drop:
            return drops
        drop_rate = cfg.drop_rate
        if not cfg.uniform_drop:
            if self._sum_weight <= 0:
                return drops
            inv_avg = len(self._tree_weight) / self._sum_weight
            if cfg.max_drop > 0:
                drop_rate = min(drop_rate,
                                cfg.max_drop * inv_avg / self._sum_weight)
            for i in range(self.iter_):
                if self._drop_rng.random() < \
                        drop_rate * self._tree_weight[i] * inv_avg:
                    drops.append(i)
                    if len(drops) >= cfg.max_drop > 0:
                        break
        else:
            if cfg.max_drop > 0 and self.iter_ > 0:
                drop_rate = min(drop_rate, cfg.max_drop / self.iter_)
            for i in range(self.iter_):
                if self._drop_rng.random() < drop_rate:
                    drops.append(i)
                    if len(drops) >= cfg.max_drop > 0:
                        break
        return drops

    def _dropping_trees(self):
        """DroppingTrees (dart.hpp:86-135): subtract the dropped trees
        from the train scores and lower the shrinkage for the new tree."""
        cfg = self.config
        self._drop_index = self._select_drops()
        K = self.num_tree_per_iteration
        if self._drop_index:
            # hoisted: the packed4 tier's nibble-unpack is a full-
            # matrix pass — one per drop round, not one per tree
            tb = self._train_bins_unpacked()
        for i in self._drop_index:
            for k in range(K):
                rec = self.records[i * K + k]
                leaf = replay_partition(rec, tb,
                                        self._meta)[:self._n_score]
                self._scores = self._scores.at[k].set(add_leaf_outputs(
                    self._scores[k], leaf, rec.leaf_output, -1.0,
                    **self._mesh_kw()))
        kdrop = len(self._drop_index)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + kdrop)
        else:
            self.shrinkage_rate = (
                cfg.learning_rate if kdrop == 0
                else cfg.learning_rate / (cfg.learning_rate + kdrop))

    def _normalize(self):
        """Normalize (dart.hpp:137-190): rescale dropped trees to
        k/(k+1) of their old weight and patch train/valid scores."""
        cfg = self.config
        kdrop = float(len(self._drop_index))
        if not self._drop_index:
            return
        K = self.num_tree_per_iteration
        tb = self._train_bins_unpacked()   # hoisted full-matrix unpack
        if not cfg.xgboost_dart_mode:
            keep_scale = kdrop / (kdrop + 1.0)    # final tree weight
            weight_sub = 1.0 / (kdrop + 1.0)      # dart.hpp:163
        else:
            # sr = lr/(lr+k): final weight k*sr/lr = k/(lr+k)
            keep_scale = kdrop * self.shrinkage_rate / cfg.learning_rate
            weight_sub = 1.0 / (kdrop + cfg.learning_rate)  # dart.hpp:181
        for i in self._drop_index:
            for k in range(K):
                t = i * K + k
                rec = self.records[t]
                old_out = rec.leaf_output
                # valid: had +old, now should have keep_scale*old
                for vi in range(len(self.valid_sets)):
                    vleaf = replay_partition(
                        rec, self._valid_bins_dev[vi], self._meta)
                    self._valid_scores[vi] = \
                        self._valid_scores[vi].at[k].set(add_leaf_outputs(
                            self._valid_scores[vi][k], vleaf, old_out,
                            keep_scale - 1.0, **self._mesh_kw()))
                # train: was subtracted fully, add back keep_scale*old
                leaf = replay_partition(rec, tb, self._meta)[:self._n_score]
                self._scores = self._scores.at[k].set(add_leaf_outputs(
                    self._scores[k], leaf, old_out, keep_scale,
                    **self._mesh_kw()))
                self.records[t] = rec._replace(
                    leaf_output=old_out * keep_scale,
                    internal_value=rec.internal_value * keep_scale)
                self.models[t] = None     # refresh host mirror lazily
            if not cfg.uniform_drop:
                self._sum_weight -= self._tree_weight[i] * weight_sub
                self._tree_weight[i] *= keep_scale


class RF(GBDT):
    """Random Forest (rf.hpp:18-172): bagged trees on fixed targets,
    averaged predictions."""

    # no shared-step reuse: RF replaces the base fused step with the
    # running-mean averaging step below (its own _get_step_fn)
    _step_cache_ok = False

    def __init__(self):
        super().__init__()
        self.average_output = True

    def init(self, config, train_data, objective, training_metrics=()):
        if not (config.bagging_freq > 0
                and 0.0 < config.bagging_fraction < 1.0):
            log.fatal("RF needs bagging_freq > 0 and bagging_fraction in "
                      "(0, 1)")
        super().init(config, train_data, objective, training_metrics)
        if train_data.metadata.init_score is not None:
            log.fatal("Cannot use init_score with RF")
        self.shrinkage_rate = 1.0
        self._rf_targets()

    def _rf_targets(self):
        """GetRFTargets (rf.hpp:81-107): fixed gradients from labels."""
        n, K = self._n, self.num_tree_per_iteration
        label = np.asarray(self._label_np, np.float32)
        g = np.zeros((K, n), np.float32)
        if K == 1:
            g[0] = -label
        else:
            g[label.astype(np.int64), np.arange(n)] = -1.0
        self._rf_g = jnp.asarray(g)
        self._rf_h = jnp.ones((K, n), jnp.float32)

    def boost_from_average(self, class_id):
        return 0.0

    def _get_step_fn(self, custom: bool):
        """RF step: same fused tree build, but scores are the RUNNING
        MEAN of tree outputs (MultiplyScore dance, rf.hpp:139-143) and
        the leaf outputs are renewed against a zero baseline."""
        key_id = ("rf", len(self._valid_bins_dev))
        if getattr(self, "_step_key", None) == key_id:
            return self._step_fn
        grower = self._grower
        K = self.num_tree_per_iteration
        n = self._n
        pad_rows = self._n_total - n
        valid_slices = tuple(self._valid_row_slices)
        meta = self._meta
        obj = self.objective
        L = self._grower_cfg.num_leaves
        renew = obj is not None and obj.is_renew_tree_output()
        if renew:
            from ..ops.renew import renew_leaf_outputs
            renew_label = jnp.asarray(
                obj.trans_label if hasattr(obj, "trans_label")
                else obj.label, jnp.float32)
            w = getattr(obj, "label_weight", None)
            if w is None:
                w = obj.weights
            renew_w = None if w is None else jnp.asarray(w, jnp.float32)
            renew_alpha = float(obj.renew_tree_output_percentile())

        def step(bins, scores, valid_scores, mask, fmask,
                 iter_f, init_bias, g_in, h_in, key):
            recs = []
            vs = list(valid_scores)
            for k in range(K):
                g_k, h_k = g_in[k], h_in[k]
                if pad_rows:
                    zpad = jnp.zeros(pad_rows, jnp.float32)
                    g_k = jnp.concatenate([g_k, zpad])
                    h_k = jnp.concatenate([h_k, zpad])
                rec, leaf_full = grower(bins, g_k, h_k, mask, fmask)
                leaf_ids = leaf_full[:n]
                if renew:
                    # baseline is zero scores (tmp_score_, rf.hpp:146)
                    new_out = renew_leaf_outputs(
                        leaf_ids, renew_label, renew_w, L, renew_alpha,
                        rec.leaf_output, mask[:n])
                    new_out = jnp.where(rec.num_leaves > 1, new_out,
                                        rec.leaf_output)
                    rec = rec._replace(leaf_output=new_out)
                grew = rec.num_leaves > 1
                # scores = (scores * it + tree_out) / (it + 1); skipped
                # entirely for splitless trees (rf.hpp:139-145)
                upd = (scores[k] * iter_f + rec.leaf_output[leaf_ids]) \
                    / (iter_f + 1.0)
                scores = scores.at[k].set(jnp.where(grew, upd, scores[k]))
                for vi, (voff, vn) in enumerate(valid_slices):
                    vleaf = leaf_full[voff:voff + vn]
                    vupd = (vs[vi][k] * iter_f
                            + rec.leaf_output[vleaf]) / (iter_f + 1.0)
                    vs[vi] = vs[vi].at[k].set(
                        jnp.where(grew, vupd, vs[vi][k]))
                recs.append(rec)
            return scores, tuple(vs), recs

        # jit-capture: ok(K, n, pad_rows, grower, renew, renew_label,
        # renew_w) — RF's averaging step is step-cache-INELIGIBLE by
        # design (CHANGES.md PR 5): this jit is per-booster, cached on
        # self._step_fn, and the captured aux arrays are this
        # booster's own — never registry-shared.
        self._step_fn = jax.jit(step, donate_argnums=(1, 2))
        self._step_key = key_id
        return self._step_fn

    def train_one_iter(self, grad=None, hess=None):
        """TrainOneIter (rf.hpp:112-151): fixed targets, averaged
        scores, never finishes on its own."""
        if grad is not None or hess is not None:
            log.fatal("RF does not support custom objectives")
        mask_np = self._bagging_mask(self.iter_)
        if mask_np is None:
            mask = self._full_mask_dev
        else:
            tail = self._n_total - self._n
            if tail:
                mask_np = np.concatenate(
                    [mask_np, np.zeros(tail, np.float32)])
            mask = jnp.asarray(mask_np)
        fmask = self._feature_mask_dev()
        step = self._get_step_fn(False)
        self._scores, new_valids, recs = step(
            self._bins_dev,
            self._scores, tuple(self._valid_scores), mask, fmask,
            jnp.float32(self.iter_), self._zero_bias, self._rf_g,
            self._rf_h, self._dummy_key)
        self._valid_scores = list(new_valids)
        for rec in recs:
            self.records.append(rec)
            self.models.append(None)
            self._tree_shrinkage.append(1.0)
        self.iter_ += 1
        self._bump_model_gen()
        # RF never stops on a splitless bag (rf.hpp TrainOneIter always
        # returns false): a degenerate bagging draw says nothing about
        # later draws, and splitless trees are harmless 1-leaf no-ops
        return False

    def finish_training(self):
        return

    def _effective_num_models(self):
        # splitless trees stay in an RF model (no trimming)
        return len(self.models)

    def rollback_one_iter(self):
        """RollbackOneIter (rf.hpp:153-166): un-average the last trees."""
        if self.iter_ <= 0:
            return
        K = self.num_tree_per_iteration
        it = self.iter_
        for k in range(K - 1, -1, -1):
            rec = self.records.pop()
            self.models.pop()
            self._tree_shrinkage.pop()
            if int(rec.num_leaves) > 1:
                leaf = replay_partition(rec, self._train_bins_unpacked(),
                                        self._meta)[:self._n]
                self._scores = self._scores.at[k].set(
                    (self._scores[k] * it
                     - rec.leaf_output[leaf]) / max(it - 1, 1))
                for vi in range(len(self.valid_sets)):
                    vleaf = replay_partition(
                        rec, self._valid_bins_dev[vi], self._meta)
                    self._valid_scores[vi] = \
                        self._valid_scores[vi].at[k].set(
                            (self._valid_scores[vi][k] * it
                             - rec.leaf_output[vleaf]) / max(it - 1, 1))
        self.iter_ -= 1
        self._clean_groups = min(self._clean_groups, self.iter_)
        self._stopped = False
        # rollback + retrain lands on the SAME (gen, len) without this
        # bump — the stacked-predictor fast path would serve the
        # rolled-back trees
        self._bump_model_gen()
