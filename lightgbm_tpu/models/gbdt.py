"""GBDT boosting engine.

TPU-native counterpart of the reference GBDT
(reference: src/boosting/gbdt.{h,cpp}: Init gbdt.cpp:47, TrainOneIter
gbdt.cpp:333-412, Bagging gbdt.cpp:182-243, UpdateScore gbdt.cpp:451,
EvalAndCheckEarlyStopping gbdt.cpp:432, model text
src/boosting/gbdt_model_text.cpp:240-540).

Design: scores, gradients, bagging masks and the per-tree growth all stay
on device; the host drives one jitted tree-build per (iteration, class)
and keeps lightweight python Tree mirrors for serialization/prediction on
raw features. Bagging uses a 0/1 device mask folded into the histogram
weights (equivalent to the reference's index-subset bagging — histograms,
counts and leaf sums see only bagged rows).

The training loop performs ZERO device→host transfers per iteration:
TreeRecords stay on device, host Tree mirrors are materialized lazily
from ONE packed stacked download (pack_record), and the reference's
"no more leaves to split" stop (gbdt.cpp:393-409) is detected by a
periodic check every ``tpu_stop_check_interval`` iterations plus
``finish_training()`` after the boosting loop; serialization
independently caps at the first splitless iteration so mid-training
checkpoints stay reference-equivalent. This matters doubly on an
accelerator: a device->host read waits behind every queued step, and
the reference's own GPU path had the same host-roundtrip problem
(gpu_tree_learner.cpp:891-1073 hides it with async copies; we remove
the transfers instead).
"""
from __future__ import annotations

import contextlib
import json
import threading
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import TpuDataset
from ..metrics import Metric
from ..obs import reqlog as obs_reqlog
from ..obs import scopes as obs_scopes
from ..obs import trace as obs_trace
from ..objectives import ObjectiveFunction
from ..ops.grower import pack_record, unpack_record
from ..ops.predict import add_leaf_outputs, replay_partition
from ..ops.split import SplitParams
from ..ops.wave_grower import WaveGrowerConfig
from ..utils import log, timing
from ..analysis import lockorder
from .tree import Tree, tree_from_record

K_MODEL_VERSION = "v2"     # gbdt.h kModelVersion


@jax.jit
def _tail_summary(num_leaves, wave_work):
    """[R, 1 + w] int32 rows (num_leaves, wave_work) of R records' scalars
    and [w] vectors (w = 3; 5 under a row-sharding learner), stacked on
    the device for one download."""
    with jax.named_scope("lgbm/stop_check"):
        return jnp.concatenate([jnp.stack(num_leaves)[:, None],
                                jnp.stack(wave_work)], axis=1)


class GBDT:
    """Gradient Boosting Decision Tree driver (boosting.h:22 interface)."""

    # gate for the compiled-step registry: variants whose step is not
    # a pure function of the shared geometry opt out — RF replaces the
    # step entirely; GOSS flips this per-INSTANCE (models/boosting.py):
    # the hashed sampler (tpu_goss_hash != 0) is pad/shard-invariant
    # and rides the shared step, the legacy positional-PRNG oracle
    # (tpu_goss_hash=0) keeps the per-booster closure
    _step_cache_ok = True

    def __init__(self):
        self.config: Optional[Config] = None
        self.train_data: Optional[TpuDataset] = None
        self.objective: Optional[ObjectiveFunction] = None
        # host trees, class-major order; None = not yet materialized from
        # the device record (lazily built, see _ensure_host_trees)
        self.models: List[Optional[Tree]] = []
        self.records: List = []                # device TreeRecords (same order)
        self._tree_shrinkage: List[float] = []  # per-tree file shrinkage
        self.iter_ = 0
        self.num_class = 1
        self.num_tree_per_iteration = 1
        self.shrinkage_rate = 0.1
        self.max_feature_idx = 0
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.average_output = False
        self.valid_sets: List[TpuDataset] = []
        self.valid_names: List[str] = []
        self.valid_metrics: List[List[Metric]] = []
        self.training_metrics: List[Metric] = []
        self.best_score: Dict = {}
        self.loaded_parameter = ""
        self._grower = None
        # boosting-variant hooks (models/boosting.py): an in-jit
        # gradient sampler (GOSS) and a per-iteration PRNG stream
        self._sample_hook = None
        self._hook_rng = None
        # serving-path state: the cached StackedModel, the exact tree
        # objects it stacked (identity-checked for incremental extend),
        # and the lock that keeps a predict() racing a retrain from
        # ever seeing a half-built predictor (RLock: _bump_model_gen
        # runs under it from paths _stacked_model may itself trigger)
        self._stacked_lock = lockorder.named_rlock(
            "gbdt._stacked_lock")
        self._stacked_cache = None        # guarded-by: _stacked_guard()
        self._stacked_ref: Optional[List] = None  # guarded-by: _stacked_guard()
        self._model_gen = 0               # guarded-by: _stacked_guard()

    # -- init (gbdt.cpp:47-117) --------------------------------------------

    def init(self, config: Config, train_data: TpuDataset,
             objective: Optional[ObjectiveFunction],
             training_metrics: Sequence[Metric] = ()):
        self.config = config
        self.train_data = train_data
        # kernel autotuner + persistent XLA compile cache: tile choices
        # come from the on-disk tuning cache (timed once per shape) and
        # repeated runs skip recompilation entirely (ops/autotune.py)
        from ..ops import autotune, step_cache
        autotune.configure(config.tpu_autotune,
                           config.tpu_tuning_cache or None)
        autotune.ensure_compile_cache(mode=config.tpu_compile_cache)
        # process-wide compiled-step registry (ops/step_cache.py):
        # eligible boosters share ONE jitted training step per geometry
        step_cache.configure(config.tpu_step_cache, config.tpu_row_bucket)
        # ... and its serving twin (ops/predict_cache.py): stacked
        # predict dispatch keyed by explicit geometry, online batches
        # padded to serve buckets
        from ..ops import predict_cache
        predict_cache.configure(config.tpu_predict_cache,
                                config.tpu_serve_bucket)
        # multi-host cluster (parallel/cluster.py): adopt an already-
        # initialized jax.distributed runtime (the elastic worker
        # bootstraps BEFORE dataset construction; embedders may too) so
        # the placement seams below know the mesh spans processes.
        # Single-process runs return immediately. This runs BEFORE the
        # obs daemons below: their rank-dependent decisions — export
        # path suffixing, the rank-0-only HTTP bind, trace/reqlog rank
        # stamping (obs/identity.py) — need the topology resolved
        from ..parallel import cluster
        cluster.initialize_from_config(config)
        # streaming telemetry (obs/): the span tracer and the live
        # metrics exporter are process-global daemons — the first
        # booster with the knobs set starts them, every later one
        # (each sliding window's fresh booster) joins
        from ..obs import export as obs_export
        from ..obs import flight as obs_flight
        from ..obs import slo as obs_slo
        obs_trace.ensure_from_config(config)
        obs_export.ensure_from_config(config)
        # serving observability (obs/): the request-scoped wide-event
        # log, the SLO/error-budget engine the exporter thread
        # evaluates, and the always-on flight recorder — same
        # first-starts, later-joins discipline as the daemons above
        obs_reqlog.ensure_from_config(config)
        obs_slo.ensure_from_config(config)
        obs_flight.ensure_from_config(config)
        # deterministic fault injection (utils/faults.py): the
        # tpu_faults knob arms the recovery drills' injection points
        from ..utils import faults
        faults.configure_from_config(config)
        self.objective = objective
        self.training_metrics = list(training_metrics)
        self.iter_ = 0
        self.num_class = config.num_class
        self.shrinkage_rate = config.learning_rate
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective else config.num_class)
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        self.feature_infos = train_data.feature_infos()

        n = train_data.num_data
        self._n = n
        self._meta = train_data.feature_meta()
        # fresh init: score buffers are rebuilt below, so _setup_grower
        # must not freeze shape decisions to a previous dataset's
        # (reset_parameter, which keeps the buffers, re-enters with
        # _scores live and DOES freeze them)
        self._scores = None
        self._setup_grower()
        # feature-major device layout [F, N] (ops/hist_wave.py); EFB
        # bundles share columns (io/efb.py)
        host_bins = (train_data.bundled_bins if self._use_bundles
                     else train_data.bins)
        dev_bins = (train_data.bins_t_dev
                    if host_bins is None and not self._use_bundles
                    else None)
        if dev_bins is not None:
            # streamed ingest (io/ingest.py): the bins are already
            # device-resident in the grower's [F, N] layout — pad and
            # nibble-pack on device; no host matrix ever existed. A
            # sharded ingest already carries (-n) % D zero-bin pad
            # columns; only the difference up to this learner's row
            # alignment is padded here (and surplus pad is sliced off
            # if a re-init changed the learner mode).
            bins_t = dev_bins
            ingest_pad = getattr(train_data, "bins_t_dev_pad", 0)
            extra = self._pad_rows - ingest_pad
            if extra > 0:
                if self._mesh is not None and ingest_pad:
                    # adoption missed (ingest's alignment guess vs the
                    # tuned chunk): one-time full-matrix re-layout
                    log.info("sharded ingest pad %d < grower pad %d: "
                             "re-padding the mesh-resident bins once "
                             "at init", ingest_pad, self._pad_rows)
                bins_t = jnp.pad(bins_t, ((0, 0), (0, extra)))
            elif extra < 0:
                bins_t = bins_t[:, :self._n + self._pad_rows]
            if self._pad_features:
                bins_t = jnp.pad(bins_t,
                                 ((0, self._pad_features), (0, 0)))
            self._num_bin_rows = bins_t.shape[0]
            if self._grower_cfg.packed4:
                bins_t = self._pack4_dev(bins_t)
        else:
            bins_t = np.ascontiguousarray(host_bins.T)
            if bins_t.dtype == np.uint16:
                # device kernels take uint8 or int32; the uint16 tier
                # only sizes host storage (io/dataset.py bin_dtype)
                bins_t = bins_t.astype(np.int32)
            if self._pad_rows:
                bins_t = np.pad(bins_t, ((0, 0), (0, self._pad_rows)))
            if self._pad_features:
                bins_t = np.pad(bins_t,
                                ((0, self._pad_features), (0, 0)))
            self._num_bin_rows = bins_t.shape[0]
            if self._grower_cfg.packed4:
                # 4-bit tier: two features per HBM byte (low nibble =
                # even feature). The grower's kernels unpack in VMEM;
                # every OTHER consumer of the training bins
                # (replay_partition in early-stop trimming, continued
                # training, refit) must go through
                # _train_bins_unpacked().
                bins_t = self._pack4_host(bins_t)
                log.info("4-bit packed bins: %.1f MB HBM "
                         "(vs %.1f MB unpacked)",
                         bins_t.nbytes / 1e6, 2 * bins_t.nbytes / 1e6)
        with timing.phase("init/upload_bins", mem_peak=True) as ph:
            # grower-facing matrix: train rows (+ alignment) with every
            # valid set's rows appended as weight-0 passengers (see
            # _rebuild_grower_bins); no valids yet at init. The train
            # part is always the first _train_width columns — kept as
            # a slice view, not a second resident copy. The watch
            # blocks at phase exit so upload/ingest device time is
            # attributed here, not to the first training iteration.
            # Sharded learners place the matrix under the mesh's
            # NamedSharding HERE, once — the jitted step then sees
            # inputs already laid out as its shard_map wants them and
            # never pays a per-iteration reshard.
            self._bins_dev = ph.watch(self._place_bins(bins_t))
        if isinstance(bins_t, np.ndarray):
            # host->device bulk upload (the streamed-ingest path never
            # builds a host matrix, so nothing to count there)
            from ..obs import registry as obs
            obs.counter("transfer/h2d_bins_bytes").add(int(bins_t.nbytes))
            obs.counter("transfer/h2d_uploads").add(1)
        self._train_width = bins_t.shape[1]
        # sparse histogram tier: device coordinate planes, bucketed so
        # same-geometry sparse boosters (the sliding-window pattern)
        # share one compiled step (ops/step_cache.py bucket_entries)
        self._sparse_dev = (self._build_sparse_planes()
                            if self._grower_cfg.sparse_hist else None)
        self._valid_row_slices: List[tuple] = []
        self._n_total = self._n + self._pad_rows
        self._full_mask_dev = self._place_rows(np.concatenate(
            [np.ones(self._n, np.float32),
             np.zeros(self._pad_rows, np.float32)]))
        self._init_scores()
        self._bagging_rng = np.random.default_rng(config.bagging_seed)
        self._feature_rng = np.random.default_rng(config.feature_fraction_seed)
        self._label_np = (train_data.metadata.label
                          if train_data.metadata.label is not None
                          else np.zeros(n, np.float32))
        self._valid_bins_dev: List[jax.Array] = []
        self._stop_check_interval = max(1, config.tpu_stop_check_interval)
        self._dispatch_sync_interval = config.tpu_dispatch_sync_interval
        self._stopped = False
        # per-run eval-value history ((iteration, dataset, metric,
        # value) tuples, global iteration numbering) — part of the
        # checkpoint bundle so a resumed run's bookkeeping matches the
        # uninterrupted run's (utils/checkpoint.py)
        self._eval_history: List[tuple] = []
        # number of leading iteration-groups already verified productive,
        # so each periodic stop check scans only the new tail
        self._clean_groups = 0
        # compile the stop check's one stacked download here, in set-up,
        # not an interval into training: it always stacks an interval's
        # records, a shorter tail padded (_tail_host). Under a mesh the
        # records leave the step replicated over it, so the pads are
        # placed the same way and this compile is the one a check uses
        self._tail_pad = tuple(
            self._place_step_raw(z) for z in (
                np.zeros((), np.int32), np.zeros(self._work_len, np.int32)))
        self._tail_host([])
        obs_scopes.watch("stop_check", _tail_summary, self._tail_args([]))
        # fused-step state (see _get_step_fn)
        self._step_key = None
        self._zero_bias = jnp.zeros(self.num_tree_per_iteration,
                                    jnp.float32)
        self._dummy_gh = jnp.zeros((1, 1), jnp.float32)
        self._dummy_key = jax.random.PRNGKey(0)
        self._fmask_cache = None
        # shared-step arguments (ops/step_cache.py): the row-validity
        # mask distinguishing real rows from bucket-pad rows, and the
        # per-booster aux pytree built lazily on first step build
        rv = np.zeros(self._n_score, bool)
        rv[:self._n] = True
        self._rvalid_dev = self._place_step_rows(rv)

    def _setup_grower(self):
        cfg = self.config
        hp = SplitParams(
            lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
            max_delta_step=cfg.max_delta_step,
            min_data_in_leaf=float(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=cfg.min_gain_to_split,
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            max_cat_threshold=cfg.max_cat_threshold,
            cat_l2=cfg.cat_l2, cat_smooth=cfg.cat_smooth,
            min_data_per_group=float(cfg.min_data_per_group),
            has_cat=any(m.bin_type == 1
                        for m in self.train_data.mappers))

        # distributed learner selection (tree_learner.cpp:9-33 analog):
        # tree_learner = serial|feature|data|voting over the device mesh
        from ..parallel.learners import (make_grower_for_mode,
                                         training_mesh)
        mode = cfg.tree_learner
        mesh = None
        if mode != "serial":
            # same policy sharded ingest used (learners.training_mesh),
            # so the bins are already under this exact mesh
            mesh = training_mesh(cfg)
            if mesh is None:
                # visible in learner_mode / num_devices / device_report()
                # and counted, so a multi-chip smoke fails on it
                from ..obs import registry as obs
                obs.counter("learner/serial_fallbacks").add(1)
                log.warning("tree_learner=%s requested but only one device"
                            " is available; falling back to serial", mode)
                mode = "serial"
        self._mesh = mesh
        self._learner_mode = mode
        D = mesh.devices.size if mesh is not None else 1
        from ..obs import registry as obs
        obs.gauge("comm/devices").set(float(D))
        # EFB rides the histogram seam (bundle columns in, member
        # histograms out) and the meta-driven partition decode, which
        # compose with the serial grower, the row-sharded data/voting
        # learners, AND feature-parallel (where the device slice is of
        # BUNDLE columns; each device expands its slice to its members'
        # histograms and the election runs on the usual global argmax).
        self._use_bundles = (self.train_data.bundles is not None
                             and mode in ("serial", "data", "voting",
                                          "feature"))

        f = max(self.train_data.num_features, 1)
        self._pad_rows = 0
        self._pad_features = 0
        # fresh per-feature metadata each entry: reset_parameter
        # re-enters this method, and re-padding an already-padded
        # self._meta would corrupt the pad (it also picks up
        # monotone/penalty changes from the new config)
        meta = self.train_data.feature_meta()
        self._meta = meta

        # wave size: leaves split per device step (ops/wave_grower.py);
        # 0 = auto. Capped by the Pallas channel budget AND kept a
        # multiple of 8: weight blocks concatenate on the sublane axis,
        # and misaligned 25-row pieces cost ~15x in relayout shuffles
        # (measured 1.7s vs 83ms per tree at 1M rows). hi/lo f32-grade
        # accumulation (tpu_use_dp) needs 5W <= 128 -> W = 24; single
        # bf16 fused needs 4W <= 128 -> W = 32.
        quant = cfg.tpu_quantized_hist
        # sparse histogram tier (config.tpu_sparse, io/sparse.py):
        # wave histograms scatter over the dataset's retained nnz
        # coordinates instead of the dense one-hot pass. Structural
        # gates here (serial learner, no EFB bundles, coordinates
        # present); the (density, exactness) rule is the autotuner's
        # (ops/autotune.py tune_hist_tier). Decided BEFORE the
        # count-proxy gate — the tiers are mutually exclusive.
        td_s = self.train_data
        sparse_tier = False
        if (getattr(td_s, "sparse_coords", None) is not None
                and mode == "serial" and not self._use_bundles):
            from ..ops.autotune import tune_hist_tier
            sparse_tier = tune_hist_tier(
                requested=cfg.tpu_sparse,
                density=td_s.sparse_density or 0.0,
                nnz=td_s.sparse_nnz,
                F=max(td_s.num_features, 1),
                B=max(td_s.max_bin_global, 2), W=0, quant=quant)
        elif (cfg.tpu_sparse == 1
              and getattr(td_s, "sparse_density", None) is not None):
            log.warning("tpu_sparse=1 needs the serial tree learner "
                        "without EFB bundles and a CSR-constructed "
                        "train set carrying coordinates; using the "
                        "dense histogram tier")
        if (getattr(self, "_scores", None) is not None
                and hasattr(self, "_grower_cfg")):
            # reset_parameter re-entry: the coordinate planes were
            # built (or not) at init — a flipped knob cannot
            # materialize them mid-life
            sparse_tier = self._grower_cfg.sparse_hist
        # count-proxy (see config.tpu_count_proxy): int8-only, needs the
        # fused kernel's default seams — serial/data modes, no EFB
        # bundles, no forced splits (voting reads LOCAL count sums in
        # its election, which proxy's global synthesis would corrupt)
        # (categorical excluded: _categorical_tables derives right-side
        # counts as num_data - left, which would turn the proxy's lower
        # bounds into over-estimates)
        proxy = (quant and mode in ("serial", "data")
                 and not self._use_bundles
                 and not cfg.forcedsplits_filename
                 and not hp.has_cat
                 and not sparse_tier
                 and cfg.tpu_count_proxy != 0)
        if cfg.tpu_count_proxy == 1 and not proxy:
            log.warning("tpu_count_proxy needs tpu_quantized_hist with "
                        "tree_learner serial/data, no EFB bundles, no "
                        "forced splits and no categorical features; "
                        "using exact counts")
        if proxy and cfg.tpu_count_proxy == -1:
            # auto-engaged (default -1): the mode changes tree structure
            # near the min_data_in_leaf gate (per-bin counts become
            # conservative lower bounds), so say so where a changed
            # model can be traced back to it
            log.info("tpu_count_proxy auto-enabled (int8 count-proxy "
                     "histograms, 64-leaf waves): per-bin counts are "
                     "conservative lower bounds for the "
                     "min_data_in_leaf gate; set tpu_count_proxy=0 for "
                     "exact counts")
        # 4-bit packed HBM bins: ride the proxy tier OR the hi/lo
        # exact tier (the kernels' nibble unpack is channel-layout
        # independent, so max_bin <= 16 datasets keep half-size HBM
        # bins under exact semantics too). Forced splits excluded —
        # the forced prefix reads unpacked bins (ops/wave_grower.py).
        packed4_exact = (not quant and cfg.tpu_use_dp
                         and mode in ("serial", "data")
                         and not self._use_bundles and not sparse_tier
                         and not cfg.forcedsplits_filename)
        packed4 = ((proxy or packed4_exact)
                   and self.train_data.max_bin_global <= 16
                   and cfg.tpu_packed_bins != 0)
        exact_variant = "hilo5"
        if quant and proxy:
            precision, w_cap = "int8", 64    # 2ch (count-proxy) cap 64
            hp = hp._replace(count_lb=True)  # conservative min_data gate
        elif quant:
            precision, w_cap = "int8", 40    # 3ch cap 42, 8-aligned 40
        elif cfg.tpu_use_dp:
            # exact tier: the hi/lo channel layout (and with it the
            # wave-width cap — passes per tree) is an autotuned choice
            # per (F, B, device) among the bit-equivalent variants of
            # ops/hist_wave.py (tune_exact_tier). Reduced-channel
            # layouts need the default kernel seams, so feature/voting
            # learners, EFB bundles and the sparse tier keep "hilo5".
            # "hilo3" fuses the hess plane with the count plane, which
            # is only sound when hessians are identically 1 and rows
            # unweighted (the L1/L2 family without weights; GOSS
            # amplifies hessians, custom gradients are unknowable) —
            # see the train_one_iter guard for the custom-grad corner.
            precision = "highest"
            if (mode in ("serial", "data") and not self._use_bundles
                    and not sparse_tier):
                from ..ops.autotune import (EXACT_TIER_CAPS,
                                            tune_exact_tier)
                obj = self.objective
                const_h = bool(
                    obj is not None
                    and getattr(obj, "is_constant_hessian", False)
                    and cfg.boosting_type() == "gbdt")
                td_e = self.train_data
                host_b = td_e.bins
                exact_variant = tune_exact_tier(
                    F=max(td_e.num_features, 1),
                    B=max(td_e.max_bin_global, 2),
                    n_rows=self._n,
                    constant_hessian=const_h,
                    any_cat=bool(hp.has_cat),
                    bins_bytes=(1 if (host_b.dtype == np.uint8
                                      if host_b is not None
                                      else td_e.max_bin_global <= 256)
                                else 4),
                    requested=cfg.tpu_exact_tier)
                w_cap = EXACT_TIER_CAPS[exact_variant]
            else:
                w_cap = 24
        else:
            precision, w_cap = "default", 32
        W = cfg.tpu_wave_size or w_cap
        if W > w_cap:
            log.warning("tpu_wave_size=%d exceeds the Pallas lane cap for "
                        "this precision; clamping to %d", W, w_cap)
        W = max(1, min(W, w_cap, max(cfg.num_leaves, 2) - 1))

        # effective Pallas row chunk (must match the WaveGrowerConfig
        # chunk below): rows are padded to a chunk multiple so the wave
        # kernels never re-pad the [F, N] bins — an XLA pad there is a
        # full-matrix copy per wave pass (~1 ms at the HIGGS shape,
        # x11 passes/iter). tpu_hist_chunk=0 routes the choice through
        # the kernel autotuner (ops/autotune.py): first encounter of
        # this (kernel, features, bins, tier, device) shape times a
        # small VMEM-feasible candidate set and persists the winner;
        # off-TPU the measured per-tier default is used untouched.
        # compiled-step registry eligibility decides shape policy from
        # here on: eligible boosters pad the histogram bin axis to a
        # power-of-two bucket (step_cache.bucket_bins) so boosters whose
        # OBSERVED max bin counts differ — every sliding window of the
        # lrb.py workload — still share one compiled step. Padded
        # columns are inert: no bin value reaches them and the split
        # finder masks per-feature via the traced meta.num_bin.
        from ..ops import step_cache
        prev_elig = getattr(self, "_cache_eligible", None)
        self._cache_eligible = self._step_cache_eligible(mode)
        if (prev_elig is not None
                and getattr(self, "_scores", None) is not None):
            # mid-life reset_parameter cannot switch step
            # implementations: the score/bins widths are frozen to the
            # live device buffers below, and the legacy closure cannot
            # consume a bucketed width (nor the shared step an exact
            # one) — a flipped knob only affects future boosters
            self._cache_eligible = prev_elig
        from ..obs import registry as obs
        # eligibility split by objective family: which production
        # workloads actually ride the registry vs fall back to the
        # per-booster closure (run reports + Prometheus export)
        family = (self.objective.name if self.objective is not None
                  else "none")
        verdict = "eligible" if self._cache_eligible else "ineligible"
        # bounded-cardinality: family is an in-tree objective class
        # name ("none" for custom-gradient boosters), verdict one of
        # eligible/ineligible
        obs.counter(f"step_cache/{verdict}/{family}").add(1)
        B_hist = max(self.train_data.max_bin_global, 2)
        if self._cache_eligible:
            B_hist = step_cache.bucket_bins(B_hist, cfg.tpu_row_bucket)
            # the FEATURE axis is data-dependent too (the dataset
            # excludes trivial columns, so a 53-column window sample
            # can surface 51 features and the next 52): bucket F to a
            # multiple of 8 with trivial pad features — num_bin=1
            # yields zero split candidates and the fmask pads False,
            # exactly the feature-parallel mode's proven pad scheme
            self._pad_features = (-f) % 8
        if cfg.tpu_hist_chunk > 0:
            kchunk = cfg.tpu_hist_chunk
        else:
            from ..ops import autotune
            td = self.train_data
            bundled = self._use_bundles
            host_bins = td.bundled_bins if bundled else td.bins
            kchunk = autotune.tune_hist_chunk(
                # fused-kernel eligibility mirrors wave_grower's
                # default-seams rule: serial/data without bundles
                fused=not bundled and mode in ("serial", "data"),
                F=(len(td.bundles) if bundled
                   else max(td.num_features, 1) + self._pad_features),
                B=(max(td.bundle_width, 2) if bundled else B_hist),
                W=W, precision=precision, count_proxy=proxy,
                packed4=packed4, any_cat=bool(hp.has_cat),
                variant=exact_variant,
                bins_bytes=(1 if (host_bins.dtype == np.uint8
                                  if host_bins is not None
                                  else td.max_bin_global <= 256)
                            else 4),
                # per-device rows: only data/voting shard rows across
                # the mesh (rounded UP — padding below aligns shards
                # to a chunk multiple, and the int8 overflow filter
                # must see the padded worst case, not floor(n/D));
                # serial and feature-parallel kernels see every row
                n_rows=(-(-self._n // D) if mode in ("data", "voting")
                        else self._n))
        if mode in ("data", "voting"):
            self._pad_rows = (-self._n) % D
            if self._n >= 4 * D * kchunk:
                # large shards: chunk-align each shard's rows too (the
                # per-shard fused kernel re-pads otherwise); small test
                # datasets skip this (padding would dwarf the data)
                self._pad_rows = (-self._n) % (D * kchunk)
            ing = getattr(self.train_data, "bins_t_dev_pad", 0)
            if ing > self._pad_rows:
                unit = step_cache.shard_align_unit(self._n, D, kchunk)
                if (self._n + ing) % unit == 0:
                    # sharded ingest already padded wider (32k-aligned
                    # shards) AND its width satisfies this learner's
                    # alignment — adopt it wholesale: the matrix is
                    # mesh-resident at that width, and re-padding
                    # would reshard every shard boundary
                    self._pad_rows = ing
        elif mode == "serial":
            from ..utils.device import on_tpu
            if on_tpu():
                # the Pallas kernels pad rows to a chunk multiple
                # internally — aligning up front avoids the per-step
                # re-pad
                self._pad_rows = (-self._n) % kchunk
        # alignment unit the row padding above respects — the bucketed
        # score width must stay a multiple of it (even shards for the
        # data/voting learners, chunk-aligned rows for the accelerator
        # kernels)
        if mode in ("data", "voting"):
            unit = step_cache.shard_align_unit(self._n, D, kchunk)
        elif mode == "serial":
            from ..utils.device import on_tpu
            unit = kchunk if on_tpu() else 1
        else:
            unit = 1
        self._row_align_unit = unit
        # compiled-step registry (ops/step_cache.py): eligible boosters
        # bucket the score-block width so boosters whose row counts
        # land in the same bucket share ONE compiled step; the bins
        # matrix widens to at least that width. Ineligible
        # configurations keep exact shapes (n_score == n), as does the
        # f32 data-parallel learner: bucketing moves the row->shard
        # boundaries, which regroups the f32 histogram/root psums and
        # drifts the last bit — the quantized path's integer wire is
        # grouping-invariant, so it buckets freely. Exact-shape cached
        # boosters still share steps between same-N runs.
        prev_ns = getattr(self, "_n_score", None)
        self._n_score = self._n
        if self._cache_eligible and (mode == "serial" or quant):
            ns = step_cache.bucket_rows(self._n, unit,
                                        cfg.tpu_row_bucket)
            local = ns // (D if mode in ("data", "voting") else 1)
            if quant and 127 * local >= 2 ** 31:
                # bucket pad would push the padded shard past the int8
                # kernels' int32 histogram-sum bound: keep exact shapes
                # (the registry still shares between same-N boosters)
                ns = self._n
            self._n_score = max(ns, self._n)
        if prev_ns is not None and getattr(self, "_scores",
                                           None) is not None:
            # reset_parameter re-entry: the score/rvalid widths were
            # allocated at init and are frozen — a changed bucket
            # decision must not orphan the live buffers
            self._n_score = prev_ns
        self._pad_rows = max(self._pad_rows,
                             self._n_score - self._n)
        if mode == "feature" and not self._use_bundles:
            self._pad_features = (-f) % D
        if (prev_ns is not None
                and getattr(self, "_scores", None) is not None
                and getattr(self, "_f_pad", None) is not None):
            # re-entry: the [F_pad, N] bins matrix is device-resident
            # at the width chosen at init — a changed pad decision
            # (e.g. reset_parameter flipping a step-cache knob) must
            # not orphan it
            self._pad_features = self._f_pad - f
        if self._pad_features:
            pad = self._pad_features
            meta = type(meta)(
                num_bin=np.concatenate(
                    [meta.num_bin, np.ones(pad, np.int32)]),
                missing_type=np.concatenate(
                    [meta.missing_type, np.zeros(pad, np.int32)]),
                default_bin=np.concatenate(
                    [meta.default_bin, np.zeros(pad, np.int32)]),
                monotone=np.concatenate(
                    [meta.monotone, np.zeros(pad, np.int32)]),
                penalty=np.concatenate(
                    [meta.penalty, np.ones(pad, np.float32)]),
                is_cat=np.concatenate(
                    [np.broadcast_to(np.asarray(meta.is_cat,
                                                np.int32), (f,)),
                     np.zeros(pad, np.int32)]))
            self._meta = meta
        self._n_pad = self._n + self._pad_rows
        self._f_pad = f + self._pad_features

        # quantized histogram reduction (tpu_quantized_psum): on the
        # data-parallel path the wave-histogram psum carries the RAW
        # int32 quantized representation and dequantizes after the
        # collective — exact integer addition on the wire and, with the
        # count-proxy tier, a 2-channel payload. Needs the default
        # seams (no EFB hist_fn) and global scales (already pmax'd);
        # the int-vs-f32 wire choice is autotuned on real meshes
        # (ops/autotune.py tune_hist_psum).
        quant_psum = False
        if (quant and mode == "data" and mesh is not None
                and not self._use_bundles):
            from ..ops.autotune import tune_hist_psum
            quant_psum = tune_hist_psum(
                # the PADDED axes: that is the [W, F, B, C] block the
                # psum actually carries (F pads to /8 when eligible)
                mesh=mesh, W=W, F=self._f_pad,
                B=B_hist,
                channels=2 if proxy else 3,
                n_rows_global=self._n_pad,
                requested=cfg.tpu_quantized_psum)
        elif cfg.tpu_quantized_psum == 1:
            log.warning("tpu_quantized_psum=1 needs tpu_quantized_hist "
                        "with tree_learner=data on a multi-device mesh "
                        "and no EFB bundles; using the f32 reduction")

        # packed wire + overlap slots (tpu_psum_wire / tpu_async_psum):
        # both arms live in the grower config so the step-cache
        # geometry key separates programs compiled for different
        # wire/slot choices, and both are bit-identical to the legacy
        # collective (parallel/learners.py make_hist_reduce)
        psum_wire = "int32"
        psum_slots = 1
        if mode == "data" and mesh is not None:
            from ..ops.autotune import (tune_hist_psum_async,
                                        tune_psum_wire)
            if quant_psum:
                psum_wire = tune_psum_wire(
                    n_rows_global=self._n_pad,
                    requested=cfg.tpu_psum_wire)
            elif cfg.tpu_psum_wire == 1:
                log.warning("tpu_psum_wire=1 needs the quantized psum "
                            "(tpu_quantized_psum) active; the f32 "
                            "wire cannot be narrowed exactly")
            psum_slots = tune_hist_psum_async(
                mesh=mesh, W=W, F=self._f_pad, B=B_hist,
                channels=2 if proxy else 3,
                wire=psum_wire if quant_psum else "f32",
                requested=cfg.tpu_async_psum)
        elif cfg.tpu_async_psum == 1:
            log.warning("tpu_async_psum=1 needs tree_learner=data on a "
                        "multi-device mesh; the serial histogram has "
                        "no collective to overlap")

        from ..ops.autotune import tune_hist_route
        gcfg = WaveGrowerConfig(
            num_leaves=max(cfg.num_leaves, 2),
            # >= 2 so the per-feature split scan is never empty (the
            # all-trivial-features case has one dummy single-bin feature)
            num_bins=B_hist,
            wave_size=W,
            max_depth=cfg.max_depth,
            # autotuned row chunk (ops/autotune.py; defaults: 16384
            # int8 / 8192 otherwise). kchunk (computed above) kept in
            # sync for row padding.
            chunk=kchunk,
            hp=hp,
            precision=precision,
            exact_variant=exact_variant,
            forced=self._parse_forced_splits(),
            count_proxy=proxy,
            packed4=packed4,
            quant_psum=quant_psum,
            psum_wire=psum_wire,
            psum_slots=psum_slots,
            sparse_hist=sparse_tier,
            # resolved per device kind so the step-cache geometry key
            # (which hashes this config) separates programs compiled
            # for different kernel families — a Mosaic-route step
            # never serves a CPU restore of the same geometry
            route=tune_hist_route(
                fused_eligible=not self._use_bundles
                and not sparse_tier))
        self._grower_cfg = gcfg
        hist_fn = None
        efb_feature = None
        if self._use_bundles:
            # EFB: the wave kernel runs over BUNDLE columns, then member
            # histograms are reconstructed (io/efb.py docstring)
            from ..io.efb import expand_bundle_histogram
            from ..ops.hist_wave import wave_histogram
            td = self.train_data
            Bb = max(td.bundle_width, 2)
            mb = jnp.asarray(td.member_bundle)
            mo = jnp.asarray(td.member_offset)
            nb_m = jnp.asarray(meta.num_bin)
            db_m = jnp.asarray(meta.default_bin)
            B_out = gcfg.num_bins
            if mode == "feature":
                # feature-parallel slices BUNDLE columns; the learner
                # builds its own per-device slice-and-expand seam
                efb_feature = (td.member_bundle, td.member_offset,
                               meta.num_bin, meta.default_bin, Bb,
                               B_out, td.bundled_bins.shape[1])
            else:
                def hist_fn(bins_t, g, h, leaf_ids, wave_leaves,
                            gh_scale=None):
                    bh = wave_histogram(bins_t, g, h, leaf_ids,
                                        wave_leaves,
                                        num_bins=Bb, chunk=gcfg.chunk,
                                        use_pallas=gcfg.use_pallas,
                                        precision=gcfg.precision,
                                        gh_scale=gh_scale)
                    return expand_bundle_histogram(bh, mb, mo, nb_m,
                                                   db_m, B_out)
        self._grower = make_grower_for_mode(
            mode, gcfg, meta, mesh, self._f_pad, cfg.top_k,
            hist_fn=hist_fn, efb_feature=efb_feature)
        self._step_key = None       # grower changed: rebuild fused step
        # a row-sharding learner's records carry the fullest shard's
        # dotted rows and the tree's wave passes beside the serial
        # learner's three: the grower says how wide its records are
        self._work_len = getattr(self._grower, "resolved", {}).get(
            "work_len", 3)

    def _step_cache_eligible(self, mode: str) -> bool:
        """True when this booster's fused step can be served by the
        process-wide registry (ops/step_cache.py): serial/data learner
        without EFB bundles, an objective with a pure gradient seam
        (or none — custom gradients are traced arguments anyway), and
        a boosting variant whose step is the standard one. Reads THIS
        booster's config knob, not the module global — another
        booster's init must not flip a live booster's shape policy."""
        if self.config.tpu_step_cache == 0 or not self._step_cache_ok:
            return False
        if self._use_bundles or mode not in ("serial", "data"):
            return False
        if mode == "data":
            # externally-injected collectives (LGBM_NetworkInitWith-
            # Functions) are arbitrary callables the geometry key
            # cannot cover — a cached step would silently bypass the
            # injected wrapper (or serve a program traced with a
            # different one); trace per-instance instead
            from ..parallel.learners import _collective_overrides
            if _collective_overrides:
                return False
        obj = self.objective
        if obj is not None and obj.gradient_builder() is None:
            return False
        return True

    # -- sharded iteration state (data/voting over a mesh) -------------------

    @property
    def num_devices(self) -> int:
        """Devices the training step actually spans: the mesh size for
        the sharded learners, 1 for serial (public — bench/reporting
        must not reach into ``_mesh``)."""
        mesh = getattr(self, "_mesh", None)
        return int(mesh.devices.size) if mesh is not None else 1

    @property
    def learner_mode(self) -> str:
        """Resolved tree learner — may be 'serial' after a one-device
        fallback, unlike config.tree_learner (public, for reporting)."""
        return getattr(self, "_learner_mode", "serial")

    def device_report(self) -> dict:
        """What this booster RESOLVED to on this process's devices —
        read from its own state, never from config: the device, the
        learner and mesh actually in use, the histogram route the
        grower factory took (and whether its Pallas kernels run
        interpreted), the tier geometry, where the bin matrix's shards
        live, and whether the streamed device ingest built them.
        chip_smoke.py asserts on this so that a TPU run cannot quietly
        be something else."""
        from ..ops.autotune import device_kind
        from ..utils.device import get_devices
        g = self._grower_cfg
        bins = self._bins_dev
        return {
            "platform": get_devices()[0].platform,
            "device_kind": device_kind(),
            "learner_mode": self.learner_mode,
            "num_devices": self.num_devices,
            **getattr(self._grower, "resolved", {}),
            "precision": g.precision,
            "exact_variant": (g.exact_variant
                              if g.precision == "highest" else ""),
            "count_proxy": bool(g.count_proxy),
            "packed4": bool(g.packed4),
            "wave_size": int(g.wave_size),
            "chunk": int(g.chunk),
            "num_bins": int(g.num_bins),
            "quant_psum": bool(g.quant_psum),
            "psum_wire": self.wire_encoding(),
            "num_data": int(self._n),
            "score_rows": int(self._n_score),
            "bins_shape": tuple(int(x) for x in bins.shape),
            "bins_shards": [(str(sh.device),
                             tuple(int(x) for x in sh.data.shape))
                            for sh in bins.addressable_shards],
            "device_ingest": self.train_data.bins is None
            and self.train_data.bins_t_dev is not None,
            "step_cache_eligible": bool(self._cache_eligible),
        }

    def lower_step(self):
        """``jax.stages.Lowered`` of this booster's fused training step
        on its live arguments (objective gradients, no custom g/h) —
        the introspection surface for "what did the compiler build":
        ``.compile().as_text()`` shows the Pallas custom calls and the
        collectives of the sharded learners. Nothing runs and nothing
        is donated. (Not for boosting=rf, whose averaging step has its
        own argument list.)"""
        return self._get_step_fn(False).lower(
            self._step_bins(), self._scores, tuple(self._valid_scores),
            self._full_mask_dev, self._feature_mask_dev(),
            jnp.float32(self.shrinkage_rate), self._zero_bias,
            self._dummy_gh, self._dummy_gh, self._dummy_key)

    def _row_sharded(self) -> bool:
        """True when iteration state lives row-sharded over the mesh
        (data/voting): bins [F, N], scores [K, N], grad/hess/bagging
        masks and leaf ids all partition on the row axis, matching the
        shard_map specs — so the per-iteration step moves NO data
        between chips except the wave-histogram psum (and O(N)-vector
        boundary shuffles where train/valid slices cross shard edges)."""
        return (self._mesh is not None
                and self._learner_mode in ("data", "voting"))

    def _mesh_kw(self) -> dict:
        """How score updates must run their leaf-gather kernel under
        this booster's learner (ops/predict.py leaf_gather): per shard
        of the training mesh, rows split or replicated; {} serial."""
        if self._mesh is None:
            return {}
        return {"mesh": self._mesh, "row_sharded": self._row_sharded()}

    def _named_sharding(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.learners import AXIS
        spec = tuple(AXIS if s == "rows" else None for s in spec)
        return NamedSharding(self._mesh, PartitionSpec(*spec))

    def _multiprocess_mesh(self) -> bool:
        """True when the training mesh spans >1 OS process (real
        multi-host run, parallel/cluster.py): device_put cannot reach
        non-addressable devices, so every placement below switches to
        the global-array constructors. Host-side inputs stay
        HOST-GLOBAL (every rank passes the same full-length value —
        labels, masks, scores), which is what makes the seams the only
        multi-process-aware code in this class."""
        from ..parallel import cluster
        return cluster.spans_processes(getattr(self, "_mesh", None))

    def _global_put(self, x, *spec):
        from ..parallel import cluster
        from ..parallel.learners import AXIS
        return cluster.host_to_global(
            x, self._mesh, *tuple(AXIS if s == "rows" else None
                                  for s in spec))

    def _place_rows(self, x):
        """[N_total] row vector onto the mesh (P over rows), or the
        default device for serial."""
        if not self._row_sharded():
            return jnp.asarray(x)
        if self._multiprocess_mesh():
            return self._global_put(x, "rows")
        return jax.device_put(x, self._named_sharding("rows"))

    def _place_bins(self, x):
        """[F, N_total] bin matrix: feature axis replicated, row axis
        sharded. device_put of a host matrix distributes each shard
        straight to its chip; re-placing an already-matching sharded
        array (the sharded-ingest path) is a no-op. Under a
        multi-process mesh the matrix is REQUIRED to already be the
        multihost-assembled global array (io/ingest.py
        bin_matrix_multihost) — no single host holds the full matrix
        to place."""
        if not self._row_sharded():
            return jnp.asarray(x)
        if self._multiprocess_mesh():
            if not hasattr(x, "sharding"):
                raise ValueError(
                    "multi-process training needs the bin matrix "
                    "assembled by the multihost ingest "
                    "(io/distributed.py construct_multihost) — a host "
                    "matrix cannot be placed across processes")
            return x
        return jax.device_put(x, self._named_sharding(None, "rows"))

    def _place_scores(self, x):
        """[K, N] score block, row axis sharded. jax only places
        explicit shardings on evenly divisible axes, so score blocks
        whose (unpadded) row count doesn't divide the mesh stay on the
        default device — the step still computes correctly (GSPMD
        moves the [N] f32 vectors at the slice boundary), it just
        pays an O(N)-vector shuffle instead of staying shard-local.
        Production-scale row counts are D-aligned; tiny test sets may
        not be."""
        if (not self._row_sharded()
                or np.shape(x)[-1] % self.num_devices):
            return jnp.asarray(x)
        if self._multiprocess_mesh():
            return (x if hasattr(x, "sharding")
                    else self._global_put(x, None, "rows"))
        return jax.device_put(x, self._named_sharding(None, "rows"))

    def _place_step_rows(self, x):
        """Row-aligned shared-step argument ([..., n_score]: rvalid,
        padded objective aux): sharded on the row axis when the
        iteration state is, so the jitted step never reshards it."""
        x = np.asarray(x)
        if (not self._row_sharded()
                or x.shape[-1] % self.num_devices):
            return jnp.asarray(x)
        spec = ("rows",) if x.ndim == 1 else (None, "rows")
        if self._multiprocess_mesh():
            return self._global_put(x, *spec)
        return jax.device_put(x, self._named_sharding(*spec))

    def _parse_forced_splits(self) -> tuple:
        """forcedsplits_filename JSON -> BFS-ordered
        ((parent_leaf, inner_feature, bin), ...) matching the
        reference's ForceSplits leaf numbering
        (serial_tree_learner.cpp:546-701: left child keeps the parent
        leaf, right child takes the next id in application order)."""
        cfg = self.config
        if not cfg.forcedsplits_filename:
            return ()
        import collections
        import json as _json
        try:
            with open(cfg.forcedsplits_filename) as fh:
                spec = _json.load(fh)
        except (OSError, ValueError) as e:
            log.fatal(f"Cannot read forced splits file "
                      f"{cfg.forcedsplits_filename!r}: {e}")
        td = self.train_data
        out = []
        q = collections.deque([(spec, 0)])
        next_leaf = 1
        cap = max(cfg.num_leaves, 2) - 1
        while q and len(out) < cap:
            node, leaf = q.popleft()
            if not isinstance(node, dict) or "feature" not in node:
                continue
            if "threshold" not in node:
                log.fatal(f"Forced split node missing 'threshold': "
                          f"{node!r}")
            inner = td.real_to_inner.get(int(node["feature"]))
            if inner is None:
                log.warning("Forced split on unused feature %s skipped",
                            node["feature"])
                continue
            if td.mappers[inner].bin_type == 1:   # BinType.CATEGORICAL
                log.warning("Forced split on categorical feature %s is "
                            "not supported; skipped", node["feature"])
                continue
            tbin = int(td.mappers[inner].value_to_bin(
                np.asarray([float(node["threshold"])]))[0])
            out.append((leaf, int(inner), tbin))
            right_leaf = next_leaf
            next_leaf += 1
            if node.get("left"):
                q.append((node["left"], leaf))
            if node.get("right"):
                q.append((node["right"], right_leaf))
        if out:
            log.info("Applying %d forced splits per tree", len(out))
        return tuple(out)

    def _init_scores(self):
        n, k = self._n, self.num_tree_per_iteration
        ns = self._n_score
        # score block at the (possibly bucketed) width: columns past n
        # are pad rows whose gradients the step forces to exact +0.0
        # (step_cache.build_train_step rvalid mask) — their score
        # values are never read by metrics or predictions
        init = np.zeros((k, ns), np.float32)
        self._boost_from_avg_done = [False] * k
        md = self.train_data.metadata
        if md.init_score is not None:
            init[:, :n] += np.asarray(md.init_score,
                                      np.float32).reshape(k, n)
        self._scores = self._place_scores(init)
        self._valid_scores: List[jax.Array] = []

    def add_valid_data(self, valid_data: TpuDataset,
                       metrics: Sequence[Metric], name: str = "") -> None:
        self.valid_sets.append(valid_data)
        self.valid_names.append(name or f"valid_{len(self.valid_sets)}")
        self.valid_metrics.append(list(metrics))
        k, nv = self.num_tree_per_iteration, valid_data.num_data
        init = np.zeros((k, nv), np.float32)
        if valid_data.metadata.init_score is not None:
            init += np.asarray(valid_data.metadata.init_score,
                               np.float32).reshape(k, nv)
        self._valid_scores.append(self._place_scores(init))
        # replay existing model on the new valid set (bins cached on device
        # once — uploads are cheap, downloads are not)
        v_host = (valid_data.bundled_bins
                  if (self._use_bundles
                      and valid_data.bundles is not None)
                  else valid_data.bins)
        if v_host is None and valid_data.bins_t_dev is not None:
            # streamed ingest: the valid bins are already [F, N] on
            # device (a device-ingested valid set implies an unbundled
            # train set — io/dataset.py _device_ingest_ok)
            vb = valid_data.bins_t_dev
        else:
            vt = np.ascontiguousarray(v_host.T)
            from ..obs import registry as obs
            obs.counter("transfer/h2d_bins_bytes").add(int(vt.nbytes))
            obs.counter("transfer/h2d_uploads").add(1)
            vb = jnp.asarray(vt)
        self._valid_bins_dev.append(vb)
        for t_idx, rec in enumerate(self.records):
            cls = t_idx % self.num_tree_per_iteration
            leaf = replay_partition(rec, vb, self._meta)
            self._valid_scores[-1] = self._valid_scores[-1].at[cls].set(
                add_leaf_outputs(self._valid_scores[-1][cls], leaf,
                                 rec.leaf_output, 1.0,
                                 **self._mesh_kw()))
        # future iterations: this set's rows ride the wave partition
        self._rebuild_grower_bins()

    def init_from_loaded(self, config: Config, train_data: TpuDataset,
                         objective: Optional[ObjectiveFunction],
                         training_metrics: Sequence[Metric] = ()):
        """Continued training (input_model): call after
        ``load_model_from_string``. Rebuilds device TreeRecords for the
        loaded host trees (bin-space thresholds via the new mappers) and
        replays them into the train scores, so training continues exactly
        where the loaded model stopped (boosting.cpp:30-55 +
        gbdt.cpp ResetTrainingData semantics)."""
        from ..ops.grower import TreeRecord
        loaded_models = [m for m in self.models if m is not None]
        if len(loaded_models) != len(self.models):
            log.fatal("init_from_loaded requires a fully loaded model")
        k_loaded = max(self.num_tree_per_iteration, 1)
        self.init(config, train_data, objective, training_metrics)
        if self.num_tree_per_iteration != k_loaded:
            log.fatal("num_class of input_model doesn't match config")
        L = self._grower_cfg.num_leaves
        from .tree import record_arrays_from_tree
        self.models = loaded_models
        self.records = []
        self._bump_model_gen()
        self._tree_shrinkage = [m.shrinkage if m.shrinkage else 1.0
                                for m in loaded_models]
        for t_idx, tree in enumerate(loaded_models):
            arrs = record_arrays_from_tree(
                tree, train_data.real_to_inner, train_data.mappers, L)
            rec = TreeRecord(**{k: jnp.asarray(v)
                                for k, v in arrs.items()})
            self.records.append(rec)
            cls = t_idx % self.num_tree_per_iteration
            leaf = replay_partition(rec, self._train_bins_unpacked(), self._meta)
            self._scores = self._scores.at[cls].set(add_leaf_outputs(
                self._scores[cls], leaf[:self._n_score],
                rec.leaf_output, 1.0, **self._mesh_kw()))
        self.iter_ = len(loaded_models) // self.num_tree_per_iteration
        self._clean_groups = self.iter_
        log.info("Continuing training from iteration %d", self.iter_)

    # -- bagging (gbdt.cpp:161-243) -----------------------------------------

    def _bagging_mask(self, iteration: int) -> Optional[np.ndarray]:
        cfg = self.config
        if not (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0):
            return None
        if iteration % cfg.bagging_freq != 0 and hasattr(self, "_bag_cache"):
            return self._bag_cache
        n = self._n
        cnt = int(n * cfg.bagging_fraction)
        idx = self._bagging_rng.choice(n, cnt, replace=False)
        mask = np.zeros(n, np.float32)
        mask[idx] = 1.0
        self._bag_cache = mask
        return mask

    @staticmethod
    def _pack4_host(bins_t: np.ndarray) -> np.ndarray:
        """Nibble-pack a [F, N] uint8 bin matrix (values <= 15): two
        features per byte, even feature in the low nibble."""
        if bins_t.shape[0] % 2:
            bins_t = np.pad(bins_t, ((0, 1), (0, 0)))
        return (bins_t[0::2] | (bins_t[1::2] << 4)).astype(np.uint8)

    @staticmethod
    def _pack4_dev(bins_t: jax.Array) -> jax.Array:
        """_pack4_host for device-resident ingest bins (same layout as
        the valid-set packing in _rebuild_grower_bins)."""
        if bins_t.shape[0] % 2:
            bins_t = jnp.pad(bins_t, ((0, 1), (0, 0)))
        return jnp.bitwise_or(bins_t[0::2],
                              jnp.left_shift(bins_t[1::2], jnp.uint8(4)))

    def _build_sparse_planes(self):
        """(codes, feat, row, zero_bins) device planes for the sparse
        histogram tier (ops/hist_wave.py wave_histogram_sparse), padded
        to the nnz bucket with sentinel entries (feature == padded F,
        dropped by every scatter). Works off host coords (the host
        scatter path) or the device planes sparse ingest assembled —
        either way the ingest's own sentinels (feature == unpadded F)
        are remapped past the padded width first."""
        from ..obs import registry as obs
        from ..ops import step_cache
        td = self.train_data
        f = max(td.num_features, 1)
        codes = self._upload_plane(td.sparse_coords[0])
        feat = self._upload_plane(td.sparse_coords[1])
        rows = self._upload_plane(td.sparse_coords[2])
        feat = jnp.where(feat >= f, jnp.int32(self._f_pad), feat)
        E = int(codes.shape[0])
        Ep = (step_cache.bucket_entries(E, self.config.tpu_row_bucket)
              if self._cache_eligible else E)
        pad = Ep - E
        if pad:
            codes = jnp.concatenate([codes, jnp.zeros(pad, jnp.int32)])
            feat = jnp.concatenate(
                [feat, jnp.full(pad, self._f_pad, jnp.int32)])
            rows = jnp.concatenate([rows, jnp.zeros(pad, jnp.int32)])
        zb = np.zeros(self._f_pad, np.int32)
        zbs = td.sparse_zero_bins
        zb[:len(zbs)] = zbs
        obs.counter("sparse/hist_tier_sparse").add(1)
        log.info("sparse histogram tier: %d coordinate entries "
                 "(bucketed to %d) over %d features", E, Ep,
                 self._f_pad)
        return (codes, feat, rows, jnp.asarray(zb))

    def _upload_plane(self, arr) -> jax.Array:
        """One sparse coordinate plane to device, delta-encoded across
        the host->device wire where tpu_psum_wire allows and the int16
        delta bound holds (io/sparse.py delta_pack_plane; 0 = legacy
        int32 transport). Reconstruction by int32 cumsum is exact, so
        the device plane is bit-identical either way."""
        if (self.config.tpu_psum_wire != 0
                and isinstance(arr, np.ndarray)):
            from ..io.sparse import delta_pack_plane
            packed = delta_pack_plane(arr)
            if packed is not None:
                base, d16 = packed
                from ..obs import registry as obs
                obs.counter("comm/wire_bytes_saved").add(2 * d16.size)
                return (jnp.int32(base)
                        + jnp.cumsum(jnp.asarray(d16).astype(jnp.int32)))
        return jnp.asarray(arr).astype(jnp.int32)

    def _step_bins(self):
        """The fused step's bins argument: the dense matrix, paired
        with the sparse coordinate planes when the sparse histogram
        tier is active (the grower unpacks the tuple)."""
        sp = getattr(self, "_sparse_dev", None)
        return self._bins_dev if sp is None else (self._bins_dev, sp)

    @property
    def _bins_train_dev(self) -> jax.Array:
        """The training columns of the grower bin matrix (valid-set
        passenger columns excluded)."""
        return self._bins_dev[:, :self._train_width]

    def _train_bins_unpacked(self) -> jax.Array:
        """Training bins as [F, N] — transient nibble-unpack when the
        4-bit packed tier is active (replay_partition and friends index
        per-feature rows; only the grower kernels understand packed
        bytes)."""
        if not self._grower_cfg.packed4:
            return self._bins_train_dev
        b = self._bins_train_dev
        lo = jnp.bitwise_and(b, jnp.uint8(15))
        hi = jnp.right_shift(b, jnp.uint8(4))
        return jnp.stack([lo, hi], axis=1).reshape(
            -1, b.shape[1])[:self._num_bin_rows]

    def _rebuild_grower_bins(self) -> None:
        """Append every valid set's bin columns to the grower's bin
        matrix as weight-0 passenger rows. The wave kernels then hand
        each valid row its leaf id in the SAME fused partition pass
        that places the training rows — the per-iteration valid-score
        update becomes a slice + leaf-output gather. The alternative
        (replaying num_leaves-1 splits per tree inside the step, the
        reference's per-row traversal transliterated) measured ~2.3x
        the whole iteration cost at 11M train + 500k valid rows;
        passenger rows cost ~Nv/N extra kernel time instead.

        Masked rows cannot influence training: their g/h/bagging mask
        are zero, histogram counts ride the mask channel, and the
        count-proxy's exact per-leaf counts only count in-bag rows."""
        base = self._bins_train_dev
        parts = [base]
        self._valid_row_slices = []
        off = base.shape[1]
        for vb in self._valid_bins_dev:
            nv = vb.shape[1]
            if self._pad_features:
                vb = jnp.pad(vb, ((0, self._pad_features), (0, 0)))
            if self._grower_cfg.packed4:
                vb = self._pack4_dev(vb)
            self._valid_row_slices.append((off, nv))
            parts.append(vb.astype(base.dtype))
            off += nv
        # re-align the combined width, mirroring the init-time row-
        # padding policy EXACTLY: chunk alignment only where init would
        # have applied it (serial on TPU; big data/voting shards) —
        # small CPU/test datasets must not balloon to a 16k multiple
        from ..utils.device import on_tpu
        mode = self._learner_mode
        D = self._mesh.devices.size if self._mesh is not None else 1
        from ..ops.autotune import DEFAULT_HIST_CHUNK
        kchunk = self._grower_cfg.chunk or DEFAULT_HIST_CHUNK
        align = 1
        if mode in ("data", "voting"):
            align = D * kchunk if off >= 4 * D * kchunk else D
        elif mode == "serial" and on_tpu():
            align = kchunk
        tail = (-off) % align
        if tail:
            parts.append(jnp.zeros((base.shape[0], tail), base.dtype))
        self._n_total = off + tail
        # re-place under the mesh sharding: passenger columns arrive on
        # one device, so the combined matrix reshards ONCE here instead
        # of every iteration
        self._bins_dev = self._place_bins(
            parts[0] if len(parts) == 1
            else jnp.concatenate(parts, axis=1))
        # masks/scores pad to the new total
        self._full_mask_dev = self._place_rows(jnp.concatenate(
            [jnp.ones(self._n, jnp.float32),
             jnp.zeros(self._n_total - self._n, jnp.float32)]))
        self._step_key = None        # step closure holds the slices

    def _feature_mask(self) -> np.ndarray:
        cfg = self.config
        # >= 1: the all-trivial-features case has one dummy feature
        f = max(self.train_data.num_features, 1)
        mask = np.ones(f, bool)
        if cfg.feature_fraction < 1.0:
            used = max(1, int(f * cfg.feature_fraction))
            sel = self._feature_rng.choice(f, used, replace=False)
            mask = np.zeros(f, bool)
            mask[sel] = True
        return mask

    def _feature_mask_dev(self) -> jax.Array:
        """Padded device feature mask; the all-features case is cached so
        the common path uploads nothing per iteration."""
        if self.config.feature_fraction >= 1.0:
            if self._fmask_cache is None:
                m = np.ones(max(self.train_data.num_features, 1), bool)
                if self._pad_features:
                    m = np.concatenate(
                        [m, np.zeros(self._pad_features, bool)])
                self._fmask_cache = jnp.asarray(m)
            return self._fmask_cache
        m = self._feature_mask()
        if self._pad_features:
            m = np.concatenate([m, np.zeros(self._pad_features, bool)])
        return jnp.asarray(m)

    # -- boosting (gbdt.cpp:333-412) ----------------------------------------

    def boost_from_average(self, class_id: int) -> float:
        """BoostFromAverage (gbdt.cpp:311-330): only when the model is
        still empty and no init score was supplied."""
        cfg = self.config
        if (self.models or not cfg.boost_from_average
                or self.objective is None
                or self.train_data.metadata.init_score is not None):
            return 0.0
        if self.objective.name in (
                "regression", "regression_l1", "quantile", "huber",
                "fair", "mape", "binary", "cross_entropy",
                "poisson", "gamma", "tweedie"):
            init = self.objective.boost_from_score(class_id)
            if init != 0.0:
                self._scores = self._scores.at[class_id].add(init)
                for i in range(len(self._valid_scores)):
                    self._valid_scores[i] = \
                        self._valid_scores[i].at[class_id].add(init)
                log.info("Start training from score %g", init)
            return init
        return 0.0

    # -- shared fused step (ops/step_cache.py) -------------------------------

    def _pad_step_aux(self, aux):
        """Host aux pytree -> device: every array leaf's LAST axis is
        the row axis (objectives/objective.py seam contract); pad it
        from n to the bucketed n_score with zeros and place it under
        the step's row sharding. Leaves under a dict key starting with
        ``_`` are NOT row-shaped (lambdarank's query tables, a pytree of
        its width classes): their leaves are placed replicated, unpadded."""
        if aux is None:
            return None
        if isinstance(aux, dict):
            return {k: (jax.tree_util.tree_map(self._place_step_raw, v)
                        if k.startswith("_") else self._pad_step_aux(v))
                    for k, v in aux.items()}
        a = np.asarray(aux)
        pad = self._n_score - a.shape[-1]
        if pad:
            a = np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
        return self._place_step_rows(a)

    def _place_step_raw(self, x):
        """Non-row-shaped shared-step aux leaf (the ``_``-prefixed seam
        keys): replicated over the mesh when one is live, so the jitted
        step never reshards it."""
        if x is None:
            return None
        x = np.asarray(x)
        if self._mesh is None:
            return jnp.asarray(x)
        spec = (None,) * x.ndim
        if self._multiprocess_mesh():
            return self._global_put(x, *spec)
        return jax.device_put(x, self._named_sharding(*spec))

    def _step_geometry_key(self, custom: bool, obj, renew_alpha,
                           aux_dev, meta_dev) -> tuple:
        """Hashable registry key covering EVERYTHING that shapes the
        step's trace — a hit is guaranteed to be a functionally
        identical program (data flows through traced arguments)."""
        from ..ops import step_cache
        mesh_key = (None if self._mesh is None else
                    tuple(int(d.id) for d in self._mesh.devices.flat))
        bins = self._bins_dev
        return (
            "train_step",
            self.num_tree_per_iteration, self._n_score, self._n_total,
            tuple(self._valid_row_slices),
            self._learner_mode, mesh_key,
            bool(self._row_sharded()
                 and self._n_score % self.num_devices == 0),
            self._grower_cfg, self._f_pad,
            (bins.shape[0], str(bins.dtype)),
            ("custom",) if custom or obj is None else obj.static_key(),
            renew_alpha,
            # in-jit sample hook statics (hashed GOSS closes over its
            # rates; GBDT contributes a no-sample marker)
            self._sample_static_key(),
            step_cache.aux_signature(aux_dev),
            step_cache.aux_signature(
                dict(zip(type(meta_dev)._fields, meta_dev))),
            # sparse histogram tier: the flag rides _grower_cfg above;
            # the bucketed nnz plane length shapes the trace
            ("sparse", None if getattr(self, "_sparse_dev", None) is None
             else int(self._sparse_dev[0].shape[0])),
        )

    def _sample_static_key(self) -> tuple:
        """Hashable statics of the in-jit sample hook — the geometry-
        key component covering everything a REGISTRY-ELIGIBLE hook
        closes over. GBDT has no hook; hashed GOSS overrides this with
        its sampling rates (models/boosting.py)."""
        return ("nosample",)

    @staticmethod
    def _renew_aux(obj):
        """(renew_alpha, host renew-aux dict) for objectives that
        refit leaf outputs (the L1 family), else (None, None) — the
        ONE source of the label/weight plumbing for BOTH step
        routings (registry + legacy), so they cannot drift."""
        if not obj.is_renew_tree_output():
            return None, None
        lbl = (obj.trans_label if hasattr(obj, "trans_label")
               else obj.label)
        w = getattr(obj, "label_weight", None)
        if w is None:
            w = obj.weights
        return (float(obj.renew_tree_output_percentile()),
                {"label": np.asarray(lbl, np.float32),
                 "w": None if w is None else np.asarray(w, np.float32)})

    def _get_cached_step(self, custom: bool):
        """Fetch (or build once per geometry, process-wide) the shared
        fused step and bind this booster's rvalid/meta/aux arguments."""
        from ..ops import step_cache
        key_local = ("cache", custom, len(self._valid_bins_dev))
        if getattr(self, "_step_key", None) == key_local:
            return self._step_fn
        obj = self.objective
        grad_fn = (None if custom or obj is None
                   else obj.gradient_builder())
        renew_alpha = aux_renew = None
        if grad_fn is not None:
            renew_alpha, aux_renew = self._renew_aux(obj)
        aux_host = {"obj": None, "renew": aux_renew}
        if grad_fn is not None:
            aux_host["obj"] = obj.gradient_aux()
        aux_dev = self._pad_step_aux(aux_host)
        meta = self._meta
        meta_dev = type(meta)(*[jnp.asarray(x) for x in meta])
        key = self._step_geometry_key(custom, obj, renew_alpha,
                                      aux_dev, meta_dev)
        grower = self._grower
        K = self.num_tree_per_iteration

        sample_hook = self._sample_hook

        def builder():
            # the hook is registry-shareable: an eligible hook closes
            # only over config scalars, all covered by the geometry
            # key's _sample_static_key() component
            return step_cache.build_train_step(
                grower=grower, K=K, n_score=self._n_score,
                n_total=self._n_total,
                valid_slices=tuple(self._valid_row_slices),
                num_leaves=self._grower_cfg.num_leaves,
                grad_fn=grad_fn, renew_alpha=renew_alpha,
                sample_hook=sample_hook, **self._mesh_kw())

        shared = step_cache.get_step(key, builder)
        rvalid = self._rvalid_dev

        def stepfn(bins, scores, valid_scores, mask, fmask, shrink,
                   init_bias, g_in, h_in, prng):
            return shared(bins, scores, valid_scores, mask, fmask,
                          shrink, init_bias, g_in, h_in, prng,
                          rvalid, meta_dev, aux_dev)

        stepfn.lower = lambda *a: shared.lower(*a, rvalid, meta_dev,
                                               aux_dev)
        self._step_fn = stepfn
        self._step_key = key_local
        return stepfn

    def _get_step_fn(self, custom: bool):
        """ONE jitted function for a full boosting iteration.

        Everything — gradients, K tree builds, renew, shrinkage fold,
        AddBias on the stored record, train+valid score updates — runs
        as a single XLA program. This is the TPU-critical design point:
        an un-fused iteration pays ~100 eager op dispatches, each a
        host round trip the device idles through. Fused: one dispatch.

        Eligible configurations route to the PROCESS-WIDE registry
        (ops/step_cache.py via _get_cached_step): the step is a pure
        function of a geometry key and is compiled once per geometry,
        not once per booster. Ineligible ones get a per-instance jit of
        the SAME step body (step_cache.build_train_step with
        rvalid/meta=None — one implementation, two routings). Retraces
        only when a valid set is added or the custom-gradient mode
        flips; shrinkage/init-bias are traced arguments.
        """
        if getattr(self, "_cache_eligible", False):
            return self._get_cached_step(custom)
        # legacy per-booster closure (GOSS/EFB/feature/voting/
        # tpu_step_cache=0): SAME step body as the registry path
        # (step_cache.build_train_step — one implementation, two
        # routings), but jitted per-instance with exact row shapes:
        # rvalid=None (no bucketing pad to mask). The feature metadata
        # rides as a TRACED argument wherever the grower accepts one
        # (serial/data — the default seams), exactly like the registry
        # path: as closure constants XLA folds it (an all-"no missing"
        # missing_type deletes the whole dir=+1 scan), fuses the
        # surviving gain arithmetic differently and LLVM contracts
        # different mul/add pairs into FMAs — observed under jax 0.9 as
        # last-ulp split_gain drift between the two routings of the
        # SAME booster (docs/Design.md §5d). The feature/voting seams
        # and EFB's bundle-expansion seam keep their own closure
        # metadata (meta=None).
        key = (custom, len(self._valid_bins_dev))
        if getattr(self, "_step_key", None) == key:
            return self._step_fn
        from ..ops import step_cache
        obj = self.objective
        K = self.num_tree_per_iteration
        if custom or obj is None:
            grad_fn = None
        else:
            # closure-gradient seam: same get_gradients the objective's
            # pure gradient_builder delegates to, so the two routes
            # cannot drift (objectives/objective.py)
            def grad_fn(scores, _aux_obj, _obj=obj):
                return _obj.get_gradients(scores)
        renew_alpha = aux_renew = None
        if grad_fn is not None:
            renew_alpha, aux_renew = self._renew_aux(obj)
        aux = {"obj": None, "renew": None}
        if aux_renew is not None:
            aux["renew"] = {k: (None if v is None else jnp.asarray(v))
                            for k, v in aux_renew.items()}
        # bins (and the aux arrays) are ARGUMENTS, not closure
        # constants: closed-over arrays embed into the lowered program
        # (308 MB of literal at 11M rows, recompiled per dataset).
        # Valid rows ride INSIDE ``bins`` as weight-0
        # passenger rows (_rebuild_grower_bins): the grower's partition
        # hands every valid row its leaf id, so the per-iteration
        # valid-score update is a slice + leaf-output gather instead of
        # a num_leaves-deep split replay per tree.
        shared = step_cache.build_train_step(
            grower=self._grower, K=K, n_score=self._n,
            n_total=self._n_total,
            valid_slices=tuple(self._valid_row_slices),
            num_leaves=self._grower_cfg.num_leaves,
            grad_fn=grad_fn, renew_alpha=renew_alpha,
            sample_hook=self._sample_hook, **self._mesh_kw())

        meta_dev = None
        if (self._learner_mode in ("serial", "data")
                and not self._use_bundles):
            meta_dev = type(self._meta)(*[jnp.asarray(x)
                                          for x in self._meta])

        def stepfn(bins, scores, valid_scores, mask, fmask, shrink,
                   init_bias, g_in, h_in, prng):
            return shared(bins, scores, valid_scores, mask, fmask,
                          shrink, init_bias, g_in, h_in, prng,
                          None, meta_dev, aux)

        stepfn.lower = lambda *a: shared.lower(*a, None, meta_dev, aux)
        self._step_fn = stepfn
        self._step_key = key
        return self._step_fn

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration; returns True if training should stop
        (gbdt.cpp:333-412). grad/hess: optional custom [K, N] arrays.

        Stored TreeRecords are MODEL-equivalent: their ``leaf_output``
        already carries shrinkage and (for the first iteration) the
        boost-from-average bias, exactly like the reference's
        ``Shrinkage`` + ``AddBias`` on the saved tree (gbdt.cpp:371-377).

        Entirely device-resident: ONE fused jit call per iteration, no
        device->host transfer. The "no more splits" stop is detected by
        a periodic host check (every ``tpu_stop_check_interval``
        iterations).
        """
        # iteration span at the single choke point EVERY driver passes
        # through (gbdt.train, engine/Booster.update, the capi/lrb
        # per-window loop, bench) — dispatch-issue wall, like the phase
        # clocks; queued device time drains in the periodic
        # queue_drain spans. Its children cover the whole body, so its
        # self time is this function's own Python.
        with timing.phase("train/iteration", cat="iteration",
                          args={"it": self.iter_ + 1}):
            return self._train_one_iter_inner(grad, hess)

    def _train_one_iter_inner(self, grad, hess) -> bool:
        with timing.phase("train/prepare"):
            step, args, init_scores = self._prepare_iteration(grad, hess)
        with timing.phase("train/step_dispatch"):
            self._scores, new_valids, recs = step(*args)
        with timing.phase("train/record"):
            self._record_iteration(new_valids, recs, init_scores)
        sync_iv = self._dispatch_sync_interval
        if sync_iv > 0 and self.iter_ % sync_iv == 0:
            # drain the dispatch queue with ONE scalar readback, so
            # async dispatch never runs more than sync_iv iterations
            # ahead of the device (config.tpu_dispatch_sync_interval:
            # introduced for a retired backend, not re-checked on the
            # in-process chip). The readback — not block_until_ready —
            # is kept for the same reason: it is ordered behind every
            # queued step on any backend.
            with timing.phase("train/queue_drain"):
                np.asarray(recs[-1].num_leaves)
        if self.iter_ % self._stop_check_interval == 0:
            with timing.phase("train/stop_check"):
                return self._check_stop()
        return False

    def _prepare_iteration(self, grad, hess):
        """Host half of an iteration before the dispatch: init scores,
        bagging and feature masks, bias, the step and its PRNG key.
        -> (step, its arguments, init_scores)."""
        from ..parallel import cluster
        if cluster.is_multiprocess():
            # progress stamp for the no-hang watchdog
            # (cluster.DeadlineGuard): a peer death that BLOCKS a
            # collective instead of failing it is detected as a stall
            # at this label within tpu_collective_timeout_s
            cluster.tick(f"iteration {self.iter_ + 1}")
        K = self.num_tree_per_iteration
        init_scores = [0.0] * K
        custom = grad is not None and hess is not None
        if not custom:
            if self.objective is None:
                log.fatal("No objective; pass custom grad/hess")
            for k in range(K):
                init_scores[k] = self.boost_from_average(k)
            g_in = h_in = self._dummy_gh
        else:
            if self._grower_cfg.exact_variant == "hilo3":
                from ..utils.device import on_tpu
                if on_tpu():
                    # the hilo3 kernel reads the hess plane AS the
                    # count plane — custom hessians would silently
                    # corrupt both (the XLA oracle is layout-free, so
                    # off-TPU custom gradients are unaffected)
                    log.fatal(
                        "custom grad/hess with the hilo3 exact tier: "
                        "the fused hess/count plane assumes unit "
                        "hessians; set tpu_exact_tier=hilo4 (or "
                        "hilo5) for custom-objective training")
            g_in = jnp.asarray(grad, jnp.float32).reshape(K, self._n)
            h_in = jnp.asarray(hess, jnp.float32).reshape(K, self._n)
            pad = self._n_score - self._n
            if pad:
                # bucketed step width: pad custom gradients with exact
                # zeros (the rvalid mask re-zeroes them in-step anyway)
                g_in = jnp.pad(g_in, ((0, 0), (0, pad)))
                h_in = jnp.pad(h_in, ((0, 0), (0, pad)))

        mask_np = self._bagging_mask(self.iter_)
        if mask_np is None:
            mask = self._full_mask_dev  # precomputed padded all-ones mask
        else:
            tail = self._n_total - self._n   # align pad + valid rows
            if tail:
                mask_np = np.concatenate(
                    [mask_np, np.zeros(tail, np.float32)])
            mask = self._place_rows(mask_np)
        fmask = self._feature_mask_dev()

        first_iteration = not self.models
        init_bias = (jnp.asarray(init_scores, jnp.float32)
                     if first_iteration else self._zero_bias)
        step = self._get_step_fn(custom)
        if self._sample_hook is not None:
            key = jax.random.PRNGKey(self._hook_rng.integers(1, 2**31))
        else:
            key = self._dummy_key
        return step, (
            self._step_bins(),
            self._scores, tuple(self._valid_scores), mask, fmask,
            jnp.float32(self.shrinkage_rate), init_bias, g_in, h_in,
            key), init_scores

    def _record_iteration(self, new_valids, recs, init_scores) -> None:
        """Host half after the dispatch: keep the step's (still
        in-flight) outputs as this iteration's records."""
        first_iteration = not self.models
        self._valid_scores = list(new_valids)
        for k, rec in enumerate(recs):
            shrinkage_for_file = self.shrinkage_rate
            if first_iteration and abs(init_scores[k]) > 1e-15:
                shrinkage_for_file = 1.0
            self.records.append(rec)
            self.models.append(None)
            self._tree_shrinkage.append(shrinkage_for_file)
        self.iter_ += 1
        self._bump_model_gen()


    def leaves_and_waves(self, start_group: int = 0):
        """Per-iteration [class-tree] leaf counts and wave-pass counts
        for the stored records from ``start_group`` on — ONE stacked
        device download. Public: the run report (train) and bench both
        derive their comm accounting from these."""
        K = self.num_tree_per_iteration
        recs = self.records[start_group * K:]
        if not recs:
            return [], []
        nl, work = self._tail_host(recs)
        leaves = nl.reshape(-1, K).tolist()
        if self._work_len > 3:
            # the passes the grower counted (a wave grows: 1, 2, 4 ..
            # leaves split before it is ever full)
            waves = work[:, 4].reshape(-1, K).sum(axis=1).tolist()
        else:
            W = max(self._grower_cfg.wave_size, 1)
            waves = [sum(max(-(-(int(l) - 1) // W), 1) for l in grp)
                     for grp in leaves]
        return leaves, waves

    def wire_encoding(self) -> str:
        """The histogram-collective wire encoding this booster trains
        with: "" off the data-parallel path (no collective), "f32" for
        the dequantize-first wire, else the quantized wire's dtype
        ("int32"/"int16"/"int8", config.tpu_psum_wire). Surfaces as
        ``meta.wire`` in run reports."""
        if self._mesh is None or self._learner_mode != "data":
            return ""
        gcfg = self._grower_cfg
        return gcfg.psum_wire if gcfg.quant_psum else "f32"

    def record_comm_bytes(self, recorder, waves) -> Optional[list]:
        """Attach per-iteration psum payload bytes (and the cumulative
        comm counters, including the packed-wire savings and the
        measured stall-time estimate) to a RunRecorder; returns the
        byte list, or None off the data-parallel path."""
        comm = self._comm_bytes_per_iteration(waves)
        if comm:
            from ..obs import registry as obs
            for i, cb in enumerate(comm):
                recorder.set_field(i + 1, "comm_bytes", cb)
            # comm/psum_bytes and comm/psum_passes are fed where the
            # trees are counted (_first_splitless_group), report or none
            passes = (sum(waves)
                      + self.num_tree_per_iteration * len(waves))
            saved = self._wire_bytes_saved_per_pass() * passes
            if saved:
                obs.counter("comm/wire_bytes_saved").add(saved)
            stall = self.psum_stall_estimate_s(passes)
            if stall is not None:
                obs.counter("comm/psum_stall_s").add(stall)
        return comm

    def psum_stall_estimate_s(self, passes: int) -> Optional[float]:
        """Seconds the run would stall on the histogram collective:
        MEASURED per-pass wall of the real psum payload on the real
        mesh (ops/autotune.py measure_psum_s — outside the compiled
        step, where in-step timing is impossible) x pass count. None
        off the data-parallel path."""
        if self._mesh is None or self._learner_mode != "data" \
                or passes <= 0:
            return None
        gcfg = self._grower_cfg
        from ..ops.autotune import measure_psum_s
        from ..parallel.learners import _WIRE_DTYPES
        C = self._wire_channels()
        dtype = (_WIRE_DTYPES[gcfg.psum_wire] if gcfg.quant_psum
                 else jnp.float32)
        shape = (gcfg.wave_size, self._f_pad, gcfg.num_bins, C)
        try:
            per_pass = measure_psum_s(self._mesh, shape, dtype)
        except Exception as e:        # noqa: BLE001 — a measurement
            # must never take accounting (or training) down, but a
            # collective that cannot run on this mesh is worth hearing
            log.warning("psum stall measurement failed: %s: %s",
                        type(e).__name__, e)
            return None
        return float(per_pass) * int(passes)

    def _wire_channels(self) -> int:
        """Channel count of the histogram-collective payload."""
        from ..utils.device import on_tpu
        # the 2-channel proxy wire only exists where the Pallas fused
        # kernel runs (the XLA oracle keeps 3 exact channels)
        return 2 if (self._grower_cfg.count_proxy and on_tpu()) else 3

    def _wire_entry_bytes(self) -> int:
        """Bytes per histogram entry on the wire: 4 for f32/int32, 2
        for the packed int16 wire, 1 for int8 (tpu_psum_wire)."""
        gcfg = self._grower_cfg
        if not gcfg.quant_psum:
            return 4
        return {"int8": 1, "int16": 2}.get(gcfg.psum_wire, 4)

    def _wire_bytes_saved_per_pass(self) -> int:
        """Bytes per collective pass the packed wire keeps off the
        DCN relative to the 4-byte legacy wire."""
        width_saved = 4 - self._wire_entry_bytes()
        if not width_saved:
            return 0
        gcfg = self._grower_cfg
        F_h = max(self.train_data.num_features, 1)
        return (gcfg.wave_size * F_h * gcfg.num_bins
                * self._wire_channels() * width_saved)

    def _comm_bytes_per_iteration(self, waves) -> Optional[list]:
        """Per-iteration cross-chip psum payload bytes on the
        data-parallel path (None otherwise): each class tree pays one
        root histogram pass plus one per wave step, and each pass
        reduces a [W, F_hist, B, C] block (entry width set by the
        wire — 4 bytes f32/int32, 2/1 packed int16/int8; the
        count-proxy tier carries 2 channels instead of 3). Scalar
        reductions (root aggregates, quantization pmax) are a few
        hundred bytes per tree and are not counted."""
        if self._mesh is None or self._learner_mode != "data":
            return None
        K = self.num_tree_per_iteration
        return [self._psum_bytes(int(w), K) for w in waves]

    def _psum_bytes(self, waves: int, trees: int) -> int:
        """Bytes ONE chip hands the histogram seam for ``trees`` trees
        grown in ``waves`` wave passes, from the shapes as the step
        holds them: a wave pass sums a [W, F_pad, B, C] block, a root
        pass one of ``root_slots`` slots (1 under the root kernel)."""
        gcfg = self._grower_cfg
        slot = (self._f_pad * gcfg.num_bins * self._wire_channels()
                * self._wire_entry_bytes())
        root = getattr(self._grower, "resolved", {}).get(
            "root_slots", gcfg.wave_size)
        return (waves * gcfg.wave_size + trees * root) * slot

    def _tail_host(self, records):
        """(num_leaves [R], wave_work [R, w]) of a list of records in
        ONE transfer: what the stop check reads."""
        R = self._stop_check_interval * self.num_tree_per_iteration
        parts = []
        for i in range(0, max(len(records), 1), R):
            part = records[i:i + R]
            parts.append(np.asarray(
                _tail_summary(*self._tail_args(part)))[:len(part)])
        a = np.concatenate(parts)
        return a[:, 0], a[:, 1:]

    def _tail_args(self, part):
        """``_tail_summary``'s arguments for up to an interval's records,
        a shorter part padded to the interval: one compiled program."""
        R = self._stop_check_interval * self.num_tree_per_iteration
        w = self._work_len

        def work(r):
            # a loaded model's record is the serial learner's [3]
            short = w - r.wave_work.shape[0]
            return jnp.pad(r.wave_work, (0, short)) if short else r.wave_work

        pad = R - len(part)
        return (tuple(r.num_leaves for r in part)
                + (self._tail_pad[0],) * pad,
                tuple(work(r) for r in part) + (self._tail_pad[1],) * pad)

    def _drop_last_iterations(self, n_groups: int) -> None:
        """Remove the last ``n_groups`` boosting iterations AND subtract
        their score contributions (shared by stop-trim and rollback)."""
        K = self.num_tree_per_iteration
        for _ in range(n_groups):
            for k in range(K - 1, -1, -1):
                rec = self.records.pop()
                self.models.pop()
                self._tree_shrinkage.pop()
                leaf = replay_partition(rec, self._train_bins_unpacked(),
                                        self._meta)[:self._n_score]
                self._scores = self._scores.at[k].set(add_leaf_outputs(
                    self._scores[k], leaf, rec.leaf_output, -1.0,
                    **self._mesh_kw()))
                for vi in range(len(self.valid_sets)):
                    vleaf = replay_partition(rec, self._valid_bins_dev[vi],
                                             self._meta)
                    self._valid_scores[vi] = \
                        self._valid_scores[vi].at[k].set(add_leaf_outputs(
                            self._valid_scores[vi][k], vleaf,
                            rec.leaf_output, -1.0, **self._mesh_kw()))
            self.iter_ -= 1
        self._clean_groups = min(self._clean_groups, self.iter_)
        self._bump_model_gen()

    def _first_splitless_group(self) -> Optional[int]:
        """Index of the first iteration in which NO class tree could
        split — where the reference stops (gbdt.cpp:393-409). Scans only
        groups not yet verified productive; one device download of the
        scanned tail. None if every iteration was productive."""
        K = self.num_tree_per_iteration
        num_groups = len(self.records) // K
        if num_groups <= self._clean_groups:
            return None
        tail = self.records[self._clean_groups * K:num_groups * K]
        nl, work = self._tail_host(tail)
        groups = nl.reshape(-1, K)
        splitless = [i for i in range(len(groups))
                     if (groups[i] <= 1).all()]
        n_clean = splitless[0] if splitless else len(groups)
        first = self._clean_groups + n_clean if splitless else None
        self._clean_groups += n_clean
        # the verified trees' wave-pass work rode the same download
        from ..obs import registry as obs
        from ..ops.hist_wave import COMPACT_TILE_UNIT
        work = work[:n_clean * K].astype(np.int64).sum(axis=0)
        rows = work * COMPACT_TILE_UNIT
        obs.counter("hist/rows_scanned").add(int(rows[0]))
        obs.counter("hist/rows_dotted").add(int(rows[1]))
        obs.counter("hist/blocks_dotted").add(int(work[2]))
        obs.counter("hist/trees_counted").add(n_clean * K)
        if self._mesh is not None and self._learner_mode == "data":
            # the sum across chips: what the fullest shard dotted at
            # each (how long the others waited), and the bytes a chip
            # handed the histogram seam for these trees
            obs.counter("hist/rows_dotted_max_shard").add(int(rows[3]))
            waves = int(work[4])
            obs.counter("comm/psum_passes").add(waves + n_clean * K)
            obs.counter("comm/psum_bytes").add(
                self._psum_bytes(waves, n_clean * K))
        return first

    def _trim_at_splitless(self, gi: int) -> None:
        """Drop the splitless iteration ``gi`` and everything after it.
        A splitless iteration 0 is kept as the reference's constant first
        tree (gbdt.cpp:378-396) but still stops training."""
        keep = max(gi, 1)
        self._drop_last_iterations(self.iter_ - keep)
        self._stopped = True
        log.warning("Stopped training because there are no more leaves "
                    "that meet the split requirements")

    def _check_stop(self) -> bool:
        """Periodic host check for the reference's early stop; removes
        the splitless iteration and everything trained after it (score
        contributions subtracted, so state stays consistent)."""
        if self._stopped:
            return True
        gi = self._first_splitless_group()
        if gi is None:
            return False
        self._trim_at_splitless(gi)
        return True

    def finish_training(self) -> None:
        """Final trim; call once after the boosting loop. Mirrors
        _check_stop for splitless iterations that landed after the last
        periodic check."""
        if self._stopped:
            return
        gi = self._first_splitless_group()
        if gi is not None:
            self._trim_at_splitless(gi)

    # -- lazy host-tree materialization --------------------------------------

    def _ensure_host_trees(self) -> None:
        """Build host Tree mirrors for all device records that don't have
        one yet — a single packed stacked download for all of them."""
        missing = [i for i, m in enumerate(self.models) if m is None]
        if not missing:
            return
        packed = jnp.stack([pack_record(self.records[i]) for i in missing])
        packed_np = np.asarray(packed)
        L = self._grower_cfg.num_leaves
        for row, i in enumerate(missing):
            rec_np = unpack_record(packed_np[row], L)
            tree = tree_from_record(
                rec_np, self.train_data.mappers,
                self.train_data.used_feature_map, 1.0, L)
            tree.shrinkage = self._tree_shrinkage[i]
            self.models[i] = tree

    def _stacked_guard(self) -> threading.RLock:
        """The serving-path lock — created lazily for instances
        deserialized around __init__ (copy/pickle shims)."""
        lk = getattr(self, "_stacked_lock", None)
        if lk is None:
            lk = self._stacked_lock = lockorder.named_rlock(
                "gbdt._stacked_lock")
        return lk

    def _bump_model_gen(self) -> None:
        """Invalidate prediction caches — call from every path that
        mutates the ensemble (train, rollback, refit, load). Runs
        under the serving lock so a concurrent predict() never reads a
        generation that is mid-bump."""
        with self._stacked_guard():
            self._model_gen = getattr(self, "_model_gen", 0) + 1

    def _invalidate_stacked(self) -> None:
        """Hard-drop the stacked predictor. Needed by paths that
        mutate a host tree IN PLACE (LGBM_BoosterSetLeafValue): tree
        identity survives such edits, so the prefix-reuse check in
        _stacked_model cannot see them — the stale stacks must go."""
        with self._stacked_guard():
            self._model_gen = getattr(self, "_model_gen", 0) + 1
            self._stacked_cache = None
            self._stacked_ref = None

    def _stacked_model(self):
        """Cached whole-ensemble device predictor (ops/stacked_predict);
        None when the model shape can't be stacked.

        Serving-grade reuse: the whole check-build-publish runs under
        one lock (a predict() during a retrain serializes behind the
        build instead of racing a half-built StackedModel), and a
        generation bump no longer forces a full re-stack — when the
        previously stacked trees are still a prefix of the live
        ensemble (continued training appends; rollback trims), the
        cached predictor is EXTENDED with only the new tree chunk
        (StackedModel.extend) or reused as-is with the caller's ntree
        slicing. Only a genuinely different ensemble (retrain on a
        fresh booster, refit, shuffle, load) pays a full stack."""
        with self._stacked_guard():
            # snapshot BOUND first: a training thread may append a
            # record (models gains a not-yet-materialized None tail
            # entry) at any moment — everything below operates on the
            # prefix that existed here, which _ensure_host_trees is
            # guaranteed to have materialized
            n_live = len(self.models)
            self._ensure_host_trees()
            models = list(self.models[:n_live])
            key = (getattr(self, "_model_gen", 0), len(models))
            cached = getattr(self, "_stacked_cache", None)
            if cached is not None and cached[0] == key:
                return cached[1]
            sm = None
            prev = cached[1] if cached is not None else None
            # invariant: _stacked_ref lists EXACTLY the tree objects
            # prev has stacked, in order — every reuse decision below
            # is an identity check against it
            ref = getattr(self, "_stacked_ref", None)
            if prev is not None and prev.ok and ref:
                shared = min(len(ref), len(models))
                if all(a is b for a, b in zip(ref[:shared],
                                              models[:shared])):
                    if len(models) <= len(ref):
                        # trim/rollback or a pure gen bump: the stacks
                        # already cover every live tree — predict()
                        # slices by ntree; ref keeps describing prev's
                        # FULL contents (a later append on top of the
                        # trim must not extend past stale positions)
                        sm = prev
                    else:
                        # copy-on-write: extend() re-bins the WHOLE
                        # table layout in place, so it must never run
                        # on the published object — a predict() in
                        # flight outside this lock would read mixed
                        # old/new tables mid-mutation. Extend a clone
                        # and publish that instead; in-flight readers
                        # keep the consistent original.
                        cand = prev.clone_for_extend()
                        if cand.extend(models[len(ref):]):
                            sm = cand
                            self._stacked_ref = models
            if sm is None:
                from ..ops.stacked_predict import StackedModel
                nf = self.max_feature_idx + 1
                if nf <= 0 and models:
                    nf = max([max(t.split_feature, default=-1)
                              for t in models]) + 1
                cfg = self.config
                sm = StackedModel(
                    models, max(nf, 1), self.num_tree_per_iteration,
                    serve_bucket=(cfg.tpu_serve_bucket
                                  if cfg is not None else None))
                sm = sm if sm.ok else None
                self._stacked_ref = models if sm is not None else None
            self._stacked_cache = (key, sm)
            return sm

    def prepare_serving(self, warm_rows: int = 0) -> bool:
        """Pre-build this model's serving path BEFORE it is published
        into a live request stream — the swap seam of the pipelined
        lrb loop: the trainer thread calls this on the freshly trained
        booster, so the atomic model swap hands over a predictor whose
        stacked tables (and, with ``warm_rows`` > 0, the compiled
        program for that serve-bucket shape) are already warm. Runs
        under the serving lock like every stacked build; returns True
        when a stacked predictor is available."""
        sm = self._stacked_model() if len(self.models) >= 1 else None
        if sm is None:
            return False
        if warm_rows > 0:
            sm.warmup(warm_rows)
            if self.objective is not None:
                # warm the FULL wire path, not just raw scores: the
                # objective transform compiles per serve bucket too
                # (see predict), and a live request stream must never
                # pay that trace — the fleet daemon registers models
                # through here (serve/tenants.py)
                self.predict(np.zeros((int(warm_rows),
                                       max(self.max_feature_idx + 1, 1)),
                                      np.float64))
        return True

    def rollback_one_iter(self) -> None:
        """RollbackOneIter (gbdt.cpp:414-430). Training may resume
        afterwards, so the stop latch is cleared."""
        if self.iter_ <= 0:
            return
        self._drop_last_iterations(1)
        self._stopped = False

    # -- evaluation (gbdt.cpp:432-534) --------------------------------------

    def get_eval_at(self, data_idx: int) -> List[tuple]:
        """Returns [(metric_name, value, bigger_better)] for dataset
        data_idx (0 = train, 1.. = valid).

        When every metric for the dataset has a device implementation
        (metrics/metric.py device_eval_builder), evaluation runs as ONE
        jitted reduction and only len(metrics) scalars cross the wire —
        per-iteration eval (early stopping) no longer downloads the
        full [K, N] score tensor."""
        out = []
        if data_idx == 0:
            scores = self.train_scores()
            metrics = self.training_metrics
        else:
            scores = self._valid_scores[data_idx - 1]
            metrics = self.valid_metrics[data_idx - 1]
        with timing.phase("eval/metrics"):
            fn = self._device_eval_fn(data_idx, metrics)
            if fn is not None:
                vals = np.asarray(fn(scores))
                return [(m.name, float(v), m.bigger_is_better)
                        for m, v in zip(metrics, vals)]
            raw = np.asarray(scores)
            for m in metrics:
                for name, val in m.eval(raw, self.objective):
                    out.append((name, val, m.bigger_is_better))
        return out

    def train_scores(self) -> jax.Array:
        """[K, n] train scores with any bucket-pad columns sliced off —
        every consumer outside the fused step (metrics, fobj, inner
        predict) must read scores through this, not ``_scores``."""
        if self._n_score != self._n:
            return self._scores[:, :self._n]
        return self._scores

    def _device_eval_fn(self, data_idx: int, metrics):
        """Jitted scores -> stacked metric scalars, cached per dataset;
        None when any metric lacks a device implementation."""
        cache = getattr(self, "_dev_eval_fns", None)
        if cache is None:
            cache = self._dev_eval_fns = {}
        if data_idx in cache:
            return cache[data_idx]
        fn = None
        if metrics:
            builders = [m.device_eval_builder(self.objective)
                        for m in metrics]
            if all(b is not None for b in builders):
                # jit-capture: ok(builders) — per-booster jit cached
                # on self._dev_eval_fns keyed by dataset; the metric
                # builders close over THIS booster's eval arrays,
                # never registry-shared
                fn = jax.jit(
                    lambda s: jnp.stack([b(s) for b in builders]))
        cache[data_idx] = fn
        return fn

    # -- prediction ---------------------------------------------------------

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    start_iteration: int = 0,
                    pred_early_stop: bool = False,
                    pred_early_stop_freq: int = 10,
                    pred_early_stop_margin: float = 10.0) -> np.ndarray:
        """Raw scores [N] or [N, K]. Device path: bin with train mappers,
        replay trees on device, ONE download (gbdt_prediction.cpp:9-30).

        ``pred_early_stop``: stop accumulating trees for rows whose
        prediction margin exceeds the threshold, re-checked every
        ``freq`` trees (prediction_early_stop.cpp:20-84: binary margin
        = 2|raw|, multiclass margin = top1 - top2). Rows stop in
        batches of ``freq`` — inherently data-dependent, so it runs on
        the host tree path."""
        out = self._predict_sparse_chunked(
            X, lambda Xd: self.predict_raw(
                Xd, num_iteration, start_iteration, pred_early_stop,
                pred_early_stop_freq, pred_early_stop_margin))
        if out is not None:
            return out
        X = np.asarray(X, np.float64)
        n = X.shape[0]
        k = self.num_tree_per_iteration
        # live predictions see the same trees a checkpoint would contain
        ntree = self._effective_num_models()
        if num_iteration >= 0:
            ntree = min(ntree, (start_iteration + num_iteration) * k)
        first = start_iteration * k
        # the reference enables early stop only where approximate
        # predictions are acceptable: binary / multiclass
        # (NeedAccuratePrediction, prediction_early_stop.cpp)
        if pred_early_stop and k == 1 and not (
                self.objective is not None
                and self.objective.name in ("binary", "multiclassova",
                                            "cross_entropy")):
            log.warning("pred_early_stop is only supported for "
                        "binary/multiclass objectives; ignoring")
            pred_early_stop = False
        if pred_early_stop and k >= 1 and ntree > first:
            self._ensure_host_trees()
            out = np.zeros((k, n), np.float64)
            active = np.arange(n)
            Xa = X                      # re-sliced only when rows stop
            for t_idx in range(first, ntree):
                cls = t_idx % k
                out[cls, active] += self.models[t_idx].predict(Xa)
                done_group = ((t_idx - first + 1) % max(
                    pred_early_stop_freq * k, 1) == 0)
                if done_group and len(active):
                    if k == 1:
                        margin = 2.0 * np.abs(out[0, active])
                    else:
                        part = np.sort(out[:, active], axis=0)
                        margin = part[-1] - part[-2]
                    keep = margin <= pred_early_stop_margin
                    if not keep.all():
                        active = active[keep]
                        Xa = X[active]
                    if not len(active):
                        break
            if self.average_output:
                out /= max((ntree - first) // k, 1)
            return out[0] if k == 1 else out.T
        # no row floor: with serve buckets (ops/predict_cache.py) a
        # 1-row online request rides the same warm compiled program as
        # a 4096-row batch — the host walk stays only for tiny
        # ensembles where stacking cannot pay for itself
        sm = (self._stacked_model() if (ntree - first) >= 4 and n >= 1
              else None)
        if sm is not None:
            # whole-ensemble MXU scan: one dispatch chain instead of one
            # replay per tree (ops/stacked_predict.py). A serving-path
            # caller with an active request context (obs/reqlog.py —
            # the lrb loop, bench --serve) gets its dispatch spanned
            # with the request identity, so the trace timeline answers
            # "which request was on the device" during a stall.
            rctx = obs_reqlog.current()
            if rctx is not None:
                args = {"req_id": rctx.req_id, "rows": int(n)}
                if rctx.window is not None:
                    args["window"] = rctx.window
                span = obs_trace.span("predict/stacked", cat="serve",
                                      args=args)
            else:
                span = contextlib.nullcontext()
            with span:
                out = sm.predict(X, first, ntree).astype(np.float64)
        else:
            self._ensure_host_trees()
            out = np.zeros((k, n), np.float64)
            for t_idx in range(first, ntree):
                out[t_idx % k] += self.models[t_idx].predict(X)
        if self.average_output:
            # reference divides by the iteration count actually predicted
            # (gbdt_prediction.cpp:51-65)
            used_iters = max((ntree - first) // k, 1)
            out /= used_iters
        return out[0] if k == 1 else out.T

    @staticmethod
    def _predict_sparse_chunked(X, fn):
        """CSR predict input (io/sparse.py SparseMatrix) densifies in
        bounded row chunks through ``fn`` — never the whole [N, F]
        matrix; the chunk shrinks with the column count so even a
        100k-column hashed matrix stays under the densify byte budget.
        Bit-exact: every predict path is row-independent. Returns None
        for non-sparse input (the caller proceeds dense)."""
        from ..io.sparse import SparseMatrix, predict_chunk_rows
        if not isinstance(X, SparseMatrix):
            return None
        n = X.shape[0]
        chunk = predict_chunk_rows(X.shape[1])
        if n <= chunk:
            return fn(X.to_dense())
        parts = [fn(X.to_dense_rows(r0, min(r0 + chunk, n)))
                 for r0 in range(0, n, chunk)]
        return np.concatenate(parts, axis=0)

    def _bin_input(self, X: np.ndarray) -> np.ndarray:
        """Bin raw rows with the train mappers -> [F, N] feature-major
        (bundle-encoded when the train set used EFB)."""
        ds = self.train_data
        f = max(ds.num_features, 1)
        dtype = np.uint8 if ds.max_bin_global <= 256 else np.int32
        bins = np.zeros((X.shape[0], f), dtype)
        for i, real in enumerate(ds.used_feature_map):
            bins[:, i] = ds.mappers[i].value_to_bin(
                X[:, real]).astype(dtype)
        if ds.bundles is not None and getattr(self, "_use_bundles",
                                              False):
            from ..io.efb import bundle_bins
            db = np.array([m.default_bin for m in ds.mappers], np.int32)
            nb = np.array([m.num_bin for m in ds.mappers], np.int32)
            bins, _, _, _ = bundle_bins(bins, ds.bundles, db, nb)
        return np.ascontiguousarray(bins.T)

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                **pred_kw) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration, **pred_kw)
        if self.objective is not None:
            # convert_output operates class-major [K, N] like the
            # reference's ConvertOutput; predict_raw returns [N, K]
            r = raw.T if raw.ndim == 2 else raw
            # pad the transform to the SAME serve bucket the forest
            # predict rode: convert_output is a per-row jax op, so an
            # online stream of odd batch sizes would otherwise
            # re-trace it once per distinct size — a serving-path
            # stall the bucketed forest predict already paid to avoid.
            # Rows are independent (sigmoid/per-row softmax); the pad
            # is sliced off, so results are bit-identical.
            from ..ops import predict_cache
            n = int(r.shape[-1])
            cfg = self.config
            b = predict_cache._bucket_rows(
                n, cfg.tpu_serve_bucket if cfg is not None else None)
            if b > n:
                r = np.pad(np.asarray(r),
                           [(0, 0)] * (r.ndim - 1) + [(0, b - n)])
            out = np.asarray(self.objective.convert_output(jnp.asarray(r)))
            out = out[..., :n]
            return out.T if raw.ndim == 2 else out
        return raw

    def predict_leaf_index(self, X: np.ndarray,
                           num_iteration: int = -1) -> np.ndarray:
        out = self._predict_sparse_chunked(
            X, lambda Xd: self.predict_leaf_index(Xd, num_iteration))
        if out is not None:
            return out
        self._ensure_host_trees()
        X = np.asarray(X, np.float64)
        ntree = self._effective_num_models()
        if num_iteration >= 0:
            ntree = min(ntree, num_iteration * self.num_tree_per_iteration)
        sm = (self._stacked_model() if ntree >= 4 and X.shape[0] >= 1
              else None)
        if sm is not None:
            return sm.predict(X, 0, ntree, pred_leaf=True)
        out = np.zeros((X.shape[0], ntree), np.int32)
        for t in range(ntree):
            out[:, t] = self.models[t].predict_leaf_index(X)
        return out

    def predict_contrib(self, X: np.ndarray,
                        num_iteration: int = -1) -> np.ndarray:
        """SHAP feature contributions [N, F+1] (or [N, K*(F+1)] for
        multiclass): per-feature Shapley values + bias column
        (gbdt.h PredictContrib / tree.h:118)."""
        out = self._predict_sparse_chunked(
            X, lambda Xd: self.predict_contrib(Xd, num_iteration))
        if out is not None:
            return out
        self._ensure_host_trees()
        X = np.asarray(X, np.float64)
        n = X.shape[0]
        k = self.num_tree_per_iteration
        f1 = self.max_feature_idx + 2
        ntree = self._effective_num_models()
        if num_iteration >= 0:
            ntree = min(ntree, num_iteration * k)
        out = np.zeros((k, n, f1), np.float64)
        for t_idx in range(ntree):
            self.models[t_idx].predict_contrib(X, out[t_idx % k])
        if self.average_output:
            out /= max(ntree // k, 1)
        if k == 1:
            return out[0]
        return out.transpose(1, 0, 2).reshape(n, k * f1)

    def refit_existing(self, decay_rate: Optional[float] = None) -> None:
        """RefitTree (gbdt.cpp:265-289) against the CURRENT train_data:
        keep every tree's structure, re-learn its leaf outputs on the
        new data's gradients, blending with refit_decay_rate
        (FitByExistingTree, serial_tree_learner.cpp:223-253:
        new = decay*old + (1-decay) * (-sum_g/(sum_h+l2)) * shrinkage).
        Sequential like the reference: iteration i's gradients see the
        refit outputs of iterations 0..i-1. Call after
        ``init_from_loaded`` bound this booster to the new dataset."""
        cfg = self.config
        decay = cfg.refit_decay_rate if decay_rate is None else decay_rate
        if self.objective is None:
            log.fatal("Refit requires an objective")
        K = self.num_tree_per_iteration
        L = self._grower_cfg.num_leaves
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        mds = cfg.max_delta_step
        from ..ops.split import KEPSILON, calculate_leaf_output

        @jax.jit
        def refit_one(scores_k, rec_leaf_output, leaf, g_k, h_k, shrink):
            sg = jnp.zeros(L, jnp.float32).at[leaf].add(g_k)
            sh = jnp.full(L, KEPSILON, jnp.float32).at[leaf].add(h_k)
            new_out = calculate_leaf_output(sg, sh, l1, l2, mds) * shrink
            out = decay * rec_leaf_output + (1.0 - decay) * new_out
            return scores_k + out[leaf], out

        self._init_scores()
        n_iters = len(self.records) // K
        n = self._n
        for it in range(n_iters):
            # gradients see the REAL rows only (objective arrays are
            # [n]; bucket-pad score columns are sliced off)
            sc = self.train_scores()
            g_all, h_all = self.objective.get_gradients(
                sc if K > 1 else sc[0])
            if K == 1:
                g_all, h_all = g_all[None, :], h_all[None, :]
            for k in range(K):
                t = it * K + k
                rec = self.records[t]
                leaf = replay_partition(rec, self._train_bins_unpacked(),
                                        self._meta)[:n]
                new_scores, out = refit_one(
                    self._scores[k, :n], rec.leaf_output, leaf,
                    g_all[k], h_all[k],
                    jnp.float32(self._tree_shrinkage[t]))
                self._scores = self._scores.at[k, :n].set(new_scores)
                self.records[t] = rec._replace(leaf_output=out)
                self.models[t] = None
        self._bump_model_gen()
        log.info("Refit %d trees with decay_rate=%g", len(self.records),
                 decay)

    # -- CLI training driver (gbdt.cpp:245-263 GBDT::Train) ------------------

    def train(self, snapshot_freq: int = -1, output_model: str = "",
              resume_from: str = "") -> None:
        """The application-side training loop: boosting iterations with
        per-iteration metric output (OutputMetric, gbdt.cpp:466-534),
        reference-style early stopping (EvalAndCheckEarlyStopping,
        gbdt.cpp:432-448: pop the last ``early_stopping_round``
        iterations on stop), and periodic snapshots.

        Fault tolerance (utils/checkpoint.py): with
        ``tpu_checkpoint_dir``/``tpu_checkpoint_freq`` set, the loop
        periodically writes a resumable checkpoint bundle (atomic,
        pruned to ``tpu_snapshot_keep``); ``resume_from`` (a bundle
        path or a checkpoint directory — newest valid bundle wins)
        restores a killed run and continues it BIT-IDENTICALLY to the
        uninterrupted run, in the same global iteration numbering.

        Telemetry seam (obs/): every iteration is spanned by a
        RunRecorder (wall time, HBM, transfer-byte deltas, eval values;
        per-iteration leaf counts are filled at the end from ONE
        stacked download), the slow-iteration watchdog warns with the
        phase table, and tpu_profile_dir/tpu_profile_iters bracket a
        configurable iteration window with the jax profiler."""
        import time

        from ..obs.profiler import ProfileWindow
        from ..obs.recorder import RunRecorder
        from ..utils import faults
        cfg = self.config
        # best_score_[i][j] per (valid set, metric), in
        # bigger-is-better orientation
        self._best_score = [[-np.inf] * len(ms) for ms in self.valid_metrics]
        self._best_iter = [[0] * len(ms) for ms in self.valid_metrics]
        self._best_msg = [[""] * len(ms) for ms in self.valid_metrics]
        start_iter = 0
        if resume_from:
            # restore overwrites the best-score lists initialized
            # above, the RNG streams, the bagging mask and the device
            # scores — the loop below then continues at start_iter + 1
            # with the uninterrupted run's numbering. The checkpoint
            # stores TOTAL tree groups; the loop counts ADDITIONAL
            # rounds on top of any loaded input_model (gbdt.cpp:248),
            # so a continued-training resume subtracts the base the
            # input model contributed.
            from ..utils import checkpoint as ckpt
            pre_groups = (len(self.records)
                          // max(self.num_tree_per_iteration, 1))
            restored = ckpt.restore(self, ckpt.resolve_resume(
                resume_from))
            start_iter = restored - pre_groups
            if start_iter < 0:
                log.fatal(f"checkpoint at iteration {restored} predates "
                          f"the loaded input_model ({pre_groups} "
                          f"iterations) — it belongs to a different run")
        start_time = time.monotonic()
        is_finished = False
        recorder = RunRecorder(
            path=cfg.tpu_run_report,
            watchdog_factor=cfg.tpu_watchdog_factor,
            meta={"driver": "gbdt.train", "objective": cfg.objective,
                  "tree_learner": self._learner_mode,
                  "mesh_devices": self.num_devices,
                  "num_iterations": cfg.num_iterations,
                  "num_leaves": cfg.num_leaves,
                  "wave_size": self._grower_cfg.wave_size,
                  "num_data": self._n,
                  "num_features": self.train_data.num_features,
                  "num_class": self.num_class,
                  **({"resumed_from_iteration": start_iter}
                     if start_iter else {})}).start()
        self._recorder = recorder
        profile = ProfileWindow(cfg.tpu_profile_dir,
                                cfg.tpu_profile_iters)

        def materialize_batch(batch):
            """[(it, handles)] -> [(it, {idx: [(name, val, bigger)]})]
            with ONE device concat and ONE download for the whole
            batch: every np.asarray is a blocking device->host read, so
            per-handle downloads re-serialize the training loop no
            matter how the evals are pipelined."""
            flat = [entry[1] for _, ph in batch
                    for entry in ph.values() if entry is not None]
            vals = (np.asarray(jnp.concatenate(flat)) if flat
                    else np.zeros(0, np.float32))
            out = []
            pos = 0
            for pit, ph in batch:
                values = {}
                for idx, entry in ph.items():
                    if entry is None:
                        values[idx] = []
                        continue
                    metrics = entry[0]
                    v = vals[pos:pos + len(metrics)]
                    pos += len(metrics)
                    values[idx] = [
                        (m.name, float(x), m.bigger_is_better)
                        for m, x in zip(metrics, v)]
                out.append((pit, values))
            return out

        # Pipelined evaluation with a BATCHED lookahead, like
        # engine._train_loop but K deep: iteration N's device metric
        # scalars are dispatched right after its update and
        # materialized up to K training iterations later, in order.
        # Any device->host read waits behind EVERY queued dispatch
        # (the transfer stream is ordered), so a per-iteration
        # materialize silently re-serializes the loop to train-time +
        # read latency; batching K evals amortizes that drain to 1/K
        # per round. Semantics are unchanged: metric
        # lines keep the reference format and indices (gbdt.cpp:466-
        # 534, printed in small batches), and an early stop detected
        # late pops the extra lookahead iterations (extra_drop), so
        # the kept model is identical to the synchronous path's. Falls
        # back to the synchronous path when any metric lacks a device
        # implementation.
        pipeline_ok = True
        pending: List[tuple] = []    # [(iteration index, handles)]
        trained = 0
        kdepth = 16

        def flush_pending():
            """Materialize ALL queued evals (one batched download) and
            process them in order; True = early stop fired (the extra
            lookahead iterations are popped)."""
            if not pending:
                return False
            batch = materialize_batch(pending)
            pending.clear()
            for pit, values in batch:
                if self._eval_and_check_early_stopping(
                        pit, values=values, extra_drop=trained - pit):
                    return True
            return False

        # num_iterations counts ADDITIONAL rounds on top of a loaded
        # input_model, like the reference's train loop (gbdt.cpp:248
        # iterates config num_iterations times from the loaded state);
        # the log/snapshot index is likewise the ADDITIONAL-round
        # counter (gbdt.cpp:255-260 uses its loop-local iter + 1)
        # groups already present before this loop (continued
        # training): the report's per-iteration leaf rows must
        # align with the ADDITIONAL-round numbering used above
        base_groups = len(self.records) // self.num_tree_per_iteration
        try:
            for add in range(start_iter, cfg.num_iterations):
                if faults.active():
                    # the kill-and-resume drills aim here (train.iter)
                    faults.check("train.iter", context=add + 1)
                profile.iter_begin(add + 1)
                recorder.begin_iteration(add + 1)
                is_finished = self.train_one_iter()
                # periodic drain/stop-check iterations block on the
                # device and absorb the queued dispatch backlog — tag
                # them so the watchdog compares like spans with like
                sync_iv = self._dispatch_sync_interval
                drained = ((sync_iv > 0 and self.iter_ % sync_iv == 0)
                           or self.iter_ % self._stop_check_interval == 0)
                recorder.end_iteration(
                    add + 1, kind="sync" if drained else "iter")
                profile.iter_end(add + 1)
                trained = add + 1
                if not is_finished:
                    it = add + 1
                    handles = (self._eval_dispatch(it) if pipeline_ok
                               else None)
                    if handles is None:
                        pipeline_ok = False
                    if pipeline_ok:
                        pending.append((it, handles))
                        if len(pending) >= kdepth:
                            # ONE drain per K rounds: the wait rides the
                            # already-queued training work, costing ~one
                            # round-trip per batch instead of per round
                            is_finished = flush_pending()
                    else:
                        # drain the lookahead before going synchronous
                        is_finished = flush_pending()
                        if not is_finished:
                            is_finished = \
                                self._eval_and_check_early_stopping(it)
                log.info("%f seconds elapsed, finished iteration %d",
                         time.monotonic() - start_time, add + 1)
                if snapshot_freq > 0 and (add + 1) % snapshot_freq == 0:
                    # flush the pipelined evals BEFORE snapshotting: a
                    # late-detected early stop pops its lookahead
                    # iterations, and a snapshot written first would
                    # contain trees the pop then removes
                    if not is_finished:
                        is_finished = flush_pending()
                    self._write_snapshot(output_model, add + 1)
                if (cfg.tpu_checkpoint_freq > 0 and cfg.tpu_checkpoint_dir
                        and (add + 1) % cfg.tpu_checkpoint_freq == 0):
                    # same flush-first rule as snapshots: the bundle
                    # must not capture lookahead trees an early stop
                    # is about to pop
                    if not is_finished:
                        is_finished = flush_pending()
                    self.write_checkpoint(cfg.tpu_checkpoint_dir)
                if is_finished:
                    break
            # flush the tail so the last iterations' metric lines (and a
            # late-detected stop) are not lost
            flush_pending()
            profile.close()
            self.finish_training()
            if output_model:
                with timing.phase("io/save_model"):
                    self.save_model_to_file(output_model)
                log.info("Finished training; model saved to %s", output_model)
            # run report: per-iteration leaf counts come from ONE stacked
            # download of the surviving records; wave counts derive from
            # them (a W-leaf wave pass grows up to W leaves per tree).
            # finish() snapshots the phase table BEFORE log_report resets.
            self._recorder = None
            leaves = waves = None
            K = self.num_tree_per_iteration
            # the stacked download is only paid when a report will
            # actually be written (it is a blocking device->host
            # transfer that drains the dispatch queue).
            # Resumed runs skip it: their iteration numbering continues
            # at start_iter + 1 while the leaf lists would start at
            # row 1, misaligning the report.
            if cfg.tpu_run_report and start_iter == 0 \
                    and len(self.records) > base_groups * K:
                leaves, waves = self.leaves_and_waves(base_groups)
                # cross-chip traffic: every root/wave histogram pass
                # moves one [W, F, B, C] block through the psum
                self.record_comm_bytes(recorder, waves)
            from ..ops import predict_cache, step_cache
            # registry totals are process-wide; booster_eligible is
            # THIS booster's routing (the global "enabled" is
            # last-init-wins and may describe a different booster)
            recorder.meta["step_cache"] = dict(
                step_cache.stats(),
                booster_eligible=bool(getattr(self, "_cache_eligible",
                                              False)))
            recorder.meta["predict_cache"] = predict_cache.stats()
            recorder.meta["wire"] = self.wire_encoding()
            recorder.finish(
                leaves_per_iteration=leaves, waves_per_iteration=waves,
                extra={"trained_iterations": self.iter_,
                       "stopped_early": bool(self._stopped)})
        finally:
            # background checkpoint writes drain before train()
            # returns — callers may read the directory (or kill the
            # process) the moment control comes back
            self._drain_checkpoints()
            # exception path: close an open trace, write the partial
            # report, clear the log prefix (finish() is idempotent —
            # the normal path above already finished with leaf counts)
            profile.close()
            self._recorder = None
            from ..ops import predict_cache, step_cache
            recorder.meta.setdefault("step_cache", step_cache.stats())
            recorder.meta.setdefault("predict_cache",
                                     predict_cache.stats())
            recorder.meta.setdefault("wire", self.wire_encoding())
            recorder.finish(extra={"aborted": True})
        timing.log_report("training phase timings "
                          "(serial_tree_learner.cpp:14-41 analog)")

    def _write_snapshot(self, output_model: str, it: int) -> None:
        """Periodic model snapshot (save_period): atomic write + prune
        to the last ``tpu_snapshot_keep`` — a crash mid-write can no
        longer leave a torn ``.snapshot_iter_N`` file, and old
        snapshots no longer accumulate without bound. A failed write
        warns and training continues."""
        from ..utils.fileio import atomic_write, prune_numbered
        path = f"{output_model}.snapshot_iter_{it}"
        try:
            with atomic_write(path) as fh:
                fh.write(self.model_to_string())
        except OSError as e:
            log.warning("snapshot %s failed (%s); training continues",
                        path, e)
            return
        prune_numbered(output_model, ".snapshot_iter_*",
                       r"\.snapshot_iter_(\d+)$",
                       self.config.tpu_snapshot_keep)

    def write_checkpoint(self, directory: str) -> Optional[str]:
        """Write a resumable checkpoint bundle (utils/checkpoint.py);
        returns the path, or None on failure. Failures — disk full,
        an injected ``checkpoint.write`` fault — warn and NEVER stop
        or corrupt training: the atomic write leaves the previous
        complete bundle intact. Public: engine.train's periodic
        checkpoint wiring calls this too.

        With tpu_ckpt_async (-1 auto = on) the file writes ride a
        background writer thread (utils/checkpoint.py
        AsyncCheckpointWriter): the collective score gather and the
        bundle construction still happen here, on-path; only the
        serialization + atomic writes are hidden behind subsequent
        iterations. The queue drains at train end and before any
        resume read."""
        from ..utils import checkpoint as ckpt
        writer = None
        if self.config.tpu_ckpt_async != 0:
            writer = getattr(self, "_ckpt_writer", None)
            if writer is None:
                writer = self._ckpt_writer = ckpt.new_writer()
        try:
            return ckpt.save_checkpoint(
                self, directory, keep=max(self.config.tpu_snapshot_keep,
                                          1), writer=writer)
        except Exception as e:      # noqa: BLE001 — durability aid:
            # a checkpoint is insurance, never the failure itself
            from ..obs import registry as obs
            obs.counter("checkpoint/write_failures").add(1)
            log.warning("checkpoint write to %s failed at iteration %d "
                        "(%s: %s); training continues — the previous "
                        "checkpoint is intact", directory,
                        self.current_iteration, type(e).__name__, e)
            return None

    def _drain_checkpoints(self) -> None:
        """Block until this booster's background checkpoint writer has
        committed every queued bundle (no-op when sync or none were
        written). Called at train end; resolve_resume drains all
        writers itself before any read."""
        writer = getattr(self, "_ckpt_writer", None)
        if writer is not None:
            writer.drain()

    def _eval_and_check_early_stopping(self, it: int, values=None,
                                       extra_drop: int = 0) -> bool:
        # ``it`` counts additional rounds like the reference's iter_
        # (reset to 0 on model load, gbdt_model_text.cpp:485).
        # ``values``: pre-materialized {data_idx: [(name, val,
        # bigger)]} from the pipelined dispatch; ``extra_drop``:
        # lookahead iterations trained beyond ``it`` that must also be
        # popped on stop so the kept model still ends at it - es.
        best_msg = self._output_metric(it, values)
        if not best_msg:
            return False
        es = self.config.early_stopping_round
        # report in additional-round numbers so the lines match the
        # "Iteration:N" metric output (reference iter_ semantics)
        log.info("Early stopping at iteration %d, the best iteration "
                 "round is %d", it, it - es)
        log.info("Output of best iteration round:\n%s", best_msg)
        self._drop_last_iterations(es + extra_drop)
        return True

    def _eval_dispatch(self, it: int):
        """Dispatch (without materializing) the device-metric
        reductions iteration ``it`` will need. Returns {data_idx:
        (metrics, device_values) | None-for-empty} or None when some
        needed dataset has no all-device metric set (sync fallback)."""
        cfg = self.config
        need_output = cfg.metric_freq > 0 and (it % cfg.metric_freq) == 0
        es_round = cfg.early_stopping_round
        want = {}
        if need_output and self.training_metrics:
            want[0] = self.training_metrics
        if need_output or es_round > 0:
            for i in range(len(self.valid_sets)):
                want[i + 1] = self.valid_metrics[i]
        out = {}
        for idx, metrics in want.items():
            if not metrics:
                out[idx] = None
                continue
            fn = self._device_eval_fn(idx, metrics)
            if fn is None:
                return None
            scores = (self.train_scores() if idx == 0
                      else self._valid_scores[idx - 1])
            out[idx] = (metrics, fn(scores))
        return out

    def _output_metric(self, it: int, values=None) -> str:
        """OutputMetric (gbdt.cpp:466-534): print metrics at metric_freq
        and run the early-stopping bookkeeping; returns the best-round
        message when the stop condition is met. ``values``: optional
        pre-materialized {data_idx: [(name, val, bigger)]} (the
        pipelined train loop) instead of synchronous get_eval_at."""
        cfg = self.config
        need_output = cfg.metric_freq > 0 and (it % cfg.metric_freq) == 0
        es_round = cfg.early_stopping_round

        def evals(idx):
            out = (values.get(idx, []) if values is not None
                   else self.get_eval_at(idx))
            rec = getattr(self, "_recorder", None)
            hist = getattr(self, "_eval_history", None)
            if out and (rec is not None or hist is not None):
                dname = ("training" if idx == 0
                         else self.valid_names[idx - 1])
                for name, val, _ in out:
                    if rec is not None:
                        rec.record_eval(it, dname, name, val)
                    if hist is not None:
                        # checkpoint-bundle eval history (global
                        # iteration numbering, utils/checkpoint.py)
                        hist.append((it, dname, name, float(val)))
            return out

        ret = ""
        msg_lines: List[str] = []
        if need_output:
            for name, val, _ in evals(0):
                line = f"Iteration:{it}, training {name} : {val:g}"
                log.info("%s", line)
                if es_round > 0:
                    msg_lines.append(line)
        met_best: List[tuple] = []
        if need_output or es_round > 0:
            for i in range(len(self.valid_sets)):
                for j, (name, val, bigger) in enumerate(
                        evals(i + 1)):
                    line = (f"Iteration:{it}, valid_{i + 1} {name}"
                            f" : {val:g}")
                    if need_output:
                        log.info("%s", line)
                    if es_round > 0:
                        msg_lines.append(line)
                        cur = val if bigger else -val
                        if cur > self._best_score[i][j]:
                            self._best_score[i][j] = cur
                            self._best_iter[i][j] = it
                            met_best.append((i, j))
                        elif not ret and \
                                it - self._best_iter[i][j] >= es_round:
                            ret = self._best_msg[i][j]
        msg = "\n".join(msg_lines)
        for i, j in met_best:
            self._best_msg[i][j] = msg
        return ret

    # -- feature importance (gbdt.cpp FeatureImportance) ---------------------

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = 0) -> np.ndarray:
        self._ensure_host_trees()
        n_models = self._effective_num_models()
        if iteration > 0:
            n_models = min(n_models, iteration * self.num_tree_per_iteration)
        imp = np.zeros(self.max_feature_idx + 1, np.float64)
        for t in self.models[:n_models]:
            for i in range(t.num_leaves - 1):
                if importance_type == "split":
                    imp[t.split_feature[i]] += 1.0
                else:
                    imp[t.split_feature[i]] += max(t.split_gain[i], 0.0)
        return imp

    # -- model text serialization (gbdt_model_text.cpp:240-338) --------------

    def _effective_num_models(self) -> int:
        """Number of trees a reference-equivalent model would contain:
        everything before the first splitless iteration. Non-mutating, so
        mid-training checkpoints don't alter the booster."""
        n = len(self.models)
        if self.records and not self._stopped:
            gi = self._first_splitless_group()
            if gi is not None:
                n = min(n, max(gi, 1) * self.num_tree_per_iteration)
        return n

    def model_to_string(self, start_iteration: int = 0,
                        num_iteration: int = -1) -> str:
        self._ensure_host_trees()
        lines = ["tree"]
        lines.append(f"version={K_MODEL_VERSION}")
        lines.append(f"num_class={self.num_class}")
        lines.append(f"num_tree_per_iteration={self.num_tree_per_iteration}")
        lines.append(f"label_index={self.label_idx}")
        lines.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names))
        lines.append("feature_infos=" + " ".join(self.feature_infos))

        eff = self._effective_num_models()
        total_iter = eff // max(self.num_tree_per_iteration, 1)
        start_iteration = max(0, min(start_iteration, total_iter))
        num_used = eff
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration)
                           * self.num_tree_per_iteration, num_used)
        start_model = start_iteration * self.num_tree_per_iteration

        tree_strs = []
        for i in range(start_model, num_used):
            s = f"Tree={i - start_model}\n" + self.models[i].to_string() + "\n"
            tree_strs.append(s)
        lines.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        lines.append("")
        body = "\n".join(lines) + "\n" + "".join(tree_strs)
        body += "end of trees\n"

        imp = self.feature_importance(
            "split", iteration=num_used // max(self.num_tree_per_iteration, 1))
        pairs = [(int(imp[i]), self.feature_names[i])
                 for i in range(len(imp)) if imp[i] > 0]
        pairs.sort(key=lambda p: -p[0])
        body += "\nfeature importances:\n"
        for v, name in pairs:
            body += f"{name}={v}\n"
        if self.config is not None:
            body += "\nparameters:\n" + self.config.to_string() + "\n"
            body += "end of parameters\n"
        elif self.loaded_parameter:
            body += "\nparameters:\n" + self.loaded_parameter + "\n"
            body += "end of parameters\n"
        return body

    def save_model_to_file(self, filename: str, start_iteration: int = 0,
                           num_iteration: int = -1) -> None:
        with open(filename, "w") as fh:
            fh.write(self.model_to_string(start_iteration, num_iteration))

    def load_model_from_string(self, s: str, source: str = "") -> "GBDT":
        """LoadModelFromString (gbdt_model_text.cpp:339-450).

        Truncated or corrupt input fails with a ONE-LINE error naming
        the source, what is malformed and the expected shape — never a
        deep parse traceback (``source``: the file/context the text
        came from, for the message)."""
        from ..objectives import parse_objective_from_model_string
        where = source or "model text"
        lines = s.splitlines()
        first = next((ln.strip() for ln in lines if ln.strip()), "")
        if first != "tree":
            log.fatal(f"{where}: not a LightGBM model (first line "
                      f"{first[:40]!r}, expected 'tree'; model version "
                      f"{K_MODEL_VERSION})")
        kv = {}
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            if line.startswith("Tree="):
                break
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
            elif line == "average_output":
                kv["average_output"] = "1"
            i += 1
        self.num_class = int(kv.get("num_class", 1))
        self.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", 1))
        self.label_idx = int(kv.get("label_index", 0))
        self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        self.average_output = "average_output" in kv
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos = kv.get("feature_infos", "").split()
        if self.config is None:
            self.config = Config()
        if "objective" in kv:
            self.objective = parse_objective_from_model_string(
                kv["objective"], self.config)
            if self.objective is not None:
                # objective usable only for convert_output after load
                self.objective.label = np.zeros(1, np.float32)
                self.objective.weights = None
                self.objective.num_data = 1
        # parse trees
        self.models = []
        self.records = []
        self._bump_model_gen()
        cur: List[str] = []
        seen_end = False
        for line in lines[i:]:
            t = line.strip()
            if t.startswith("Tree=") or t == "end of trees":
                if cur:
                    try:
                        self.models.append(
                            Tree.from_string("\n".join(cur)))
                    except Exception as e:   # noqa: BLE001 — one-line
                        log.fatal(          # diagnosis, not a traceback
                            f"{where}: malformed Tree="
                            f"{len(self.models)} block "
                            f"({type(e).__name__}: {e})")
                    cur = []
                if t == "end of trees":
                    seen_end = True
                    break
            elif t:
                cur.append(t)
        if not seen_end:
            log.fatal(f"{where}: truncated model text — no 'end of "
                      f"trees' terminator after {len(self.models)} "
                      f"tree(s) (file cut off mid-write?)")
        self.iter_ = len(self.models) // max(self.num_tree_per_iteration, 1)
        self.shrinkage_rate = 1.0  # already folded into leaf values
        self._tree_shrinkage = [m.shrinkage if m.shrinkage else 1.0
                                for m in self.models]
        return self

    def dump_model(self, start_iteration: int = 0,
                   num_iteration: int = -1) -> dict:
        """DumpModel JSON (gbdt_model_text.cpp:15-54)."""
        self._ensure_host_trees()
        num_used = self._effective_num_models()
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration)
                           * self.num_tree_per_iteration, num_used)
        start_model = start_iteration * self.num_tree_per_iteration
        return {
            "name": "tree",
            "version": K_MODEL_VERSION,
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": (self.objective.to_string()
                          if self.objective else "none"),
            "average_output": self.average_output,
            "feature_names": self.feature_names,
            "tree_info": [t.to_json()
                          for t in self.models[start_model:num_used]],
        }

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
