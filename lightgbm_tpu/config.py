"""Parameter/config system.

TPU-native counterpart of the reference config machinery
(reference: include/LightGBM/config.h:27, src/io/config.cpp:153,
src/io/config_auto.cpp:4). One dataclass holds every documented parameter;
aliases are resolved before parsing; cross-parameter conflicts are checked
like Config::CheckParamConflict (src/io/config.cpp:202).

Parameters flow through the same four surfaces as the reference: CLI
``key=value`` argv, config files, param strings, and Python dicts.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .utils import log

# ---------------------------------------------------------------------------
# Alias table (reference: src/io/config_auto.cpp:4-156). alias -> canonical.
# ---------------------------------------------------------------------------
ALIAS_TABLE: Dict[str, str] = {
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective", "app": "objective", "application": "objective",
    "boosting_type": "boosting", "boost": "boosting",
    "train": "data", "train_data": "data", "train_data_file": "data",
    "data_filename": "data",
    "test": "valid", "valid_data": "valid", "valid_data_file": "valid",
    "test_data": "valid", "test_data_file": "valid", "valid_filenames": "valid",
    "num_iteration": "num_iterations", "n_iter": "num_iterations",
    "num_tree": "num_iterations", "num_trees": "num_iterations",
    "num_round": "num_iterations", "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations", "n_estimators": "num_iterations",
    "shrinkage_rate": "learning_rate", "eta": "learning_rate",
    "num_leaf": "num_leaves", "max_leaves": "num_leaves", "max_leaf": "num_leaves",
    "tree": "tree_learner", "tree_type": "tree_learner",
    "tree_learner_type": "tree_learner",
    "num_thread": "num_threads", "nthread": "num_threads",
    "nthreads": "num_threads", "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed", "random_state": "seed",
    "min_data_per_leaf": "min_data_in_leaf", "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction", "subsample": "bagging_fraction",
    "bagging": "bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction", "colsample_bytree": "feature_fraction",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "max_tree_output": "max_delta_step", "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2", "lambda": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints", "monotone_constraint": "monotone_constraints",
    "feature_contrib": "feature_contri", "fc": "feature_contri",
    "fp": "feature_contri", "feature_penalty": "feature_contri",
    "fs": "forcedsplits_filename",
    "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename",
    "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "hist_pool_size": "histogram_pool_size",
    "data_seed": "data_random_seed",
    "model_output": "output_model", "model_out": "output_model",
    "save_period": "snapshot_freq",
    "model_input": "input_model", "model_in": "input_model",
    "predict_result": "output_result", "prediction_result": "output_result",
    "predict_name": "output_result", "prediction_name": "output_result",
    "pred_name": "output_result", "name_pred": "output_result",
    "init_score_filename": "initscore_filename",
    "init_score_file": "initscore_filename", "init_score": "initscore_filename",
    "input_init_score": "initscore_filename",
    "valid_data_init_scores": "valid_data_initscores",
    "valid_init_score_file": "valid_data_initscores",
    "valid_init_score": "valid_data_initscores",
    "is_pre_partition": "pre_partition",
    "is_enable_bundle": "enable_bundle", "bundle": "enable_bundle",
    "is_sparse": "is_enable_sparse", "enable_sparse": "is_enable_sparse",
    "sparse": "is_enable_sparse",
    "two_round_loading": "two_round", "use_two_round_loading": "two_round",
    "is_save_binary": "save_binary", "is_save_binary_file": "save_binary",
    "load_from_binary_file": "enable_load_from_binary_file",
    "binary_load": "enable_load_from_binary_file",
    "load_binary": "enable_load_from_binary_file",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column", "group_id": "group_column",
    "query_column": "group_column", "query": "group_column",
    "query_id": "group_column",
    "ignore_feature": "ignore_column", "blacklist": "ignore_column",
    "cat_feature": "categorical_feature",
    "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "is_predict_raw_score": "predict_raw_score",
    "predict_rawscore": "predict_raw_score", "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index",
    "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib", "contrib": "predict_contrib",
    "convert_model_file": "convert_model",
    "num_classes": "num_class",
    "unbalance": "is_unbalance", "unbalanced_sets": "is_unbalance",
    "metrics": "metric", "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric",
    "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    "ndcg_eval_at": "eval_at", "ndcg_at": "eval_at",
    "map_eval_at": "eval_at", "map_at": "eval_at",
    "num_machine": "num_machines",
    "local_port": "local_listen_port", "port": "local_listen_port",
    "machine_list_file": "machine_list_filename",
    "machine_list": "machine_list_filename", "mlist": "machine_list_filename",
    "workers": "machines", "nodes": "machines",
}


def _compile_cache_from_cpu_knob(v: Any) -> int:
    """Value remap of the pre-rename tpu_compile_cache_cpu: its 1 (CPU
    opt-in) is tpu_compile_cache=1; its 0 meant "CPU off, TPU still
    on" — which is the new knob's -1 auto, NOT its 0 (that would turn
    the cache off on the TPU too)."""
    try:
        return 1 if int(float(v)) == 1 else -1
    except (TypeError, ValueError):
        return -1


# renamed knobs accepted with a deprecation warning. Unlike
# ALIAS_TABLE these remap the VALUE too, so they are resolved in
# Config.set() (on the normalized pre-alias key), not in
# key_alias_transform — an alias-table entry would silently pass the
# old value through with changed semantics.
DEPRECATED_ALIASES = {
    "tpu_compile_cache_cpu": ("tpu_compile_cache",
                              _compile_cache_from_cpu_knob),
}


@dataclass
class Config:
    """All parameters with reference defaults (include/LightGBM/config.h)."""

    # --- core ---
    config: str = ""
    task: str = "train"                    # train, predict, convert_model, refit
    objective: str = "regression"
    boosting: str = "gbdt"                 # gbdt, rf, dart, goss
    data: str = ""
    valid: List[str] = field(default_factory=list)
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    tree_learner: str = "serial"           # serial, feature, data, voting
    num_threads: int = 0
    device_type: str = "cpu"               # cpu, gpu, tpu
    seed: int = 0

    # --- learning control ---
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    early_stopping_round: int = 0
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    top_rate: float = 0.2
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20
    monotone_constraints: List[int] = field(default_factory=list)
    feature_contri: List[float] = field(default_factory=list)
    forcedsplits_filename: str = ""
    refit_decay_rate: float = 0.9
    verbosity: int = 1

    # --- IO / dataset ---
    max_bin: int = 255
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    histogram_pool_size: float = -1.0
    data_random_seed: int = 1
    output_model: str = "LightGBM_model.txt"
    snapshot_freq: int = -1
    input_model: str = ""
    output_result: str = "LightGBM_predict_result.txt"
    initscore_filename: str = ""
    valid_data_initscores: List[str] = field(default_factory=list)
    pre_partition: bool = False
    enable_bundle: bool = True
    max_conflict_rate: float = 0.0
    is_enable_sparse: bool = True
    sparse_threshold: float = 0.8
    use_missing: bool = True
    zero_as_missing: bool = False
    two_round: bool = False
    save_binary: bool = False
    enable_load_from_binary_file: bool = True
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: str = ""

    # --- predict ---
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    num_iteration_predict: int = -1
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0

    # --- convert model ---
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # --- objective ---
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    max_position: int = 20
    label_gain: List[float] = field(default_factory=list)

    # --- metric ---
    metric: List[str] = field(default_factory=list)
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: List[int] = field(default_factory=lambda: [1, 2, 3, 4, 5])

    # --- network ---
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""
    machines: str = ""

    # --- device (gpu params kept for config compatibility; tpu_* are ours) ---
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    gpu_use_dp: bool = False
    # TPU-native additions: histogram accumulation dtype and device batch
    # size. tpu_use_dp=true accumulates histograms at f32 grade via the
    # bf16 hi/lo decomposition (wave cap 25); false = single bf16
    # (2^-9 relative rounding on grad/hess, wave cap 32).
    tpu_use_dp: bool = True
    tpu_hist_chunk: int = 0          # rows per Pallas grid step; 0 = auto
    tpu_donate_buffers: bool = True
    # leaves split per device step (ops/wave_grower.py): one wave
    # histogram pass serves this many leaves at once. 1 = exact
    # reference leaf-wise order; 0 = auto: 24 with tpu_use_dp (hi/lo
    # channel budget, kept a multiple of 8 for sublane alignment) or 32
    # without — values above the active cap are clamped with a warning.
    # NOTE: with W > 1 the grown tree can differ from the reference's
    # strict leaf-wise order when the leaf budget binds mid-wave; set
    # tpu_wave_size=1 for exact reference parity.
    tpu_wave_size: int = 0
    # int8 gradient quantization (analog of modern LightGBM's quantized
    # training): g/h are stochastically rounded to integers in
    # [-127, 127] per tree and histograms accumulate exactly in int32
    # int8 MXU products — 2x the bf16 rate and a 42-leaf wave
    # (3 channels). Costs ~1e-3 AUC-grade noise on the split gains;
    # serial tree_learner without EFB bundles only.
    tpu_quantized_hist: bool = False
    # count-proxy histograms (int8 quantized mode only): drop the count
    # channel from the MXU histogram dot so 2 channels x W <= 128 lanes
    # buys 64-leaf waves — fewer full-data passes per tree (~20% faster
    # at HIGGS scale). Per-bin counts become conservative LOWER BOUNDS
    # (max(|sum g_q|, sum h_q)/127) consumed only by the
    # min_data_in_leaf candidate gate, which can then over-prune but
    # never admits a split the exact gate would reject; per-leaf counts
    # (leaf_count / internal_count in the model file) stay exact via
    # partition-mask counting. -1 = auto (on when tpu_quantized_hist
    # and the fused kernel is eligible: serial/data learner, no EFB
    # bundles, no forced splits, no categoricals); 0 = off; 1 = on.
    tpu_count_proxy: int = -1
    # quantized histogram reduction (data-parallel learner only): psum
    # the int32 quantized histogram representation across the mesh and
    # dequantize AFTER the collective, instead of psumming dequantized
    # f32 sums — the communication-compression analog of LightGBM's
    # quantized distributed training. Exact integer addition on the
    # wire (no f32 rounding across shards) and, with the count-proxy
    # tier, a 2-channel payload (33% less ICI traffic than the
    # 3-channel f32 histogram). Valid because the quantization scales
    # are GLOBAL (pmax over shards), so dequantization commutes with
    # the sum. -1 = auto (on when tpu_quantized_hist is active under
    # tree_learner=data and the global row count stays inside the
    # int32 sum bound; the int-vs-f32 wire choice is autotuned on real
    # meshes, ops/autotune.py); 0 = off (f32 psum); 1 = force.
    tpu_quantized_psum: int = -1
    # packed psum wire width (parallel/learners.py): with the
    # quantized psum active the collective payload is integer-valued,
    # so it can cross the DCN as int16 (or int8) whenever the
    # 127 * num_rows_global wrap bound proves the narrow sum cannot
    # overflow — the narrowing cast, integer psum and widening cast
    # are all exact, so the result is BIT-identical to the int32 wire.
    # The same knob gates the delta-encoded (code, feat, row)
    # coordinate transport of the sparse tier (io/sparse.py). -1 =
    # auto (narrowest provably-safe width); 0 = legacy int32/f32 wire;
    # 1 = force-narrow where safe (falls back with a warning where the
    # wrap bound refuses).
    tpu_psum_wire: int = -1
    # overlap-structured histogram collective (parallel/learners.py):
    # split the [wave, feature, bin, channel] histogram psum into
    # independent double-buffered slot collectives along the feature
    # axis so XLA can overlap one slot's DCN reduction with local
    # compute instead of stalling the step on a single monolithic
    # psum. psum is elementwise across shards, so the slot split is
    # BIT-identical to the fused collective (for f32 AND integer
    # wires). -1 = auto (async slots on data-parallel meshes; the
    # async-vs-sync arm is autotuned per (mesh, payload) key on real
    # TPUs, ops/autotune.py tune_hist_psum_async); 0 = sync (one
    # psum); 1 = force async slots.
    tpu_async_psum: int = -1
    # background checkpoint writer (utils/checkpoint.py): the
    # collective score gather stays on the training path, but rank-0's
    # bundle serialization + atomic file writes move to a bounded-queue
    # writer thread, hiding checkpoint I/O behind subsequent
    # iterations. Commit-point ordering is preserved (scores sidecar
    # first, bundle second, both atomic_write), checkpoint/
    # write_failures semantics are unchanged, and the queue is drained
    # at train end and before any resume read. -1 = auto (on when
    # checkpointing is active); 0 = synchronous writes; 1 = force.
    tpu_ckpt_async: int = -1
    # 4-bit packed HBM bins (the reference's Dense4bitsBin as a COMPUTE
    # tier, dense_nbits_bin.hpp): when max_bin <= 16 and either the
    # count-proxy int8 path or the hi/lo exact tier (tpu_use_dp) is
    # active, two features share one byte in HBM and the Pallas
    # kernels unpack nibbles in VMEM — half the bin-matrix HBM, double
    # the rows/chip. -1 = auto (on when eligible); 0 = off.
    tpu_packed_bins: int = -1
    # exact-tier (tpu_use_dp, non-quantized) histogram channel layout
    # (ops/hist_wave.py): "hilo5" = the 5-channel bf16 hi/lo rows
    # (wave cap 24); "hilo4" = 4 channels plus a second count dot
    # (cap 32 — 25% fewer full-data passes per tree); "hilo3" = the
    # fused hess/count plane (cap 40; constant-unit-hessian objectives
    # without row weights only — requesting it elsewhere falls back to
    # hilo4 with a warning). All three reconstruct identical f32-grade
    # sums. "" = auto: timed once per (features, bins, device) on a
    # real TPU (ops/autotune.py tune_exact_tier), widest feasible wave
    # off-TPU (the XLA path is layout-free, so only the wave cap — the
    # pass count — matters there).
    tpu_exact_tier: str = ""
    # Pallas kernel autotuning (ops/autotune.py): "on" times a small
    # VMEM-feasible set of tile configurations on the first encounter
    # of a (kernel, features, bins, dtype-tier, device-kind) shape and
    # persists the winner to the on-disk tuning cache; "off" pins the
    # measured per-tier defaults; "exhaustive" sweeps the full
    # candidate grid (slower first run, same cache afterwards). Tuning
    # only ever runs on a real TPU backend.
    tpu_autotune: str = "on"
    # tuning-cache file path; empty = lgbm_tpu_tuning_vN.json inside the
    # compile-cache directory (ops/autotune.py default_tuning_cache_path).
    # The file is versioned JSON: a version mismatch re-tunes instead
    # of trusting stale entries (the dataset binary-token discipline).
    tpu_tuning_cache: str = ""
    # write an xprof/tensorboard device trace of the training loop here
    # (obs/profiler.py ProfileWindow brackets the loop with
    # jax.profiler.start/stop_trace; phase names appear as
    # TraceAnnotation spans inside the capture)
    tpu_profile_dir: str = ""
    # iterations to trace when tpu_profile_dir is set: 0 = the whole
    # boosting loop; N > 0 traces exactly N iterations starting at
    # iteration 2 (skipping the compile-dominated first iteration), so
    # the capture shows steady-state device work
    tpu_profile_iters: int = 0
    # run-report artifact path (obs/recorder.py): every training run
    # writes a versioned JSON (or, with a .jsonl suffix, JSONL) report
    # with per-iteration wall times / leaves / HBM / transfer-byte
    # deltas, the phase table, and the transfer counters — perf work
    # diffs these artifacts instead of log tails. Empty = no report.
    tpu_run_report: str = ""
    # slow-iteration watchdog (obs/recorder.py): warn with the current
    # phase table when an iteration exceeds this factor x the trailing
    # median iteration time (last 64, armed after 8). 0 disables.
    tpu_watchdog_factor: float = 8.0
    # iterations between host checks for the "no more splits" stop
    # (gbdt.cpp:393-409); device→host reads are high-latency, so the stop
    # is detected periodically instead of every iteration
    tpu_stop_check_interval: int = 8
    # iterations between forced dispatch-queue drains (a scalar
    # device→host readback), bounding how many iterations async
    # dispatch may queue ahead of the device. Introduced for a backend
    # that no longer exists (deep queues cost it ~2.4x over 500
    # iterations); whether the in-process chip needs the bound: not
    # re-checked — kept, it costs one scalar read per interval.
    # 0 disables (queue unbounded).
    tpu_dispatch_sync_interval: int = 32
    # streamed TPU-side ingest (io/ingest.py): value->bin mapping runs
    # on device as a jitted chunked kernel, raw row chunks stream
    # host->device double-buffered, and the feature-major bin matrix is
    # assembled directly on device — the full host bin matrix,
    # transpose and bulk upload disappear. Bit-exact against the host
    # binner. -1 = auto (on when running on a real TPU); 0 = off
    # (host binner); 1 = force on any backend (parity tests). Datasets
    # where EFB actually bundles take the host path regardless, so the
    # bundling decision and bundled matrix stay bit-identical.
    tpu_ingest: int = -1
    # rows per ingest pipeline chunk; 0 = auto (a power of two sized so
    # one chunk carries ~64 MB of raw values).
    tpu_ingest_chunk_rows: int = 0
    # out-of-core disk->device ingest (io/loader.py): the two-round
    # loader's round-2 row blocks feed the streamed device binner
    # (io/ingest.py IngestStream) directly, so the [F, N] device bin
    # matrix assembles without ever materializing the full host value
    # matrix — peak host RSS is bounded by the block size, not N.
    # Bit-exact against the in-memory loader (same mappers, same
    # value->bin kernel). -1 = auto (stream whenever the two-round
    # loader runs and the device binner is available); 0 = off (the
    # two-round loader materializes host bins, the pre-OOC behavior);
    # 1 = force the two-round streaming route for file loads even when
    # ``two_round`` is unset (parity tests, RSS-bounded ingest of
    # bigger-than-RAM files).
    tpu_out_of_core: int = -1
    # rows per out-of-core round-2 block (the loader's disk-read
    # granularity; the device binner re-chunks to its own pipeline
    # width downstream); 0 = auto (256k rows).
    tpu_ooc_block_rows: int = 0
    # hashed GOSS sampling (models/boosting.py): the top-gradient +
    # uniform-rest draw uses the shard-invariant lowbias32 hash of the
    # GLOBAL row index and a per-tree salt (the PR-4 bagging scheme)
    # instead of a positional PRNG, so the sampled mask is identical
    # under any row sharding/padding AND the sampler rides the fused
    # step as traced arrays — GOSS boosters become step-cache eligible
    # (windows 2+ retrain at 0.00 s compile). -1 = auto (hashed);
    # 0 = legacy positional PRNG sampler (the parity/repro oracle —
    # per-booster jit, step-cache ineligible); 1 = hashed.
    tpu_goss_hash: int = -1
    # process-wide compiled-step registry (ops/step_cache.py): the fused
    # training step becomes a pure function of an explicit geometry key
    # and the jitted callable is shared across boosters — a per-window
    # retrain loop (lrb.py) or a test suite compiles each distinct
    # geometry ONCE instead of once per booster. A registry hit is
    # bit-exact by construction (the key covers everything that shapes
    # the trace; data flows through traced arguments). -1 = auto (on);
    # 0 = off (per-booster closures, the pre-cache behavior); 1 = on.
    tpu_step_cache: int = -1
    # shape bucketing for the shared step (ops/step_cache.py): rows pad
    # up to this policy's width with a validity mask zeroing the pad
    # rows, the histogram bin axis pads to the next power of two and
    # the feature axis to a multiple of 8 (trivial-column exclusion and
    # observed bin counts make BOTH data-dependent), so boosters whose
    # data shapes land in the same buckets share ONE compiled step.
    # -1 = auto (rows: next power of two, min 256); 0 = exact shapes
    # everywhere (shared only between identically-shaped boosters);
    # N > 0 = rows round up to a multiple of N. Pad rows carry exact
    # +0.0 grad/hess and a zero bagging mask, pad bins/features are
    # masked per-feature via the traced metadata — histograms, root
    # aggregates, the stochastic-rounding stream and renew percentiles
    # are bit-identical to the exact-shape run.
    tpu_row_bucket: int = -1
    # process-wide geometry-keyed predict registry
    # (ops/predict_cache.py): the stacked predictor's dispatch becomes
    # a pure function of an explicit geometry key (table offsets,
    # padded split/leaf axes, classes, tree-chunk/step counts, row
    # bucket, device kind) held in a bounded LRU — a retrained model
    # with the same geometry (the sliding-window workload) hits a warm
    # compiled program instead of re-tracing, and the hit/miss/stack
    # counters make the reuse observable. -1 = auto (on); 0 = off
    # (per-model dispatch closures, no counters — jax's own trace
    # cache still dedupes identical shapes); 1 = on.
    tpu_predict_cache: int = -1
    # serving-batch shape buckets (ops/predict_cache.py
    # serve_bucket_rows): online predict batches pad up to this
    # policy's width so a live request stream (1..4096-row batches)
    # touches a handful of compiled programs instead of one per
    # distinct batch size. Bit-exact: rows are independent in every
    # predict kernel and pad rows are sliced off. -1 = auto (next
    # power of two, floor 16; pow2/16 steps above 16k); 0 = exact
    # shapes (one trace per batch size); N > 0 = round up to a
    # multiple of N.
    tpu_serve_bucket: int = -1
    # persistent XLA compile cache, backend-aware (ops/autotune.py
    # ensure_compile_cache): -1 = auto — wired on the TPU (where the
    # expensive Mosaic compiles live), off elsewhere; 1 = on
    # everywhere; 0 = off on every backend. A cache directory the
    # operator placed (JAX_COMPILATION_CACHE_DIR) always wins and is
    # used as is; otherwise the cache lives in the one fixed
    # <checkout>/.lgbm_tpu_cache. Replaces the CPU-only
    # tpu_compile_cache_cpu (accepted as a warned alias: its 1 maps to
    # 1, its 0 to the -1 auto default).
    tpu_compile_cache: int = -1
    # cross-thread span trace (obs/trace.py): write a Chrome
    # trace-event / Perfetto-loadable JSON here showing ingest worker
    # chunks, training iterations, step-cache compiles/hits, watchdog
    # firings and (lrb.py) per-window derive/train/evaluate spans with
    # correct pid/tid across threads. Flushed at run finish, after
    # every lrb window, and at interpreter exit. Empty = off.
    tpu_trace: str = ""
    # span-trace ring capacity in EVENTS (obs/trace.py): the buffer
    # keeps the most recent N events, so a million-iteration serving
    # loop traces its tail instead of growing without bound (dropped
    # count recorded in the file's metadata). Floor 1024.
    tpu_trace_buffer: int = 65536
    # live metrics export (obs/export.py): base path for periodic
    # registry snapshots — "<base>.prom" (Prometheus text exposition,
    # atomically replaced) and "<base>.jsonl" (append-only time
    # series) are written every tpu_metrics_interval_s DURING the run,
    # so a live loop can be watched without waiting for the run
    # report. A .prom/.jsonl suffix on the value is stripped.
    # Empty = off (unless tpu_metrics_port opens the HTTP endpoint).
    tpu_metrics_export: str = ""
    # seconds between exporter snapshots (obs/export.py); also the
    # flush cadence of the JSONL time series. Non-positive values fall
    # back to the 5.0 default; the exporter floors tiny values at 0.01.
    tpu_metrics_interval_s: float = 5.0
    # serve live metrics over HTTP (obs/export.py): a stdlib
    # http.server on 127.0.0.1:<port> answering GET /metrics
    # (Prometheus text), /metrics.json (raw snapshot), /healthz
    # (liveness + last-snapshot age + SLO budget state, JSON) and /slo
    # (the full error-budget report) while the process runs — point a
    # scraper or a fleet health check at a live training/serving loop.
    # 0 = off.
    tpu_metrics_port: int = 0
    # request-scoped wide-event log (obs/reqlog.py): JSONL path for
    # one structured record per serving request batch and per lrb
    # window — request id, latency, rows, serve bucket, the serving
    # model's window, degraded/staleness state. The in-memory ring
    # feeding the flight recorder is always on; this knob adds the
    # on-disk file. Empty = no file.
    tpu_reqlog: str = ""
    # fraction of request records written to the tpu_reqlog file,
    # decided DETERMINISTICALLY per request id (lowbias32 hash — the
    # same ids are sampled at the same rate on every run). Window and
    # degraded-window records are never sampled out. Clamped to [0, 1].
    tpu_reqlog_sample: float = 1.0
    # SLO specs (obs/slo.py), ";"-separated, evaluated every exporter
    # interval: e.g. "predict_p99_ms<50;staleness_windows<=2;
    # degraded_window_rate<0.05" (also hist:/gauge:/ratio: forms).
    # Compliance, remaining error budget and burn rate become slo/*
    # gauges, the /healthz + /slo endpoint bodies, and — on budget
    # exhaustion — a flight-recorder trigger. Empty = no SLOs.
    tpu_slo: str = ""
    # fleet scoring daemon (serve/daemon.py): TCP port for the
    # multi-tenant HTTP scoring endpoint on 127.0.0.1. 0 = ephemeral
    # (the OS picks; ScoringDaemon.http_port reports the bound port —
    # the tests' and lrb --serve-daemon's mode). The daemon only
    # starts when explicitly constructed (bench --fleet, lrb
    # --serve-daemon, or embedding code); this knob never opens a
    # socket by itself.
    tpu_fleet_port: int = 0
    # cross-request coalescer max wait in MICROSECONDS
    # (serve/coalescer.py): after the first request of a tick arrives,
    # the dispatcher lingers up to this long for more requests to
    # merge into the same pow2-bucketed device batch. Higher = bigger
    # batches (throughput), at up to this much added p50 latency.
    # Clamped to [0, 1e6]; 0 = dispatch immediately (coalescing only
    # what is already queued).
    tpu_fleet_coalesce_us: int = 2000
    # max coalesced rows dispatched per tenant per tick
    # (serve/coalescer.py). Requests beyond the cap stay queued for
    # the next tick, bounding both device-batch width and the
    # head-of-line latency a huge batch inflicts on neighbors.
    # Floor 1.
    tpu_fleet_max_batch: int = 4096
    # bounded coalescer admission queue, in REQUESTS
    # (serve/coalescer.py): submissions beyond this depth are refused
    # (HTTP 503 + Retry-After) instead of growing an unbounded buffer
    # — backpressure reaches the client as a retryable signal.
    # Floor 1.
    tpu_fleet_queue: int = 1024
    # per-tenant serving SLO: p99 latency target in MILLISECONDS for
    # the admission controller (serve/daemon.py). For every registered
    # tenant the daemon arms a "hist:fleet/tenant_latency_s/<t>:p99 <
    # target" spec on an obs/slo.py engine and sheds that tenant's
    # load when its error budget burns low (see
    # tpu_fleet_shed_budget). 0 = no admission SLO (never shed).
    # Negative values clamp to 0.
    tpu_fleet_slo_p99_ms: float = 0.0
    # admission-control shed threshold (serve/daemon.py): when a
    # tenant's remaining p99 error budget (obs/slo.py
    # budget_remaining, 1.0 = untouched, <= 0 = breached) falls to or
    # below this fraction, the daemon 429s that tenant's requests
    # BEFORE the budget exhausts, while other tenants keep serving.
    # Clamped to [0, 1]; 0 = shed only at breach.
    tpu_fleet_shed_budget: float = 0.25
    # flight recorder ring capacity (obs/flight.py): recent spans, log
    # lines and reqlog records kept in memory and dumped as ONE
    # postmortem JSON bundle when the watchdog fires, a fault
    # injects, an lrb window degrades, an SLO budget exhausts, or the
    # process gets SIGTERM/an uncaught exception. Bundles land next to
    # the run's artifacts (run report/reqlog/export/trace path), else
    # the system temp dir; run reports cross-link them as
    # meta.flight_dumps. 0 disables the black box.
    tpu_flight_buffer: int = 256
    # explicit flight-dump directory (obs/flight.py), overriding the
    # artifact-path default. Multi-process drivers
    # (parallel/elastic.py) point EVERY rank at one shared directory
    # so the incident sweep (obs/incident.py) can gather all ranks'
    # postmortem bundles into a single incident document. Empty = the
    # first configured artifact path's directory, else the temp dir.
    tpu_flight_dir: str = ""
    # cluster-scope metrics rollups (obs/clusterobs.py): each rank
    # publishes a compact metrics digest into the coordination-service
    # KV alongside its heartbeat, and rank 0's exporter merges them
    # into first-class cluster/* instruments (summed counters, true
    # cluster histogram quantiles, per-rank straggler gauges)
    # published through the usual Prometheus/JSONL//metrics surfaces.
    # -1 = auto (on whenever the run is multi-process AND a metrics
    # exporter is configured); 0 = off; 1 = force on.
    tpu_cluster_obs: int = -1
    # resumable checkpoints (utils/checkpoint.py): directory for
    # versioned JSON checkpoint bundles — the model text PLUS the
    # training state the model text lacks (iteration, bagging/feature/
    # GOSS/DART RNG streams, current bagging mask, early-stopping
    # bookkeeping, eval history, config fingerprint) — written
    # atomically every tpu_checkpoint_freq iterations by the gbdt.train
    # snapshot loop and engine.train, pruned to tpu_snapshot_keep. A
    # run resumed from a bundle continues BIT-IDENTICALLY to the
    # uninterrupted run (tests/test_faults.py kill-and-resume drill).
    # Empty = no checkpoints.
    tpu_checkpoint_dir: str = ""
    # iterations between checkpoint writes (0 = off). A failed write
    # warns and training continues — the previous complete checkpoint
    # is never corrupted (atomic replace).
    tpu_checkpoint_freq: int = 0
    # resume training from this checkpoint bundle (or directory — the
    # newest valid bundle wins; corrupt ones are skipped with a
    # warning). Refused with an actionable message when the training
    # config fingerprint differs. CLI analog of
    # GBDT.train(resume_from=...).
    tpu_resume_from: str = ""
    # model snapshots (save_period) AND checkpoint bundles retained;
    # older ones are pruned after each successful write (floor 1).
    tpu_snapshot_keep: int = 3
    # deterministic fault injection (utils/faults.py) for recovery
    # drills: "point@N[:action][;...]" — e.g.
    # "lrb.window_train@2:transient;train.iter@17:kill". Tests and
    # game-day drills only; empty = disarmed.
    tpu_faults: str = ""
    # seed for probability-based fault rules (point@p0.25) so drills
    # reproduce exactly
    tpu_fault_seed: int = 0
    # total attempts for transient-failure retries (utils/retry.py
    # bounded exponential backoff + jitter) on the ingest/transfer
    # seams and the lrb window-train path
    tpu_retry_attempts: int = 4
    # pipelined retrain-while-serve for the windowed LRB loop (lrb.py):
    # window K's training runs on a background trainer thread while the
    # main thread keeps ingesting window K+1's requests and deriving
    # its features; the finished model is published with an atomic
    # swap (a failed/degraded window publishes nothing — serving
    # continues on the previous model). Per-window results are
    # field-for-field identical to the sequential loop (model swaps
    # take effect at window boundaries either way). -1 = auto (on);
    # 0 = off (the strictly sequential derive->train->evaluate loop);
    # 1 = force on.
    tpu_lrb_pipeline: int = -1
    # device-resident ingest chunk ring (io/ingest.py ChunkRing) for
    # the per-window training matrix: each chunk slot's device buffers
    # stay resident across windows and only the bucketed live-row
    # region is re-uploaded (the chunk's pad tail — most of a
    # sample-sized window's padded chunk — never crosses the wire
    # again). Bit-identical bins; engages only when the streamed
    # device ingest path is active. -1 = auto (on); 0 = off (full
    # padded-chunk re-ingest every window); 1 = force on.
    tpu_lrb_ring: int = -1
    # sparse histogram kernel tier (ops/hist_wave.py
    # wave_histogram_sparse): wave histograms accumulate by
    # scatter/segment-sum over the nnz explicit entries (plus a
    # default-bin completion from per-leaf totals) instead of the
    # dense one-hot pass — O(nnz) histogram work for CSR-native
    # datasets (io/sparse.py). -1 = auto: engages when the dataset
    # carries sparse coordinates, density clears the autotune rule
    # (ops/autotune.py tune_hist_tier) AND tpu_quantized_hist is on —
    # integer accumulation is order-free, so the tier is BIT-equal to
    # the dense tier; 0 = off (dense tier even for CSR input);
    # 1 = force wherever structurally possible (serial learner, no EFB
    # bundles) — with f32 accumulation the default-bin completion
    # reassociates sums, so final-ulp histogram drift vs the dense
    # tier is possible (documented in docs/Design.md §5f).
    tpu_sparse: int = -1
    # multi-host cluster bootstrap (parallel/cluster.py): number of
    # JAX PROCESSES forming the training cluster. The reference's
    # num_machines counts socket peers on its TCP linkers; this counts
    # jax.distributed processes whose devices form ONE global mesh.
    # 0/1 = single-process (the virtual mesh path). Env twin
    # LGBM_TPU_NUM_MACHINES (launchers) outranks the knob.
    tpu_num_machines: int = 0
    # this process's rank in [0, tpu_num_machines); -1 = take it from
    # the LGBM_TPU_MACHINE_RANK env (how the drill launcher tells N
    # otherwise-identical workers apart)
    tpu_machine_rank: int = -1
    # coordinator address host:port (rank 0's reachable address — the
    # analog of the reference's machine_list first entry). Env twin
    # LGBM_TPU_COORDINATOR. Required when tpu_num_machines > 1.
    tpu_coordinator: str = ""
    # bounded deadline for cross-process sync points (cluster barriers,
    # the training-loop stall watchdog): a dead peer produces a
    # one-line error naming the rank within this budget, never an
    # indefinite hang. The spiritual successor of the reference's
    # ``time_out`` socket knob (minutes there, seconds here).
    tpu_collective_timeout_s: float = 60.0

    def __post_init__(self):
        self._raw_params: Dict[str, str] = {}

    # -- parsing ------------------------------------------------------------

    @staticmethod
    def key_alias_transform(key: str) -> str:
        """ParameterAlias::KeyAliasTransform (config_auto.cpp:4)."""
        k = key.strip().lower().replace("-", "_")
        return ALIAS_TABLE.get(k, k)

    @classmethod
    def str2map(cls, params: str) -> Dict[str, str]:
        """KV2Map over 'k1=v1 k2=v2' strings (src/io/config.cpp:9-36)."""
        out: Dict[str, str] = {}
        for token in params.replace("\n", " ").split():
            if "=" in token:
                k, v = token.split("=", 1)
                out[k] = v
            elif token:
                log.warning("Unknown parameter %s", token)
        return out

    def set(self, params: Dict[str, Any]) -> "Config":
        """Config::Set (src/io/config.cpp:153): alias-resolve, parse, check."""
        resolved: Dict[str, Any] = {}
        for k, v in params.items():
            nk = k.strip().lower().replace("-", "_")
            if nk in DEPRECATED_ALIASES:
                ck, remap = DEPRECATED_ALIASES[nk]
                nv = remap(v)
                log.warning("%s is deprecated; use %s (mapped %s=%s to "
                            "%s=%s)", nk, ck, nk, v, ck, nv)
                v = nv
            else:
                ck = self.key_alias_transform(k)
            if ck in resolved and str(resolved[ck]) != str(v):
                log.warning(
                    "%s is set with %s=%s, will be overridden by %s=%s",
                    ck, k, resolved[ck], k, v)
            resolved[ck] = v
        for k, v in resolved.items():
            self._set_one(k, v)
        self._raw_params.update({k: str(v) for k, v in resolved.items()})
        self.check_param_conflict()
        return self

    def _set_one(self, key: str, value: Any) -> None:
        if not hasattr(self, key):
            # Unknown keys warn (objective-specific passthrough keys allowed)
            log.warning("Unknown parameter: %s", key)
            return
        cur = getattr(self, key)
        try:
            if isinstance(cur, bool):
                setattr(self, key, _parse_bool(value))
            elif isinstance(cur, int):
                setattr(self, key, int(float(value)))
            elif isinstance(cur, float):
                setattr(self, key, float(value))
            elif isinstance(cur, list):
                setattr(self, key, _parse_list(key, value))
            else:
                setattr(self, key, str(value).strip())
        except (TypeError, ValueError) as e:
            log.fatal(f"Bad value for parameter {key}: {value!r} ({e})")

    # -- semantics ----------------------------------------------------------

    # accepted for reference compatibility but not implemented: warn
    # when set to a non-default value instead of silently ignoring
    _UNIMPLEMENTED = {
        "convert_model_language": "",
    }
    # subsumed by the TPU design (documented substitutions, not gaps)
    _SUBSUMED = {
        "num_threads": "XLA owns intra-op parallelism",
        "histogram_pool_size": "histogram pool lives in HBM "
                               "(preallocated, no LRU needed)",
        "gpu_platform_id": "device selection is jax's",
        "gpu_device_id": "device selection is jax's",
        "gpu_use_dp": "see tpu_use_dp",
        "local_listen_port": "collectives ride ICI/DCN via XLA",
        "time_out": "collectives ride ICI/DCN via XLA",
        "machine_list_filename": "host topology comes from the JAX "
                                 "runtime (jax.distributed), not a "
                                 "socket machine list",
        "machines": "host topology comes from the JAX runtime",
    }

    def check_param_conflict(self) -> None:
        """Config::CheckParamConflict (src/io/config.cpp:202)."""
        for key, default in self._UNIMPLEMENTED.items():
            if key in self._raw_params and getattr(self, key) != default:
                log.warning("Parameter %s is accepted for compatibility "
                            "but not implemented yet; it has no effect",
                            key)
        for key, why in self._SUBSUMED.items():
            if key in self._raw_params:
                log.debug("Parameter %s is subsumed by the TPU design: "
                          "%s", key, why)
        if "device_type" in self._raw_params:
            # explicit device routing (the reference's CPU/GPU switch,
            # .ci/test.sh GPU CI pattern): cpu routes the framework's
            # kernel-route decisions to the host XLA paths; tpu/gpu/cuda
            # clear that routing and REQUIRE an accelerator — asked for
            # explicitly and absent is fatal, never a quiet CPU run.
            # The routing lives in module state (utils/device.py).
            from .utils.device import (require_accelerator,
                                       set_config_platform)
            dt = self.device_type.lower()
            if dt == "cpu":
                set_config_platform("cpu")
            elif dt in ("gpu", "cuda", "tpu"):
                set_config_platform(None)
                require_accelerator(dt)
                if dt != "tpu":
                    log.info("device_type=%s maps to the accelerator "
                             "backend jax selected", dt)
            else:
                log.fatal(f"Unknown device type {self.device_type!r}")
        # reference value aliases first (GetTreeLearnerType,
        # src/io/config.cpp:57-74), THEN the whitelist — a ported
        # "data_parallel" config must select the data learner, not
        # fall through to serial
        tl = self.tree_learner.lower()
        self.tree_learner = {"serial": "serial",
                             "feature": "feature",
                             "feature_parallel": "feature",
                             "data": "data", "data_parallel": "data",
                             "voting": "voting",
                             "voting_parallel": "voting"}.get(tl, tl)
        if self.tree_learner not in ("serial", "feature", "data",
                                     "voting"):
            # warn here, not later in learner selection: the grower
            # factory (parallel/learners.py make_grower_for_mode) only
            # sees the mode after dataset construction, long after the
            # operator could still fix the config (the tpu_ingest
            # pattern below)
            log.warning("Unknown tree_learner %r (want one of "
                        "serial/feature/data/voting); using 'serial'",
                        self.tree_learner)
            self.tree_learner = "serial"
        if self.tpu_count_proxy not in (-1, 0, 1):
            log.warning("tpu_count_proxy=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_count_proxy)
            self.tpu_count_proxy = -1
        if self.tpu_packed_bins not in (-1, 0, 1):
            log.warning("tpu_packed_bins=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_packed_bins)
            self.tpu_packed_bins = -1
        # unsupported tier combinations fail HERE, at param-check time
        # with the knob names — not as a bare NotImplementedError from
        # the kernel dispatch mid-training (ops/hist_wave.py keeps the
        # raises as a backstop for direct kernel callers)
        if self.tpu_count_proxy == 1 and not self.tpu_quantized_hist:
            log.fatal("tpu_count_proxy=1 requires tpu_quantized_hist="
                      "true (the count-proxy tier rides the int8 "
                      "quantized histogram kernels); set "
                      "tpu_quantized_hist=true or drop tpu_count_proxy")
        if self.tpu_packed_bins == 1:
            if self.tpu_quantized_hist and self.tpu_count_proxy == 0:
                log.fatal("tpu_packed_bins=1 with tpu_quantized_hist "
                          "needs the count-proxy tier: leave "
                          "tpu_count_proxy enabled (-1/1) or drop "
                          "tpu_packed_bins")
            if not self.tpu_quantized_hist and not self.tpu_use_dp:
                log.fatal("tpu_packed_bins=1 needs the count-proxy "
                          "int8 tier (tpu_quantized_hist=true) or the "
                          "hi/lo exact tier (tpu_use_dp=true); "
                          "single-bf16 (tpu_use_dp=false) packed bins "
                          "are not implemented")
            if self.max_bin > 16:
                log.fatal(f"tpu_packed_bins=1 needs max_bin <= 16 "
                          f"(two 4-bit bins per byte); max_bin="
                          f"{self.max_bin}")
        if self.tpu_exact_tier not in ("", "hilo5", "hilo4", "hilo3"):
            log.warning("tpu_exact_tier=%r is not one of ''/hilo5/"
                        "hilo4/hilo3; using '' (auto)",
                        self.tpu_exact_tier)
            self.tpu_exact_tier = ""
        if self.tpu_hist_chunk < 0:
            log.warning("tpu_hist_chunk=%d is negative; using 0 "
                        "(auto)", self.tpu_hist_chunk)
            self.tpu_hist_chunk = 0
        if self.tpu_wave_size < 0:
            # the grower clamps the UPPER side against the active lane
            # cap (models/gbdt.py); a negative would flow through
            # ``tpu_wave_size or w_cap`` as a bogus wave width
            log.warning("tpu_wave_size=%d is negative; using 0 "
                        "(auto)", self.tpu_wave_size)
            self.tpu_wave_size = 0
        if self.tpu_stop_check_interval < 1:
            log.warning("tpu_stop_check_interval=%d is below the "
                        "floor; using 1 (check every iteration)",
                        self.tpu_stop_check_interval)
            self.tpu_stop_check_interval = 1
        if self.tpu_dispatch_sync_interval < 0:
            log.warning("tpu_dispatch_sync_interval=%d is negative; "
                        "using 0 (unbounded dispatch queue)",
                        self.tpu_dispatch_sync_interval)
            self.tpu_dispatch_sync_interval = 0
        if self.tpu_ingest_chunk_rows < 0:
            log.warning("tpu_ingest_chunk_rows=%d is negative; using "
                        "0 (auto-sized chunks)",
                        self.tpu_ingest_chunk_rows)
            self.tpu_ingest_chunk_rows = 0
        if self.tpu_quantized_psum not in (-1, 0, 1):
            log.warning("tpu_quantized_psum=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_quantized_psum)
            self.tpu_quantized_psum = -1
        if self.tpu_psum_wire not in (-1, 0, 1):
            log.warning("tpu_psum_wire=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_psum_wire)
            self.tpu_psum_wire = -1
        if self.tpu_async_psum not in (-1, 0, 1):
            log.warning("tpu_async_psum=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_async_psum)
            self.tpu_async_psum = -1
        if self.tpu_ckpt_async not in (-1, 0, 1):
            log.warning("tpu_ckpt_async=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_ckpt_async)
            self.tpu_ckpt_async = -1
        if self.tpu_ingest not in (-1, 0, 1):
            log.warning("tpu_ingest=%d is not one of -1/0/1; using -1 "
                        "(auto)", self.tpu_ingest)
            self.tpu_ingest = -1
        if self.tpu_out_of_core not in (-1, 0, 1):
            log.warning("tpu_out_of_core=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_out_of_core)
            self.tpu_out_of_core = -1
        if self.tpu_ooc_block_rows < 0:
            log.warning("tpu_ooc_block_rows=%d is negative; using 0 "
                        "(auto block size)", self.tpu_ooc_block_rows)
            self.tpu_ooc_block_rows = 0
        if self.tpu_goss_hash not in (-1, 0, 1):
            log.warning("tpu_goss_hash=%d is not one of -1/0/1; "
                        "using -1 (auto: hashed)", self.tpu_goss_hash)
            self.tpu_goss_hash = -1
        if self.tpu_watchdog_factor < 0:
            log.warning("tpu_watchdog_factor=%g is negative; disabling "
                        "the watchdog (0)", self.tpu_watchdog_factor)
            self.tpu_watchdog_factor = 0.0
        if self.tpu_profile_iters < 0:
            log.warning("tpu_profile_iters=%d is negative; tracing the "
                        "whole loop (0)", self.tpu_profile_iters)
            self.tpu_profile_iters = 0
        if self.tpu_autotune not in ("on", "off", "exhaustive"):
            log.warning("tpu_autotune=%r is not one of on/off/exhaustive;"
                        " using 'on'", self.tpu_autotune)
            self.tpu_autotune = "on"
        if self.tpu_step_cache not in (-1, 0, 1):
            log.warning("tpu_step_cache=%d is not one of -1/0/1; using "
                        "-1 (auto)", self.tpu_step_cache)
            self.tpu_step_cache = -1
        if self.tpu_row_bucket < -1:
            log.warning("tpu_row_bucket=%d is negative; using -1 "
                        "(power-of-two buckets)", self.tpu_row_bucket)
            self.tpu_row_bucket = -1
        if self.tpu_predict_cache not in (-1, 0, 1):
            log.warning("tpu_predict_cache=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_predict_cache)
            self.tpu_predict_cache = -1
        if self.tpu_serve_bucket < -1:
            log.warning("tpu_serve_bucket=%d is negative; using -1 "
                        "(power-of-two serve buckets)",
                        self.tpu_serve_bucket)
            self.tpu_serve_bucket = -1
        if self.tpu_compile_cache not in (-1, 0, 1):
            log.warning("tpu_compile_cache=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_compile_cache)
            self.tpu_compile_cache = -1
        if self.tpu_trace_buffer < 1024:
            log.warning("tpu_trace_buffer=%d is below the floor; "
                        "using 1024", self.tpu_trace_buffer)
            self.tpu_trace_buffer = 1024
        if self.tpu_checkpoint_freq < 0:
            log.warning("tpu_checkpoint_freq=%d is negative; disabling "
                        "checkpoints (0)", self.tpu_checkpoint_freq)
            self.tpu_checkpoint_freq = 0
        if self.tpu_checkpoint_freq > 0 and not self.tpu_checkpoint_dir:
            log.warning("tpu_checkpoint_freq=%d but tpu_checkpoint_dir "
                        "is empty; no checkpoints will be written",
                        self.tpu_checkpoint_freq)
        if self.tpu_snapshot_keep < 1:
            log.warning("tpu_snapshot_keep=%d is below the floor; "
                        "using 1", self.tpu_snapshot_keep)
            self.tpu_snapshot_keep = 1
        if self.tpu_retry_attempts < 1:
            log.warning("tpu_retry_attempts=%d is below the floor; "
                        "using 1 (no retries)", self.tpu_retry_attempts)
            self.tpu_retry_attempts = 1
        if self.tpu_lrb_pipeline not in (-1, 0, 1):
            log.warning("tpu_lrb_pipeline=%d is not one of -1/0/1; "
                        "using -1 (auto)", self.tpu_lrb_pipeline)
            self.tpu_lrb_pipeline = -1
        if self.tpu_lrb_ring not in (-1, 0, 1):
            log.warning("tpu_lrb_ring=%d is not one of -1/0/1; using "
                        "-1 (auto)", self.tpu_lrb_ring)
            self.tpu_lrb_ring = -1
        if self.tpu_sparse not in (-1, 0, 1):
            log.warning("tpu_sparse=%d is not one of -1/0/1; using -1 "
                        "(auto)", self.tpu_sparse)
            self.tpu_sparse = -1
        if self.tpu_num_machines < 0:
            log.warning("tpu_num_machines=%d is negative; using 0 "
                        "(single process)", self.tpu_num_machines)
            self.tpu_num_machines = 0
        if self.tpu_machine_rank < -1:
            log.warning("tpu_machine_rank=%d is below -1; using -1 "
                        "(take the rank from LGBM_TPU_MACHINE_RANK)",
                        self.tpu_machine_rank)
            self.tpu_machine_rank = -1
        if (self.tpu_num_machines > 1
                and self.tpu_machine_rank >= self.tpu_num_machines):
            log.fatal(f"tpu_machine_rank={self.tpu_machine_rank} is "
                      f"outside [0, tpu_num_machines="
                      f"{self.tpu_num_machines}) — every process needs "
                      f"a distinct rank below the world size")
        if self.tpu_collective_timeout_s <= 0:
            log.warning("tpu_collective_timeout_s=%g is not positive; "
                        "using 60.0", self.tpu_collective_timeout_s)
            self.tpu_collective_timeout_s = 60.0
        if not 0.0 < self.sparse_threshold <= 1.0:
            # the CSR route gate (io/sparse.py route_sparse): the
            # implicit fraction must reach this threshold
            log.warning("sparse_threshold=%g is outside (0, 1]; using "
                        "0.8", self.sparse_threshold)
            self.sparse_threshold = 0.8
        if self.tpu_metrics_interval_s <= 0:
            log.warning("tpu_metrics_interval_s=%g is not positive; "
                        "using 5.0", self.tpu_metrics_interval_s)
            self.tpu_metrics_interval_s = 5.0
        if not 0 <= self.tpu_metrics_port <= 65535:
            log.warning("tpu_metrics_port=%d is not a port; disabling "
                        "the metrics endpoint (0)", self.tpu_metrics_port)
            self.tpu_metrics_port = 0
        if not 0.0 <= self.tpu_reqlog_sample <= 1.0:
            log.warning("tpu_reqlog_sample=%g is outside [0, 1]; "
                        "clamping", self.tpu_reqlog_sample)
            self.tpu_reqlog_sample = min(
                max(self.tpu_reqlog_sample, 0.0), 1.0)
        if not 0 <= self.tpu_fleet_port <= 65535:
            log.warning("tpu_fleet_port=%d is not a port; using an "
                        "ephemeral port (0)", self.tpu_fleet_port)
            self.tpu_fleet_port = 0
        if not 0 <= self.tpu_fleet_coalesce_us <= 1_000_000:
            log.warning("tpu_fleet_coalesce_us=%d is outside [0, 1e6]; "
                        "clamping", self.tpu_fleet_coalesce_us)
            self.tpu_fleet_coalesce_us = min(
                max(self.tpu_fleet_coalesce_us, 0), 1_000_000)
        if self.tpu_fleet_max_batch < 1:
            log.warning("tpu_fleet_max_batch=%d is below the floor; "
                        "using 1", self.tpu_fleet_max_batch)
            self.tpu_fleet_max_batch = 1
        if self.tpu_fleet_queue < 1:
            log.warning("tpu_fleet_queue=%d is below the floor; "
                        "using 1", self.tpu_fleet_queue)
            self.tpu_fleet_queue = 1
        if self.tpu_fleet_slo_p99_ms < 0:
            log.warning("tpu_fleet_slo_p99_ms=%g is negative; disabling "
                        "the admission SLO (0)", self.tpu_fleet_slo_p99_ms)
            self.tpu_fleet_slo_p99_ms = 0.0
        if not 0.0 <= self.tpu_fleet_shed_budget <= 1.0:
            log.warning("tpu_fleet_shed_budget=%g is outside [0, 1]; "
                        "clamping", self.tpu_fleet_shed_budget)
            self.tpu_fleet_shed_budget = min(
                max(self.tpu_fleet_shed_budget, 0.0), 1.0)
        if self.tpu_flight_buffer < 0:
            log.warning("tpu_flight_buffer=%d is negative; disabling "
                        "the flight recorder (0)", self.tpu_flight_buffer)
            self.tpu_flight_buffer = 0
        if self.tpu_cluster_obs not in (-1, 0, 1):
            log.warning("tpu_cluster_obs=%d is not -1/0/1; using auto "
                        "(-1)", self.tpu_cluster_obs)
            self.tpu_cluster_obs = -1
        if self.tpu_slo:
            # refuse a malformed spec at config time, not in the
            # exporter thread mid-run (the parse error names the
            # offending fragment)
            from .obs.slo import parse_specs
            try:
                parse_specs(self.tpu_slo)
            except ValueError as e:
                log.warning("tpu_slo disabled: %s", e)
                self.tpu_slo = ""
        if self.is_provide_training_metric or self.valid:
            if not self.metric:
                # force defaults from objective later; handled by metric factory
                pass
        if self.num_machines > 1:
            if self.tree_learner == "serial":
                log.warning(
                    "num_machines>1 with serial tree learner; only one machine "
                    "will train")
        if self.tree_learner in ("data", "voting") and self.histogram_pool_size >= 0:
            log.warning(
                "Histogram LRU queue was enabled (histogram_pool_size=%g); "
                "will disable this for distributed learning",
                self.histogram_pool_size)
            self.histogram_pool_size = -1.0
        if self.boosting == "rf":
            if not (self.bagging_freq > 0 and 0.0 < self.bagging_fraction < 1.0):
                log.fatal("Random forest needs bagging_freq > 0 and "
                          "bagging_fraction in (0, 1)")
            if self.feature_fraction >= 1.0:
                # upstream requires feature_fraction < 1 OR bagging; bagging
                # is already enforced above so just warn
                pass
        if self.objective in ("lambdarank", "rank_xendcg") and self.num_class != 1:
            log.fatal("Ranking objectives don't support multiclass")
        if self.max_depth > 0 and self.num_leaves == 31:
            # reference caps leaves by depth implicitly during growth
            pass

    @property
    def device(self) -> str:
        return self.device_type

    def boosting_type(self) -> str:
        """GetBoostingType normalization (src/io/config.cpp:45)."""
        b = self.boosting
        if b in ("gbdt", "gbrt"):
            return "gbdt"
        if b in ("dart",):
            return "dart"
        if b in ("goss",):
            return "goss"
        if b in ("rf", "random_forest"):
            return "rf"
        log.fatal(f"Unknown boosting type {b}")

    def to_string(self) -> str:
        """Config::ToString — saved into the model file `parameters:` block."""
        lines = []
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, list):
                v = ",".join(str(x) for x in v)
            elif isinstance(v, bool):
                v = "1" if v else "0"
            lines.append(f"[{f.name}: {v}]")
        return "\n".join(lines)

    def copy(self) -> "Config":
        new = Config()
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            setattr(new, f.name, list(v) if isinstance(v, list) else v)
        new._raw_params = dict(self._raw_params)
        return new


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("true", "1", "+", "yes", "on"):
        return True
    if s in ("false", "0", "-", "no", "off"):
        return False
    raise ValueError(f"not a bool: {v!r}")


_INT_LIST_KEYS = {"monotone_constraints", "eval_at"}
_STR_LIST_KEYS = {"valid", "metric", "valid_data_initscores"}


def _parse_list(key: str, v: Any) -> list:
    if isinstance(v, (list, tuple)):
        items = list(v)
    else:
        items = [x for x in str(v).replace(";", ",").split(",") if x != ""]
    if key in _INT_LIST_KEYS:
        return [int(float(x)) for x in items]
    if key in _STR_LIST_KEYS:
        return [str(x).strip() for x in items]
    return [float(x) for x in items]


def param_dict_to_str(params: Optional[Dict[str, Any]]) -> str:
    """Python-side param dict → 'k=v' string (basic.py:123 semantics)."""
    if not params:
        return ""
    pairs = []
    for k, v in params.items():
        if isinstance(v, (list, tuple)):
            pairs.append(f"{k}={','.join(map(str, v))}")
        elif isinstance(v, bool):
            pairs.append(f"{k}={'true' if v else 'false'}")
        elif v is None:
            continue
        else:
            pairs.append(f"{k}={v}")
    return " ".join(pairs)
