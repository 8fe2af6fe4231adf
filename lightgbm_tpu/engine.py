"""Training routines: train() and cv().

TPU-native counterpart of the reference python engine
(reference: python-package/lightgbm/engine.py:19-332 train/cv,
engine.py:240-268 CVBooster). Continued training follows the reference
protocol: the init model's raw predictions are folded into the train
set's init_score (engine.py:122-135), and the returned booster holds
only the newly trained trees.
"""
from __future__ import annotations

import collections
import copy
from operator import attrgetter
from typing import Dict, List, Optional

import numpy as np

from . import callback
from .basic import Booster, Dataset, LightGBMError, _InnerPredictor

__all__ = ["train", "cv", "CVBooster"]

_NUM_BOOST_ROUND_ALIASES = [
    "num_iterations", "num_iteration", "n_iter", "num_tree", "num_trees",
    "num_round", "num_rounds", "num_boost_round", "n_estimators"]
_EARLY_STOP_ALIASES = [
    "early_stopping_round", "early_stopping_rounds", "early_stopping"]


def train(params: Dict, train_set: Dataset, num_boost_round: int = 100,
          valid_sets=None, valid_names=None, fobj=None, feval=None,
          init_model=None, feature_name="auto",
          categorical_feature="auto", early_stopping_rounds=None,
          evals_result=None, verbose_eval=True, learning_rates=None,
          keep_training_booster=False, callbacks=None) -> Booster:
    """Train one model (engine.py:19-238 semantics and defaults)."""
    params = copy.deepcopy(params) if params else {}
    for alias in _NUM_BOOST_ROUND_ALIASES:
        if alias in params:
            num_boost_round = int(params.pop(alias))
            break
    for alias in _EARLY_STOP_ALIASES:
        if alias in params and params[alias] is not None:
            early_stopping_rounds = int(params.pop(alias))
            break
    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")

    if isinstance(init_model, str):
        predictor = _InnerPredictor(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor = init_model._to_predictor()
    else:
        predictor = None
    init_iteration = predictor.num_total_iteration if predictor else 0

    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    train_set.params.update(params)
    train_set._set_predictor(predictor)
    train_set.set_feature_name(feature_name)
    train_set.set_categorical_feature(categorical_feature)

    is_valid_contain_train = False
    train_data_name = "training"
    reduced_valid_sets: List[Dataset] = []
    name_valid_sets: List[str] = []
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if isinstance(valid_names, str):
            valid_names = [valid_names]
        for i, valid_data in enumerate(valid_sets):
            if valid_data is train_set:
                is_valid_contain_train = True
                if valid_names is not None:
                    train_data_name = valid_names[i]
                continue
            if not isinstance(valid_data, Dataset):
                raise TypeError("Training only accepts Dataset object")
            valid_data.set_reference(train_set)
            reduced_valid_sets.append(valid_data)
            if valid_names is not None and len(valid_names) > i:
                name_valid_sets.append(valid_names[i])
            else:
                name_valid_sets.append("valid_" + str(i))

    if callbacks is None:
        callbacks = set()
    else:
        for i, cb in enumerate(callbacks):
            cb.__dict__.setdefault("order", i - len(callbacks))
        callbacks = set(callbacks)
    if verbose_eval is True:
        callbacks.add(callback.print_evaluation())
    elif isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool):
        callbacks.add(callback.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None:
        callbacks.add(callback.early_stopping(
            early_stopping_rounds, verbose=bool(verbose_eval)))
    if learning_rates is not None:
        callbacks.add(callback.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        callbacks.add(callback.record_evaluation(evals_result))

    # run report (obs/recorder.py): when tpu_run_report is set, a
    # RunRecorder spans the iterations via an internal after-iteration
    # callback (defined in the callback module, so the pipelined-eval
    # fast path stays eligible) and serializes the run at the end
    recorder = None
    run_report = str(params.get("tpu_run_report", "") or "")
    if run_report:
        from .obs.recorder import RunRecorder
        recorder = RunRecorder(
            path=run_report,
            watchdog_factor=float(
                params.get("tpu_watchdog_factor", 8.0) or 0.0),
            meta={"driver": "engine.train",
                  "num_boost_round": num_boost_round,
                  "init_iteration": init_iteration})
        callbacks.add(callback.record_run(recorder))

    callbacks_before_iter = sorted(
        (cb for cb in callbacks if getattr(cb, "before_iteration", False)),
        key=attrgetter("order"))
    callbacks_after_iter = sorted(
        (cb for cb in callbacks if not getattr(cb, "before_iteration",
                                               False)),
        key=attrgetter("order"))

    booster = Booster(params=params, train_set=train_set)
    if recorder is not None:
        # free-form env section of the report: the resolved mesh size
        # (the learner may have fallen back to serial on one device)
        recorder.meta["mesh_devices"] = booster.num_devices
        recorder.meta["tree_learner"] = booster.learner_mode
    if is_valid_contain_train:
        booster.set_train_data_name(train_data_name)
    for valid_set, name in zip(reduced_valid_sets, name_valid_sets):
        booster.add_valid(valid_set, name)
    booster.best_iteration = 0

    # xprof capture of the training loop (tpu_profile_dir +
    # tpu_profile_iters; obs/profiler.py — the device-level analog of
    # the utils/timing.py wall timers, readable with tensorboard/xprof)
    from .obs.profiler import ProfileWindow
    profile = ProfileWindow(
        str(params.get("tpu_profile_dir", "") or ""),
        int(params.get("tpu_profile_iters", 0) or 0))
    if recorder is not None:
        # started here (not at construction) so an exception during
        # booster/valid-set setup can't leak the log run-prefix
        recorder.start()
    try:
        evaluation_result_list = _train_loop(
            booster, params, init_iteration, num_boost_round,
            callbacks_before_iter, callbacks_after_iter, fobj, feval,
            valid_sets, is_valid_contain_train, profile,
            ckpt_dir=str(params.get("tpu_checkpoint_dir", "") or ""),
            ckpt_freq=int(params.get("tpu_checkpoint_freq", 0) or 0))
    finally:
        profile.close()
        if recorder is not None:
            # distributed runs: per-iteration leaf/wave counts and the
            # psum payload bytes (models/gbdt.py public helpers; one
            # stacked download, only paid when a report is written)
            leaves = waves = None
            try:
                if init_iteration == 0:
                    # continued training skips: the recorder's
                    # iteration keys start at init_iteration + 1 and
                    # would misalign with the group-0-based lists
                    leaves, waves = booster.leaves_and_waves()
                    if waves:
                        booster.record_comm_bytes(recorder, waves)
            except Exception:       # noqa: BLE001 — telemetry must
                pass                # never fail the training result
            try:
                from .ops import predict_cache, step_cache
                recorder.meta["step_cache"] = step_cache.stats()
                recorder.meta["predict_cache"] = predict_cache.stats()
            except Exception:       # noqa: BLE001
                pass
            recorder.finish(
                leaves_per_iteration=leaves or None,
                waves_per_iteration=waves or None,
                extra={"best_iteration": booster.best_iteration})
    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for dataset_name, eval_name, score, _ in evaluation_result_list:
        booster.best_score[dataset_name][eval_name] = score
    if not keep_training_booster:
        booster.free_dataset()
    return booster


def _train_loop(booster, params, init_iteration, num_boost_round,
                callbacks_before_iter, callbacks_after_iter, fobj,
                feval, valid_sets, is_valid_contain_train,
                profile=None, ckpt_dir: str = "", ckpt_freq: int = 0):
    evaluation_result_list: List[tuple] = []
    want_eval = valid_sets is not None or feval is not None
    # pipelined evaluation: when every metric evaluates on device
    # (Booster.eval_dispatch_async), iteration i's metric scalars are
    # fetched WHILE iteration i+1 computes, so per-round evaluation
    # (early stopping) costs copy latency, not training throughput.
    # Custom fevals need host scores -> synchronous path. USER
    # callbacks also force the synchronous path: under pipelining an
    # after-iteration callback for iteration i runs while the booster
    # already holds iteration i+1's tree, so a user callback that
    # snapshots the model or calls eval would silently observe the
    # lookahead iteration. The built-in callbacks (print/record/early
    # stopping) only read evaluation_result_list, which IS iteration
    # i's, so they pipeline safely.
    builtin_only = all(
        getattr(cb, "__module__", None) == callback.__name__
        for cb in callbacks_after_iter)
    pipelined = want_eval and feval is None and builtin_only
    end_iteration = init_iteration + num_boost_round
    pending = None                    # (iteration, async eval handles)

    def run_after_cbs(iteration, results):
        """True = early stop (the extra lookahead iteration, if any,
        is trimmed by the caller)."""
        nonlocal evaluation_result_list
        evaluation_result_list = results
        try:
            for cb in callbacks_after_iter:
                cb(callback.CallbackEnv(
                    model=booster, params=params, iteration=iteration,
                    begin_iteration=init_iteration,
                    end_iteration=end_iteration,
                    evaluation_result_list=results))
        except callback.EarlyStopException as early_stop:
            booster.best_iteration = early_stop.best_iteration + 1
            evaluation_result_list = early_stop.best_score
            return True
        return False

    for i in range(init_iteration, end_iteration):
        for cb in callbacks_before_iter:
            cb(callback.CallbackEnv(
                model=booster, params=params, iteration=i,
                begin_iteration=init_iteration,
                end_iteration=end_iteration,
                evaluation_result_list=None))

        if profile is not None:
            profile.iter_begin(i - init_iteration + 1)
        booster.update(fobj=fobj)
        if profile is not None:
            profile.iter_end(i - init_iteration + 1)
        # resumable checkpoint bundle (utils/checkpoint.py): atomic
        # write, pruned, warns-never-raises on failure. Written only
        # AFTER this iteration's evals are processed (the gbdt.train
        # flush-first rule): a bundle must never capture a tree an
        # early stop is about to roll back.
        ckpt_due = (ckpt_freq > 0 and ckpt_dir
                    and (i + 1 - init_iteration) % ckpt_freq == 0)

        handles = (booster.eval_dispatch_async(is_valid_contain_train)
                   if pipelined else None)
        if handles is None:
            pipelined = False
            results = []
            if want_eval:
                if is_valid_contain_train:
                    results.extend(booster.eval_train(feval))
                results.extend(booster.eval_valid(feval))
            if run_after_cbs(i, results):
                return evaluation_result_list
            if ckpt_due:
                booster.save_checkpoint(ckpt_dir)
            continue
        if pending is not None:
            pi, ph = pending
            if run_after_cbs(pi, booster.eval_materialize(ph)):
                # the lookahead iteration trained past the stop point
                booster.rollback_one_iter()
                return evaluation_result_list
        pending = (i, handles)
        if ckpt_due:
            # drain the one-deep lookahead so the stop decision for
            # THIS iteration lands before the bundle is written
            pi, ph = pending
            pending = None
            if run_after_cbs(pi, booster.eval_materialize(ph)):
                return evaluation_result_list
            booster.save_checkpoint(ckpt_dir)
    if pending is not None:
        pi, ph = pending
        run_after_cbs(pi, booster.eval_materialize(ph))
    return evaluation_result_list


class CVBooster:
    """Holds all fold boosters of a cv run (engine.py:240-268)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, fpreproc=None, stratified: bool = False,
                  shuffle: bool = True) -> CVBooster:
    """Fold construction (engine.py:271-324): group-aware for ranking,
    stratified for classification when requested."""
    full_data.construct()
    num_data = full_data.num_data()
    group = full_data.get_group()
    if folds is not None:
        if not hasattr(folds, "__iter__"):
            folds = folds.split(X=np.zeros(num_data),
                                y=full_data.get_label())
        else:
            # normalize: elements are either (train_idx, test_idx)
            # pairs (python convention) or bare TEST-index arrays (the
            # reference R package's folds semantics, lgb.cv.R) whose
            # train side is the complement
            all_idx = np.arange(num_data)
            norm = []
            for fd in folds:
                if (isinstance(fd, (tuple, list)) and len(fd) == 2
                        and all(hasattr(x, "__len__") for x in fd)):
                    norm.append((np.asarray(fd[0], np.int64),
                                 np.asarray(fd[1], np.int64)))
                else:
                    te = np.asarray(list(fd), np.int64)
                    norm.append((np.setdiff1d(all_idx, te), te))
            folds = norm
    elif group is not None:
        # ranking: keep queries intact per fold (GroupKFold analog)
        group = np.asarray(group, np.int64)
        flatted_group = np.repeat(np.arange(len(group)), group)
        try:
            from sklearn.model_selection import GroupKFold
            folds = GroupKFold(n_splits=nfold).split(
                X=np.zeros(num_data), groups=flatted_group)
        except ImportError:
            raise LightGBMError(
                "scikit-learn is required for group-aware cv")
    elif stratified:
        try:
            from sklearn.model_selection import StratifiedKFold
        except ImportError:
            raise LightGBMError(
                "scikit-learn is required for stratified cv")
        skf = StratifiedKFold(n_splits=nfold, shuffle=shuffle,
                              random_state=seed if shuffle else None)
        folds = skf.split(X=np.zeros(num_data), y=full_data.get_label())
    else:
        rng = np.random.default_rng(seed)
        randidx = (rng.permutation(num_data) if shuffle
                   else np.arange(num_data))
        kstep = int(num_data / nfold)
        test_id = [randidx[i * kstep:
                           (i + 1) * kstep if i + 1 < nfold else num_data]
                   for i in range(nfold)]
        folds = ((np.setdiff1d(randidx, tid, assume_unique=True), tid)
                 for tid in test_id)

    ret = CVBooster()
    for train_idx, test_idx in folds:
        train_sub = full_data.subset(np.sort(train_idx))
        valid_sub = full_data.subset(np.sort(test_idx))
        valid_sub.reference = train_sub
        if fpreproc is not None:
            train_sub, valid_sub, tparam = fpreproc(
                train_sub, valid_sub, params.copy())
        else:
            tparam = params
        cvbooster = Booster(params=tparam, train_set=train_sub)
        cvbooster.add_valid(valid_sub, "valid")
        ret.append(cvbooster)
    return ret


def _agg_cv_result(raw_results):
    """Aggregate per-fold eval results (engine.py:327-338)."""
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = one_line[1]
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k],
             float(np.std(v))) for k, v in cvmap.items()]


def cv(params: Dict, train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       early_stopping_rounds=None, fpreproc=None, verbose_eval=None,
       show_stdv: bool = True, seed: int = 0, callbacks=None) -> Dict:
    """K-fold cross-validation (engine.py:341-501); returns the
    eval-history dict {metric-mean: [...], metric-stdv: [...]}."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    params = copy.deepcopy(params) if params else {}
    for alias in _NUM_BOOST_ROUND_ALIASES:
        if alias in params:
            num_boost_round = int(params.pop(alias))
            break
    for alias in _EARLY_STOP_ALIASES:
        if alias in params and params[alias] is not None:
            early_stopping_rounds = int(params.pop(alias))
            break
    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")
    if metrics is not None:
        params["metric"] = metrics

    if isinstance(init_model, str):
        predictor = _InnerPredictor(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor = init_model._to_predictor()
    else:
        predictor = None

    if train_set.get_label() is None and not isinstance(train_set.data, str):
        raise LightGBMError("Labels should not be None")
    train_set.params.update(params)
    train_set._set_predictor(predictor)
    train_set.set_feature_name(feature_name)
    train_set.set_categorical_feature(categorical_feature)
    if train_set.free_raw_data and not isinstance(train_set.data, str):
        # cv needs raw rows for fold subsets
        train_set.free_raw_data = False

    if stratified and params.get("objective") not in (
            "binary", "multiclass", "multiclassova", None) \
            and train_set.get_group() is None:
        stratified = False

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, folds, nfold, params, seed,
                            fpreproc=fpreproc, stratified=stratified,
                            shuffle=shuffle)

    if callbacks is None:
        callbacks = set()
    else:
        for i, cb in enumerate(callbacks):
            cb.__dict__.setdefault("order", i - len(callbacks))
        callbacks = set(callbacks)
    if early_stopping_rounds is not None:
        callbacks.add(callback.early_stopping(
            early_stopping_rounds, verbose=False))
    if verbose_eval is True:
        callbacks.add(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and not isinstance(verbose_eval,
                                                          bool):
        callbacks.add(callback.print_evaluation(verbose_eval, show_stdv))

    callbacks_before_iter = sorted(
        (cb for cb in callbacks if getattr(cb, "before_iteration", False)),
        key=attrgetter("order"))
    callbacks_after_iter = sorted(
        (cb for cb in callbacks if not getattr(cb, "before_iteration",
                                               False)),
        key=attrgetter("order"))

    for i in range(num_boost_round):
        for cb in callbacks_before_iter:
            cb(callback.CallbackEnv(
                model=cvfolds, params=params, iteration=i,
                begin_iteration=0, end_iteration=num_boost_round,
                evaluation_result_list=None))
        cvfolds.update(fobj=fobj)
        res = _agg_cv_result(cvfolds.eval_valid(feval))
        for _, key, mean, _, std in res:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in callbacks_after_iter:
                cb(callback.CallbackEnv(
                    model=cvfolds, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=res))
        except callback.EarlyStopException as early_stop:
            cvfolds.best_iteration = early_stop.best_iteration + 1
            for k in list(results):
                results[k] = results[k][:cvfolds.best_iteration]
            break
    return dict(results)
