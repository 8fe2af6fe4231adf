"""User-facing Dataset / Booster wrappers.

TPU-native counterpart of the reference python ``basic.py``
(reference: python-package/lightgbm/basic.py:626 Dataset,
basic.py:1450 Booster). The reference routes everything through the C
API (``_LIB``); here the Python objects sit directly on the in-process
engine (io.TpuDataset, models.GBDT) — same surface, no FFI hop. The
``lightgbm_tpu.capi`` module provides the C-API-shaped entry points for
code that wants them.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .io.dataset import Metadata, TpuDataset
from .metrics import create_metrics
from .objectives import create_objective
from .utils import log
from .utils.log import LightGBMError

__all__ = ["Dataset", "Booster", "LightGBMError"]


def _is_pandas_df(data) -> bool:
    try:
        import pandas as pd
        return isinstance(data, pd.DataFrame)
    except ImportError:
        return False


def _is_pandas_series(data) -> bool:
    try:
        import pandas as pd
        return isinstance(data, pd.Series)
    except ImportError:
        return False


def _is_scipy_sparse(data) -> bool:
    try:
        import scipy.sparse as sp
        return sp.issparse(data)
    except ImportError:
        return False


def keeps_float32(data) -> bool:
    """True when ``data`` goes through ``Dataset`` and ``predict`` as the
    float32 it is: a C-contiguous 2-D float32 ndarray. The device binner
    then makes its keys ON the device from the raw bits (io/ingest.py,
    "exactness": the float64 route's bins bit for bit, half the bytes on
    the wire, no float64 copy of the matrix on the host). Every other
    input becomes float64, as ever. Callers that hold tens of GB ask
    this before they build the matrix (benchmark/kinds/train_loop_dp.py)."""
    return (isinstance(data, np.ndarray) and data.dtype == np.float32
            and data.ndim == 2 and data.flags.c_contiguous)


def _data_to_2d(data, feature_name="auto", categorical_feature="auto"):
    """Normalize input to (ndarray[N, F] float64, or the float32 matrix
    itself where ``keeps_float32``; feature_names, categorical_indices).
    Pandas categorical/object columns are factorized like the
    reference's pandas handling (basic.py _data_from_pandas)."""
    cat_idx: List[int] = []
    names: Optional[List[str]] = None
    if _is_pandas_df(data):
        import pandas as pd
        df = data
        if feature_name == "auto":
            names = [str(c) for c in df.columns]
        cat_cols = [i for i, c in enumerate(df.columns)
                    if isinstance(df[c].dtype, pd.CategoricalDtype)
                    or df[c].dtype == object]
        if categorical_feature == "auto":
            cat_idx = cat_cols
        X = np.empty((len(df), df.shape[1]), np.float64)
        for i, c in enumerate(df.columns):
            col = df[c]
            if isinstance(col.dtype, pd.CategoricalDtype):
                codes = col.cat.codes.to_numpy(np.float64)
            elif col.dtype == object:
                codes = pd.Categorical(col).codes.astype(np.float64)
            else:
                X[:, i] = col.to_numpy(np.float64)
                continue
            # cat code -1 means missing -> NaN (reference
            # _data_from_pandas maps it back before binning)
            X[:, i] = np.where(codes < 0, np.nan, codes)
    elif _is_scipy_sparse(data):
        # CSR-native: scipy input stays O(nnz) (io/sparse.py); the
        # densify-vs-CSR route decision is TpuDataset's (it has the
        # config), and the predict paths densify in bounded chunks
        from .io.sparse import SparseMatrix
        X = SparseMatrix.from_scipy(data)
    elif keeps_float32(data):
        X = data
    else:
        X = np.asarray(data, np.float64)
        if X.ndim == 1:
            X = X.reshape(-1, 1)
    if isinstance(feature_name, (list, tuple)):
        names = [str(x) for x in feature_name]
    if isinstance(categorical_feature, (list, tuple)):
        resolved = []
        for c in categorical_feature:
            if isinstance(c, str):
                if names is None or c not in names:
                    raise LightGBMError(
                        f"categorical_feature {c!r} not found in "
                        "feature names")
                resolved.append(names.index(c))
            else:
                resolved.append(int(c))
        cat_idx = resolved
    return X, names, sorted(set(cat_idx))


def _label_to_1d(y) -> np.ndarray:
    if _is_pandas_df(y):
        if y.shape[1] != 1:
            raise LightGBMError("DataFrame for label should be 1-D")
        y = y.iloc[:, 0]
    if _is_pandas_series(y):
        y = y.to_numpy()
    return np.asarray(y, np.float32).reshape(-1)


class Dataset:
    """Dataset for training/validation (basic.py:626-1448 surface).

    Lazily constructed: binning happens on first use (``construct``),
    so ``set_*`` calls and reference linking behave like the C engine's
    deferred ``Dataset::Construct``.
    """

    def __init__(self, data, label=None, reference: "Dataset" = None,
                 weight=None, group=None, init_score=None,
                 feature_name="auto", categorical_feature="auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, silent: bool = False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self.used_indices: Optional[np.ndarray] = None
        self._inner: Optional[TpuDataset] = None
        self._predictor = None      # init-model predictor for init_score

    # -- construction -------------------------------------------------------

    def construct(self) -> "Dataset":
        if self._inner is not None:
            return self
        cfg = Config()
        ref = self.reference
        if ref is not None:
            ref.construct()
            cfg = ref._inner.config
        if self.params:
            cfg = cfg.copy() if ref is not None else cfg
            cfg.set(self.params)

        raw_X = None
        if isinstance(self.data, str):
            from .io.loader import DatasetLoader
            loader = DatasetLoader(cfg)
            self._inner = loader.load_from_file(
                self.data, reference=ref._inner if ref else None)
            if self.label is not None:
                self._inner.metadata.label = _label_to_1d(self.label)
            if self._predictor is not None:
                raw_X, _ = loader.load_predict_matrix(
                    self.data, self._inner.num_total_features)
        else:
            X, names, cat_idx = _data_to_2d(
                self.data, self.feature_name, self.categorical_feature)
            if self.used_indices is not None:
                X = X[self.used_indices]
            meta = self._build_metadata()
            if ref is not None:
                self._inner = ref._inner.create_valid(X, meta)
            else:
                ds = TpuDataset(cfg)
                ds.construct_from_matrix(X, meta, categorical=cat_idx,
                                         feature_names=names)
                self._inner = ds
            raw_X = X
        if self._predictor is not None and raw_X is not None:
            self._apply_init_score_from_predictor(raw_X)
        if self.free_raw_data:
            self.data = None
        return self

    def _build_metadata(self) -> Metadata:
        sub = self.used_indices
        label = (None if self.label is None else _label_to_1d(self.label))
        weight = (None if self.weight is None
                  else np.asarray(self.weight, np.float32).reshape(-1))
        init = (None if self.init_score is None
                else np.asarray(self.init_score, np.float64))
        group = (None if self.group is None
                 else np.asarray(self.group, np.int64).reshape(-1))
        if sub is not None:
            if label is not None:
                label = label[sub]
            if weight is not None:
                weight = weight[sub]
            if init is not None:
                init = init.reshape(len(init), -1)[sub].reshape(-1)
            if group is not None:
                # per-query membership counts (Metadata::Init subset
                # path, metadata.cpp:97-115); group-aware folds keep
                # queries intact so nonzero counts are whole queries
                qb = np.concatenate([[0], np.cumsum(group)])
                qidx = np.searchsorted(qb, sub, side="right") - 1
                counts = np.bincount(qidx, minlength=len(group))
                group = counts[counts > 0]
        return Metadata(label=label, weight=weight, group=group,
                        init_score=init)

    def _apply_init_score_from_predictor(self, raw_X: np.ndarray):
        """Continued training: fold an init model's raw scores into this
        dataset's init_score (basic.py _set_init_score_by_predictor).
        The pre-fold init score is kept so a later predictor swap
        rebases instead of stacking."""
        if not hasattr(self, "_base_init_score"):
            self._base_init_score = self._inner.metadata.init_score
        raw = self._predictor.init_score_for(raw_X)
        base = self._base_init_score
        self._inner.metadata.init_score = (
            raw if base is None else np.asarray(base, np.float64) + raw)

    def _set_predictor(self, predictor) -> None:
        if predictor is self._predictor:
            return
        self._predictor = predictor
        if self._inner is not None and predictor is not None:
            # already constructed (e.g. second train() on the same
            # Dataset): fold now, using the retained raw data
            if self.data is None:
                raise LightGBMError(
                    "Cannot set init model on a constructed Dataset "
                    "whose raw data was freed; use free_raw_data=False")
            if isinstance(self.data, str):
                from .io.loader import DatasetLoader
                loader = DatasetLoader(self._inner.config)
                raw_X, _ = loader.load_predict_matrix(
                    self.data, self._inner.num_total_features)
            else:
                raw_X, _, _ = _data_to_2d(self.data, self.feature_name,
                                          self.categorical_feature)
                if self.used_indices is not None:
                    raw_X = raw_X[self.used_indices]
            self._apply_init_score_from_predictor(raw_X)

    # -- field access (basic.py set_field/get_field) ------------------------

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._inner is not None and label is not None:
            self._inner.metadata.label = _label_to_1d(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None and weight is not None:
            self._inner.metadata.weights = np.asarray(
                weight, np.float32).reshape(-1)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None and group is not None:
            g = np.asarray(group, np.int64).reshape(-1)
            self._inner.metadata.query_boundaries = np.concatenate(
                [[0], np.cumsum(g)]).astype(np.int64)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None and init_score is not None:
            self._inner.metadata.init_score = np.asarray(
                init_score, np.float64)
        return self

    def get_label(self):
        if self._inner is not None:
            return self._inner.metadata.label
        return None if self.label is None else _label_to_1d(self.label)

    def get_weight(self):
        if self._inner is not None:
            return self._inner.metadata.weights
        return self.weight

    def get_init_score(self):
        if self._inner is not None:
            return self._inner.metadata.init_score
        return self.init_score

    def get_group(self):
        if self._inner is not None:
            qb = self._inner.metadata.query_boundaries
            return None if qb is None else np.diff(qb)
        return self.group

    def get_field(self, field_name: str):
        getter = {"label": self.get_label, "weight": self.get_weight,
                  "init_score": self.get_init_score,
                  "group": self.get_group}.get(field_name)
        if getter is None:
            raise LightGBMError(f"Unknown field {field_name!r}")
        return getter()

    def set_field(self, field_name: str, data) -> "Dataset":
        setter = {"label": self.set_label, "weight": self.set_weight,
                  "init_score": self.set_init_score,
                  "group": self.set_group}.get(field_name)
        if setter is None:
            raise LightGBMError(f"Unknown field {field_name!r}")
        return setter(data)

    # -- shape --------------------------------------------------------------

    def num_data(self) -> int:
        self.construct()
        return self._inner.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._inner.num_total_features

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._inner.feature_names)

    # -- derived datasets ---------------------------------------------------

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """Validation set binned with this Dataset's mappers
        (basic.py:866-900)."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params,
                       free_raw_data=self.free_raw_data)

    def subset(self, used_indices: Sequence[int],
               params=None) -> "Dataset":
        """Row subset sharing this Dataset's raw data and bin mappers
        (basic.py:902-926). Requires raw data (free_raw_data=False) or a
        not-yet-constructed Dataset."""
        if self.data is None:
            raise LightGBMError(
                "Cannot subset a Dataset whose raw data was freed; "
                "construct with free_raw_data=False")
        ret = Dataset(self.data, label=self.label,
                      reference=self if self._inner is not None else None,
                      weight=self.weight, group=self.group,
                      init_score=self.init_score,
                      feature_name=self.feature_name,
                      categorical_feature=self.categorical_feature,
                      params=params or self.params,
                      free_raw_data=self.free_raw_data)
        ret.used_indices = np.sort(np.asarray(used_indices, np.int64))
        ret._predictor = self._predictor
        return ret

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if reference is self.reference:
            return self
        if self._inner is not None:
            raise LightGBMError("Cannot set reference after the dataset "
                                "was constructed")
        self.reference = reference
        return self

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        self._inner.save_binary(filename)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if categorical_feature == "auto":
            # 'auto' means "keep what the Dataset already has"
            # (reference basic.py:1040-1053)
            return self
        if self._inner is not None and \
                list(categorical_feature) != list(
                    self.categorical_feature or []):
            raise LightGBMError("Cannot change categorical_feature after "
                                "the dataset was constructed")
        self.categorical_feature = categorical_feature
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        self.feature_name = feature_name
        if self._inner is not None and isinstance(feature_name,
                                                  (list, tuple)):
            if len(feature_name) != self._inner.num_total_features:
                raise LightGBMError("Length of feature names doesn't equal "
                                    "with num_feature")
            self._inner.feature_names = [str(x) for x in feature_name]
        return self


# -- default metric resolution (src/io/config.cpp GetMetricType) ------------

_DEFAULT_METRIC = {
    "regression": "l2", "regression_l2": "l2", "mean_squared_error": "l2",
    "l2_root": "rmse", "rmse": "rmse",
    "regression_l1": "l1", "mean_absolute_error": "l1",
    "huber": "huber", "fair": "fair", "poisson": "poisson",
    "quantile": "quantile", "mape": "mape", "gamma": "gamma",
    "tweedie": "tweedie",
    "binary": "binary_logloss",
    "multiclass": "multi_logloss", "softmax": "multi_logloss",
    "multiclassova": "multi_logloss", "ova": "multi_logloss",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda",
    "xentlambda": "cross_entropy_lambda",
    "lambdarank": "ndcg",
}


def _resolve_metric_names(cfg: Config) -> List[str]:
    names = [n for n in cfg.metric if n]
    if not names:
        default = _DEFAULT_METRIC.get(cfg.objective)
        return [default] if default else []
    if all(n.lower() in ("none", "null", "na", "custom") for n in names):
        return []
    return names


class Booster:
    """Booster: the trained model driver (basic.py:1450-2415 surface)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        from .models.gbdt import GBDT
        self.params = dict(params) if params else {}
        self.train_set = train_set
        self.valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_data_name = "training"
        self._gbdt: Optional[GBDT] = None
        self.pandas_categorical = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance, "
                                f"met {type(train_set).__name__}")
            self._init_from_train_set(train_set)
        elif model_file is not None:
            with open(model_file) as fh:
                model_str = fh.read()
            self._init_from_string(model_str)
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster "
                            "instance")

    # -- init ---------------------------------------------------------------

    def _init_from_train_set(self, train_set: Dataset):
        from .models.boosting import create_boosting
        cfg = Config()
        cfg.set(self.params)
        if cfg.verbosity < 1:
            from .utils.log import set_level
            set_level(max(-1, cfg.verbosity))
        train_set.params = {**self.params, **train_set.params}
        train_set.construct()
        inner = train_set._inner
        objective = create_objective(cfg.objective, cfg)
        if objective is not None:
            objective.init(inner.metadata, inner.num_data)
        self._metric_names = _resolve_metric_names(cfg)
        train_metrics = create_metrics(self._metric_names, cfg,
                                       inner.metadata, inner.num_data)
        self.config = cfg
        self._gbdt = create_boosting(cfg.boosting_type())
        self._gbdt.init(cfg, inner, objective, train_metrics)

    def _init_from_string(self, model_str: str):
        from .models.gbdt import GBDT
        self.config = None
        self._gbdt = GBDT().load_model_from_string(model_str)
        self._metric_names = []

    # -- training -----------------------------------------------------------

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if self._gbdt is None or self.train_set is None:
            raise LightGBMError("Add valid data requires a Booster with "
                                "training data")
        # late-link like basic.py:1540 (valid must share bin mappers);
        # raises if the data was already constructed with other mappers
        data.set_reference(self.train_set)
        # valid sets inherit the train set's init predictor so their
        # scores include the init model (reference set_reference chain)
        data._set_predictor(self.train_set._predictor)
        data.construct()
        metrics = create_metrics(self._metric_names, self.config,
                                 data._inner.metadata, data._inner.num_data)
        self._gbdt.add_valid_data(data._inner, metrics, name)
        self.valid_sets.append(data)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None,
               fobj=None) -> bool:
        """One boosting iteration; True when no further split was
        possible (basic.py:1693-1746)."""
        if train_set is not None and train_set is not self.train_set:
            raise LightGBMError("Replacing the train set mid-training is "
                                "not supported; create a new Booster")
        if fobj is None:
            return self._gbdt.train_one_iter()
        grad, hess = fobj(self.__inner_predict(0), self.train_set)
        return self.__boost(grad, hess)

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, np.float32)
        hess = np.asarray(hess, np.float32)
        k = self._gbdt.num_tree_per_iteration
        n = self._gbdt._n
        if grad.size != k * n:
            raise ValueError(
                f"Lengths of gradient({grad.size}) don't equal to "
                f"num_data*num_class({k * n})")
        return self._gbdt.train_one_iter(grad.reshape(k, n),
                                         hess.reshape(k, n))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """ResetConfig subset: training-time resettable parameters
        (gbdt.cpp ResetConfig)."""
        if self.config is not None:
            self.config.set(params)
            self._gbdt.shrinkage_rate = self.config.learning_rate
            self._gbdt._setup_grower()
        self.params.update(params)
        return self

    # -- evaluation ---------------------------------------------------------

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def eval_train(self, feval=None) -> List[tuple]:
        return self.__eval(0, self._train_data_name, feval)

    def eval_valid(self, feval=None) -> List[tuple]:
        out = []
        for i, name in enumerate(self.name_valid_sets):
            out.extend(self.__eval(i + 1, name, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None) -> List[tuple]:
        if data is self.train_set:
            return self.eval_train(feval)
        for i, vs in enumerate(self.valid_sets):
            if data is vs:
                return self.__eval(i + 1, name, feval)
        raise LightGBMError("Data should be added with add_valid first")

    def __eval(self, data_idx: int, name: str, feval=None) -> List[tuple]:
        out = [(name, mname, val, bigger)
               for mname, val, bigger in self._gbdt.get_eval_at(data_idx)]
        if feval is not None:
            ds = self.train_set if data_idx == 0 \
                else self.valid_sets[data_idx - 1]
            ret = feval(self.__inner_predict(data_idx), ds)
            if isinstance(ret, list):
                for fname, val, bigger in ret:
                    out.append((name, fname, val, bigger))
            elif ret is not None:
                fname, val, bigger = ret
                out.append((name, fname, val, bigger))
        return out

    def eval_dispatch_async(self, include_train: bool):
        """Dispatch this round's evaluations as device reductions and
        begin their host copies WITHOUT blocking; returns opaque
        handles for eval_materialize, or None when any dataset's
        metrics lack device implementations.

        The engine's training loop uses this to pipeline: iteration
        i+1's fused step overlaps the copy that fetches iteration i's
        metric scalars, so per-iteration evaluation (early stopping)
        costs latency, not throughput."""
        idxs = ([(0, self._train_data_name)] if include_train else [])
        idxs += [(i + 1, nm) for i, nm in enumerate(self.name_valid_sets)]
        if not idxs:
            return None
        g = self._gbdt
        handles = []
        for di, name in idxs:
            metrics = (g.training_metrics if di == 0
                       else g.valid_metrics[di - 1])
            fn = g._device_eval_fn(di, metrics)
            if fn is None:
                return None
            scores = (g.train_scores() if di == 0
                      else g._valid_scores[di - 1])
            arr = fn(scores)
            try:
                arr.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
            handles.append((name, metrics, arr))
        return handles

    @staticmethod
    def eval_materialize(handles) -> List[tuple]:
        """Block on eval_dispatch_async handles -> the evaluation
        result list [(data_name, metric_name, value, bigger_better)]."""
        out = []
        for name, metrics, arr in handles:
            vals = np.asarray(arr)
            out.extend((name, m.name, float(v), m.bigger_is_better)
                       for m, v in zip(metrics, vals))
        return out

    def __inner_predict(self, data_idx: int) -> np.ndarray:
        """Raw scores for train (0) or valid set (1..); flattened
        class-major for multiclass like the reference."""
        scores = (self._gbdt.train_scores() if data_idx == 0
                  else self._gbdt._valid_scores[data_idx - 1])
        raw = np.asarray(scores, np.float64)
        return raw[0] if raw.shape[0] == 1 else raw.reshape(-1)

    # -- prediction ---------------------------------------------------------

    def predict(self, data, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, data_has_header: bool = False,
                is_reshape: bool = True, **kwargs) -> np.ndarray:
        if isinstance(data, str):
            from .io.loader import DatasetLoader
            cfg = Config()
            cfg.header = data_has_header
            loader = DatasetLoader(cfg)
            X, _ = loader.load_predict_matrix(
                data, self._gbdt.max_feature_idx + 1)
        else:
            X, _, _ = _data_to_2d(data)
        if num_iteration < 0 and self.best_iteration > 0:
            num_iteration = self.best_iteration
        pred_kw = {k: v for k, v in kwargs.items()
                   if k.startswith("pred_early_stop")}
        if pred_leaf:
            return self._gbdt.predict_leaf_index(X, num_iteration)
        if pred_contrib:
            return self._gbdt.predict_contrib(X, num_iteration)
        if raw_score:
            return self._gbdt.predict_raw(X, num_iteration, **pred_kw)
        return self._gbdt.predict(X, num_iteration, **pred_kw)

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit the existing model's leaf values on new data
        (basic.py Booster.refit -> GBDT::RefitTree)."""
        from .models.gbdt import GBDT
        X, _, _ = _data_to_2d(data)
        y = _label_to_1d(label)
        cfg = Config()
        params = dict(self.params)
        params.pop("refit_decay_rate", None)
        cfg.set(params)
        cfg.refit_decay_rate = decay_rate
        if not params.get("objective") and self._gbdt.objective is not None:
            cfg.objective = self._gbdt.objective.name
        model_str = self.model_to_string()
        new = GBDT()
        new.load_model_from_string(model_str)
        # categorical columns are recoverable from the model header:
        # categorical feature_infos are ':'-joined category lists,
        # numerical are '[lo:hi]' ranges (io/dataset.py feature_infos)
        cats = [i for i, info in enumerate(new.feature_infos)
                if info and info != "none" and not info.startswith("[")]
        inner = TpuDataset(cfg).construct_from_matrix(
            X, Metadata(label=y), categorical=cats)
        objective = create_objective(cfg.objective, cfg)
        if objective is not None:
            objective.init(inner.metadata, inner.num_data)
        new.init_from_loaded(cfg, inner, objective, [])
        new.refit_existing(decay_rate)
        out = Booster(model_str=model_str)   # normal ctor: one source
        out._gbdt = new                      # of truth for attributes
        out.params = params
        out.config = cfg
        out.pandas_categorical = self.pandas_categorical
        return out

    # -- introspection ------------------------------------------------------

    def current_iteration(self) -> int:
        return self._gbdt.current_iteration

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    @property
    def num_devices(self) -> int:
        """Devices the training step spans (mesh size for the sharded
        tree learners, 1 for serial)."""
        return self._gbdt.num_devices

    @property
    def learner_mode(self) -> str:
        """Resolved tree learner (may be 'serial' after fallback)."""
        return self._gbdt.learner_mode

    def device_report(self) -> dict:
        """What the training engine resolved to on this process's
        devices (route, tier, mesh, shard placement) — see
        GBDT.device_report."""
        return self._gbdt.device_report()

    def leaves_and_waves(self, start_group: int = 0):
        """Per-iteration leaf/wave counts (ONE stacked download) —
        the public reporting surface drivers use (engine/bench)."""
        return self._gbdt.leaves_and_waves(start_group)

    def record_comm_bytes(self, recorder, waves):
        """Attach per-iteration psum payload bytes to a RunRecorder
        (None off the data-parallel path)."""
        return self._gbdt.record_comm_bytes(recorder, waves)

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_model_per_iteration()

    def num_feature(self) -> int:
        return self._gbdt.max_feature_idx + 1

    def feature_name(self) -> List[str]:
        return list(self._gbdt.feature_names)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = 0) -> np.ndarray:
        imp = self._gbdt.feature_importance(importance_type, iteration)
        if importance_type == "split":
            return imp.astype(np.int32)
        return imp

    # -- serialization ------------------------------------------------------

    def save_model(self, filename: str, num_iteration: int = -1,
                   start_iteration: int = 0) -> "Booster":
        if num_iteration < 0 and self.best_iteration > 0:
            num_iteration = self.best_iteration
        self._gbdt.save_model_to_file(filename, start_iteration,
                                      num_iteration)
        return self

    def model_to_string(self, num_iteration: int = -1,
                        start_iteration: int = 0) -> str:
        if num_iteration < 0 and self.best_iteration > 0:
            num_iteration = self.best_iteration
        return self._gbdt.model_to_string(start_iteration, num_iteration)

    def dump_model(self, num_iteration: int = -1,
                   start_iteration: int = 0) -> dict:
        if num_iteration < 0 and self.best_iteration > 0:
            num_iteration = self.best_iteration
        return self._gbdt.dump_model(start_iteration, num_iteration)

    # -- pickling (reference pickles via model string, basic.py:1476) -------

    def __getstate__(self):
        state = {
            "params": self.params,
            "best_iteration": self.best_iteration,
            "best_score": self.best_score,
            "model_str": self.model_to_string(),
            "pandas_categorical": self.pandas_categorical,
        }
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self.pandas_categorical = state.get("pandas_categorical")
        self.train_set = None
        self.valid_sets = []
        self.name_valid_sets = []
        self._train_data_name = "training"
        self._init_from_string(state["model_str"])

    def model_from_string(self, model_str: str,
                          verbose: bool = True) -> "Booster":
        """Replace this booster's model with one parsed from a string
        (basic.py:2049-2068)."""
        self._init_from_string(model_str)
        return self

    def save_checkpoint(self, directory: str) -> Optional[str]:
        """Write a resumable checkpoint bundle (utils/checkpoint.py):
        the model text plus the training state a restart needs to
        continue bit-identically. Returns the path, or None on a
        failure (which warns and never raises — the engine.train
        periodic wiring calls this mid-run)."""
        return self._gbdt.write_checkpoint(directory)

    def free_dataset(self) -> "Booster":
        self.train_set = None
        self.valid_sets = []
        return self

    def free_network(self) -> "Booster":
        return self

    def _to_predictor(self) -> "_InnerPredictor":
        return _InnerPredictor(booster=self)


class _InnerPredictor:
    """Init-model predictor for continued training
    (basic.py:356-624 _InnerPredictor). Carries a trained model's raw
    predictions so they can be folded into a Dataset's init_score."""

    def __init__(self, model_file: Optional[str] = None,
                 booster: Optional[Booster] = None,
                 model_str: Optional[str] = None):
        from .models.gbdt import GBDT
        if booster is not None:
            self._gbdt = booster._gbdt
        elif model_file is not None:
            with open(model_file) as fh:
                model_str = fh.read()
            self._gbdt = GBDT().load_model_from_string(
                model_str, source=model_file)
        elif model_str is not None:
            self._gbdt = GBDT().load_model_from_string(model_str)
        else:
            raise TypeError("Need model_file, model_str or booster")

    @property
    def num_total_iteration(self) -> int:
        return self._gbdt.current_iteration

    def init_score_for(self, X) -> np.ndarray:
        """Raw predictions flattened class-major — the init_score layout
        (metadata.cpp init_score_ is [class][row])."""
        from .io.sparse import SparseMatrix
        if not isinstance(X, SparseMatrix) and not keeps_float32(X):
            X = np.asarray(X, np.float64)
        raw = self._gbdt.predict_raw(X)
        if raw.ndim == 2:          # [N, K] -> class-major flat
            return raw.T.reshape(-1).astype(np.float64)
        return raw.astype(np.float64)
