"""Objective functions (gradient/hessian providers).

TPU-native counterparts of the reference objectives
(reference: src/objective/objective_function.cpp:10-46 factory;
regression_objective.hpp, binary_objective.hpp, multiclass_objective.hpp,
rank_objective.hpp, xentropy_objective.hpp). Formulas follow the reference
exactly (file:line cited per class); evaluation is vectorized jax instead
of OpenMP loops. Scores are laid out [num_class, N] like the reference's
class-major score buffer.

The pairwise lambdarank loops (rank_objective.hpp:81-166) become dense
per-query [w, w] blocks under ``vmap``, the queries sorted into a few width
classes by their length — no data-dependent loops, no scatter.
The reference's sigmoid lookup table (rank_objective.hpp:171-196) is a CPU
speed hack; we compute the exact sigmoid on the VPU.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import log


def _wmul(x, w):
    return x if w is None else x * w


class ObjectiveFunction:
    """Base interface (include/LightGBM/objective_function.h:20-80)."""

    name = "base"
    is_constant_hessian = False
    num_positive_data = 0
    need_query = False

    def __init__(self, config):
        self.config = config

    def init(self, metadata, num_data):
        self.label = np.asarray(metadata.label, np.float32)
        self.weights = (None if metadata.weights is None
                        else np.asarray(metadata.weights, np.float32))
        self.num_data = num_data

    # grad/hess for one model-per-iteration class slot
    def get_gradients(self, score):
        raise NotImplementedError

    # -- pure gradient seam (ops/step_cache.py) ---------------------------
    #
    # The process-wide compiled-step registry shares ONE jitted training
    # step between boosters, so the gradient computation cannot close
    # over this instance's label/weight arrays (they would embed as
    # trace constants). Eligible objectives expose:
    #   gradient_aux()      -> pytree of host arrays whose LAST axis is
    #                          the row axis (the caller pads it to the
    #                          step's bucketed width)
    #   gradient_builder()  -> pure fn(score, aux) -> (g, h) closing
    #                          only over config scalars
    #   static_key()        -> hashable tuple of everything the builder
    #                          closes over (part of the geometry key)
    # ``get_gradients`` delegates to the same pure fn, so the legacy
    # per-instance step and the shared step run IDENTICAL code — a
    # registry hit cannot change numerics. Aux dict keys starting with
    # ``_`` are NOT row-shaped (lambdarank's query tables, a pytree of
    # its width classes) and ride to the device unpadded/replicated. An
    # objective without a
    # sound pure seam would return None and keep the legacy closure
    # (none remain in-tree — lambdarank, the last holdout, rides its
    # query tables as ``_``-keys).

    def gradient_aux(self):
        return None

    def gradient_builder(self):
        return None

    def static_key(self) -> tuple:
        return (self.name,)

    def boost_from_score(self, class_id: int) -> float:
        return 0.0

    def convert_output(self, raw):
        """Raw score -> output transform (identity by default)."""
        return raw

    @property
    def num_model_per_iteration(self) -> int:
        return 1

    def is_renew_tree_output(self) -> bool:
        return False

    def renew_tree_output(self, pred, residual_fn, leaf_ids, num_leaves):
        raise NotImplementedError

    def to_string(self) -> str:
        return self.name


# --------------------------------------------------------------------------
# Regression family (src/objective/regression_objective.hpp)
# --------------------------------------------------------------------------

class RegressionL2Loss(ObjectiveFunction):
    """L2 (regression_objective.hpp:96-108): g = s - y, h = 1."""
    name = "regression"
    is_constant_hessian = True  # without weights

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.config.reg_sqrt:
            self.trans_label = np.sign(self.label) * np.sqrt(np.abs(self.label))
        else:
            self.trans_label = self.label
        self.is_constant_hessian = self.weights is None

    def gradient_aux(self):
        return {"y": self.trans_label, "w": self.weights}

    def gradient_builder(self):
        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            g = _wmul(score - y, w)
            h = jnp.ones_like(score) if w is None else w
            return g, h
        return fn

    def get_gradients(self, score):
        return self.gradient_builder()(score, self.gradient_aux())

    def boost_from_score(self, class_id):
        # weighted mean label (regression_objective.hpp:142-160)
        if self.weights is None:
            return float(np.mean(self.trans_label))
        return float(np.sum(self.trans_label * self.weights)
                     / np.sum(self.weights))

    def convert_output(self, raw):
        if self.config.reg_sqrt:
            return jnp.sign(raw) * raw * raw
        return raw


class RegressionL1Loss(RegressionL2Loss):
    """L1 (regression_objective.hpp:185-199): g = sign(s - y), h = 1;
    leaf outputs renewed to the residual median (hpp:219-258)."""
    name = "regression_l1"

    def gradient_builder(self):
        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            g = _wmul(jnp.sign(score - y), w)
            h = jnp.ones_like(score) if w is None else w
            return g, h
        return fn

    def boost_from_score(self, class_id):
        # weighted median (hpp:204-217)
        return _weighted_percentile(self.trans_label, self.weights, 0.5)

    def is_renew_tree_output(self):
        return True

    def renew_tree_output_percentile(self):
        return 0.5


class RegressionHuberLoss(RegressionL2Loss):
    """Huber (regression_objective.hpp:281-303)."""
    name = "huber"
    is_constant_hessian = False

    def gradient_builder(self):
        a = float(self.config.alpha)

        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            diff = score - y
            g = jnp.where(jnp.abs(diff) <= a, diff, jnp.sign(diff) * a)
            g = _wmul(g, w)
            h = jnp.ones_like(score) if w is None else w
            return g, h
        return fn

    def static_key(self):
        return (self.name, float(self.config.alpha))


class RegressionFairLoss(RegressionL2Loss):
    """Fair (regression_objective.hpp:335-349)."""
    name = "fair"
    is_constant_hessian = False

    def gradient_builder(self):
        c = float(self.config.fair_c)

        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            x = score - y
            g = _wmul(c * x / (jnp.abs(x) + c), w)
            h = _wmul(c * c / (jnp.abs(x) + c) ** 2, w)
            return g, h
        return fn

    def static_key(self):
        return (self.name, float(self.config.fair_c))


class RegressionPoissonLoss(RegressionL2Loss):
    """Poisson (regression_objective.hpp:414-426): score is log-mean."""
    name = "poisson"
    is_constant_hessian = False

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(self.label < 0):
            log.fatal("[poisson]: at least one target label is negative")

    def gradient_aux(self):
        return {"y": self.label, "w": self.weights}

    def gradient_builder(self):
        mds = float(self.config.poisson_max_delta_step)

        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            g = _wmul(jnp.exp(score) - y, w)
            h = _wmul(jnp.exp(score + mds), w)
            return g, h
        return fn

    def static_key(self):
        return (self.name, float(self.config.poisson_max_delta_step))

    def boost_from_score(self, class_id):
        return math.log(max(RegressionL2Loss.boost_from_score(self, class_id),
                            1e-20))

    def convert_output(self, raw):
        return jnp.exp(raw)


class RegressionQuantileLoss(RegressionL2Loss):
    """Quantile (regression_objective.hpp:465-487)."""
    name = "quantile"

    def gradient_aux(self):
        return {"y": self.label, "w": self.weights}

    def gradient_builder(self):
        a = float(self.config.alpha)

        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            g = _wmul(jnp.where(score > y, 1.0 - a, -a), w)
            h = jnp.ones_like(score) if w is None else w
            return g, h
        return fn

    def static_key(self):
        return (self.name, float(self.config.alpha))

    def boost_from_score(self, class_id):
        return _weighted_percentile(self.label, self.weights,
                                    self.config.alpha)

    def is_renew_tree_output(self):
        return True

    def renew_tree_output_percentile(self):
        return self.config.alpha


class RegressionMAPELoss(RegressionL2Loss):
    """MAPE (regression_objective.hpp:560-620)."""
    name = "mape"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.label_weight = 1.0 / np.maximum(1.0, np.abs(self.label))
        if self.weights is not None:
            self.label_weight = self.label_weight * self.weights

    def gradient_aux(self):
        return {"y": self.label, "lw": self.label_weight,
                "w": self.weights}

    def gradient_builder(self):
        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            lw = jnp.asarray(aux["lw"])
            g = jnp.sign(score - y) * lw
            h = (jnp.ones_like(score) if aux["w"] is None
                 else jnp.asarray(aux["w"]))
            return g, h
        return fn

    def boost_from_score(self, class_id):
        return _weighted_percentile(self.label, self.label_weight, 0.5)

    def is_renew_tree_output(self):
        return True

    def renew_tree_output_percentile(self):
        return 0.5


class RegressionGammaLoss(RegressionPoissonLoss):
    """Gamma (regression_objective.hpp:663-675)."""
    name = "gamma"

    def gradient_builder(self):
        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            g = 1.0 - y / jnp.exp(score)
            h = y / jnp.exp(score)
            return _wmul(g, w), _wmul(h, w)
        return fn

    def static_key(self):
        return (self.name,)


class RegressionTweedieLoss(RegressionPoissonLoss):
    """Tweedie (regression_objective.hpp:701-722)."""
    name = "tweedie"

    def gradient_builder(self):
        rho = float(self.config.tweedie_variance_power)

        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            e1 = jnp.exp((1.0 - rho) * score)
            e2 = jnp.exp((2.0 - rho) * score)
            g = -y * e1 + e2
            h = -y * (1.0 - rho) * e1 + (2.0 - rho) * e2
            return _wmul(g, w), _wmul(h, w)
        return fn

    def static_key(self):
        return (self.name,
                float(self.config.tweedie_variance_power))


# --------------------------------------------------------------------------
# Binary (src/objective/binary_objective.hpp:13-170)
# --------------------------------------------------------------------------

class BinaryLogloss(ObjectiveFunction):
    name = "binary"

    def __init__(self, config):
        super().__init__(config)
        # needed by convert_output on loaded models (no init() there)
        self.sigmoid = config.sigmoid

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        is_pos = self.label > 0
        cnt_pos = int(is_pos.sum())
        cnt_neg = int(num_data - cnt_pos)
        self.num_positive_data = cnt_pos
        w_pos, w_neg = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.config.scale_pos_weight
        self.label_val = np.where(is_pos, 1.0, -1.0).astype(np.float32)
        self.label_weight = np.where(is_pos, w_pos, w_neg).astype(np.float32)
        self.sigmoid = self.config.sigmoid
        if cnt_pos == 0 or cnt_neg == 0:
            log.warning("Contains only one class")

    def gradient_aux(self):
        return {"lv": self.label_val, "lw": self.label_weight,
                "w": self.weights}

    def gradient_builder(self):
        sig = float(self.sigmoid)

        def fn(score, aux):
            lv = jnp.asarray(aux["lv"])
            lw = jnp.asarray(aux["lw"])
            if aux["w"] is not None:
                lw = lw * jnp.asarray(aux["w"])
            response = -lv * sig / (1.0 + jnp.exp(lv * sig * score))
            ar = jnp.abs(response)
            g = response * lw
            h = ar * (sig - ar) * lw
            return g, h
        return fn

    def static_key(self):
        return (self.name, float(self.sigmoid))

    def get_gradients(self, score):
        return self.gradient_builder()(score, self.gradient_aux())

    def boost_from_score(self, class_id):
        # binary_objective.hpp:124-142
        if self.weights is not None:
            suml = float(np.sum((self.label > 0) * self.weights))
            sumw = float(np.sum(self.weights))
        else:
            suml = float(np.sum(self.label > 0))
            sumw = float(self.num_data)
        pavg = min(max(suml / max(sumw, 1e-15), 1e-15), 1.0 - 1e-15)
        return math.log(pavg / (1.0 - pavg)) / self.sigmoid

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))

    def to_string(self):
        return f"binary sigmoid:{self.sigmoid:g}"


# --------------------------------------------------------------------------
# Multiclass (src/objective/multiclass_objective.hpp:16-220)
# --------------------------------------------------------------------------

class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"

    def __init__(self, config):
        super().__init__(config)
        # needed by to_string/convert_output on loaded models
        self.num_class = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.num_class = self.config.num_class
        self.label_int = self.label.astype(np.int32)
        if np.any((self.label_int < 0) | (self.label_int >= self.num_class)):
            log.fatal("Label must be in [0, num_class)")

    @property
    def num_model_per_iteration(self):
        return self.num_class

    def gradient_aux(self):
        return {"yi": self.label_int, "w": self.weights}

    def gradient_builder(self):
        K = int(self.num_class)

        def fn(score, aux):
            """score: [K, N] -> grads/hess [K, N]
            (multiclass_objective.hpp:68)."""
            y = jax.nn.one_hot(jnp.asarray(aux["yi"]), K, axis=0,
                               dtype=score.dtype)   # [K, N]
            p = jax.nn.softmax(score, axis=0)
            g = p - y
            h = 2.0 * p * (1.0 - p)
            if aux["w"] is not None:
                w = jnp.asarray(aux["w"])[None, :]
                g, h = g * w, h * w
            return g, h
        return fn

    def static_key(self):
        return (self.name, int(self.num_class))

    def get_gradients(self, score):
        return self.gradient_builder()(score, self.gradient_aux())

    def boost_from_score(self, class_id):
        return 0.0

    def convert_output(self, raw):
        return jax.nn.softmax(raw, axis=0)

    def to_string(self):
        return f"multiclass num_class:{self.num_class}"


class MulticlassOVA(ObjectiveFunction):
    """One-vs-all (multiclass_objective.hpp:167-220): K independent
    binary objectives."""
    name = "multiclassova"

    def __init__(self, config):
        super().__init__(config)
        self.num_class = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.binary = []
        for k in range(self.num_class):
            sub = _Shim(self.config)
            meta_k = _MetaShim((self.label == k).astype(np.float32),
                               self.weights)
            b = BinaryLogloss(self.config)
            b.init(meta_k, num_data)
            self.binary.append(b)

    @property
    def num_model_per_iteration(self):
        return self.num_class

    def gradient_aux(self):
        return {"lv": np.stack([b.label_val for b in self.binary]),
                "lw": np.stack([b.label_weight for b in self.binary]),
                "w": self.weights}

    def gradient_builder(self):
        # K independent binary objectives, vectorized over the class
        # axis — elementwise, so bit-identical to the per-class loop
        sig = float(self.config.sigmoid)

        def fn(score, aux):
            lv = jnp.asarray(aux["lv"])             # [K, N]
            lw = jnp.asarray(aux["lw"])
            if aux["w"] is not None:
                lw = lw * jnp.asarray(aux["w"])[None, :]
            response = -lv * sig / (1.0 + jnp.exp(lv * sig * score))
            ar = jnp.abs(response)
            return response * lw, ar * (sig - ar) * lw
        return fn

    def static_key(self):
        return (self.name, int(self.num_class),
                float(self.config.sigmoid))

    def get_gradients(self, score):
        return self.gradient_builder()(score, self.gradient_aux())

    def boost_from_score(self, class_id):
        return self.binary[class_id].boost_from_score(0)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * raw))

    def to_string(self):
        return (f"multiclassova num_class:{self.num_class} "
                f"sigmoid:{self.config.sigmoid:g}")


class _Shim:
    def __init__(self, config):
        self.__dict__.update(config.__dict__)


class _MetaShim:
    def __init__(self, label, weights):
        self.label = label
        self.weights = weights


# --------------------------------------------------------------------------
# Cross entropy (src/objective/xentropy_objective.hpp)
# --------------------------------------------------------------------------

class CrossEntropy(ObjectiveFunction):
    """xentropy (hpp:77-86): labels in [0,1]; z = sigmoid(s)."""
    name = "cross_entropy"

    def gradient_aux(self):
        return {"y": self.label, "w": self.weights}

    def gradient_builder(self):
        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            w = aux["w"]
            w = None if w is None else jnp.asarray(w)
            z = 1.0 / (1.0 + jnp.exp(-score))
            g = _wmul(z - y, w)
            h = z * (1.0 - z)
            if w is not None:
                h = h * w
            return g, h
        return fn

    def get_gradients(self, score):
        return self.gradient_builder()(score, self.gradient_aux())

    def boost_from_score(self, class_id):
        # xentropy_objective.hpp:107-118: log(pavg / (1 - pavg))
        if self.weights is not None:
            pavg = float(np.sum(self.label * self.weights)
                         / np.sum(self.weights))
        else:
            pavg = float(np.mean(self.label))
        pavg = min(max(pavg, 1e-15), 1.0 - 1e-15)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-raw))

    def to_string(self):
        return "cross_entropy"


class CrossEntropyLambda(ObjectiveFunction):
    """xentlambda (hpp:150-240): intensity-weighted cross entropy."""
    name = "cross_entropy_lambda"

    def gradient_aux(self):
        return {"y": self.label, "w": self.weights}

    def gradient_builder(self):
        weighted = self.weights is not None

        def fn(score, aux):
            y = jnp.asarray(aux["y"])
            if not weighted:
                # unit weights: identical to CrossEntropy (hpp:184-189)
                z = 1.0 / (1.0 + jnp.exp(-score))
                return z - y, z * (1.0 - z)
            return _xentlambda_weighted(score, y,
                                        jnp.asarray(aux["w"]))
        return fn

    def get_gradients(self, score):
        return self.gradient_builder()(score, self.gradient_aux())

    def boost_from_score(self, class_id):
        pavg = float(np.mean(self.label))
        pavg = min(max(pavg, 1e-15), 1.0 - 1e-15)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))

    def to_string(self):
        return "cross_entropy_lambda"


def _xentlambda_weighted(score, y, w):
    """Weighted xentlambda grads (xentropy_objective.hpp:192-206)."""
    epf = jnp.exp(score)
    hhat = jnp.log1p(epf)
    z = 1.0 - jnp.exp(-w * hhat)
    enf = 1.0 / epf
    g = (1.0 - y / z) * w / (1.0 + enf)
    c = 1.0 / (1.0 - z)
    d = 1.0 + epf
    a = w * epf / (d * d)
    d = c - 1.0
    b = (c / (d * d)) * (1.0 + w * epf - c)
    h = a * (1.0 + y * b)
    return g, h


# --------------------------------------------------------------------------
# LambdaRank (src/objective/rank_objective.hpp:19-240)
# --------------------------------------------------------------------------

# What the pair layout is chosen by (LambdarankNDCG.init): no knob.
PAIR_SLOTS_BOUND = 4.0      # slots one padded class may spend per real pair
PAIR_MIN_WIDTH = 8          # the narrowest width class
PAIR_BLOCK_BYTES = 1 << 26  # one float32 [block, w, w] temporary of a class


def pair_class_widths(counts, nq_padded: int) -> list:
    """The width classes of a ranking data set, from its observed query
    lengths. One class of width ``qmax`` (the layout every release had)
    while that spends at most ``PAIR_SLOTS_BOUND`` slots per real pair
    (``sum n_q^2``); else powers of two from ``PAIR_MIN_WIDTH`` with the
    widest cut to ``qmax``, a query in the narrowest class that holds it
    (so at most 4 slots a pair from 4 documents up), empty classes left
    out."""
    counts = np.asarray(counts, np.int64)
    qmax = int(counts.max())
    if nq_padded * qmax * qmax <= PAIR_SLOTS_BOUND * float(np.sum(counts ** 2)):
        return [qmax]
    widths = []
    w = PAIR_MIN_WIDTH
    while w < qmax:
        widths.append(w)
        w *= 2
    widths.append(qmax)
    used = np.unique(np.searchsorted(widths, counts))
    return [widths[i] for i in used]


class LambdarankNDCG(ObjectiveFunction):
    """rank_objective.hpp:19-240. A query's rows are one contiguous range
    (``query_boundaries``), so the pair block is laid out by query length:
    ``init`` sorts the queries into a few width classes
    (``pair_class_widths``), each a ``[nq_c, w_c]`` table with ``nq_c``
    padded to its pow2 bucket (windows of like shape share one compiled
    step), and every row learns where its sums will stand (``src``). The
    step evaluates ``[nq_c, w_c, w_c]`` pairs a class and GATHERS the rows'
    sums: no scatter anywhere."""
    name = "lambdarank"
    need_query = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries,
                                           np.int64)
        self.sigmoid = self.config.sigmoid
        self.optimize_pos_at = self.config.max_position
        label_gain = self.config.label_gain
        if not label_gain:
            label_gain = [float(2 ** i - 1) for i in range(31)]
        self.label_gain = np.asarray(label_gain, np.float64)
        lab = self.label.astype(np.int32)
        if lab.max() >= len(self.label_gain):
            log.fatal("Label exceeds label_gain size")
        if lab.min() < 0:
            log.fatal("Label should be non-negative for ranking task")
        self._build_pair_layout(lab)
        # the eager entry (``refit_existing``) runs the pair block as ONE
        # program, as the step does: op by op every [nq_c, w, w] temporary
        # would be materialised
        # jit-capture: ok(*) — the pure seam's builder closes over config
        # scalars alone (``static_key``); labels and tables arrive in aux
        self._eager_grads = jax.jit(self.gradient_builder())

    def _build_pair_layout(self, lab):
        from ..obs import registry as obs
        from ..ops.step_cache import pow2_bucket
        qb = self.query_boundaries
        counts = np.diff(qb)
        nq, qmax = len(counts), int(counts.max())
        exact = getattr(self.config, "tpu_row_bucket", -1) == 0

        def bucket(n):
            return n if exact else pow2_bucket(n, 16)

        valid = np.arange(qmax)[None, :] < counts[:, None]
        lab_q = np.full((nq, qmax), -1, np.int32)
        lab_q[valid] = lab
        # inverse max DCG at k per query (rank_objective.hpp:55-68): the
        # labels a query a line, sorted descending, the top k discounted;
        # summed a group of like length at a time, so that each query's
        # sum runs over its own documents alone, in their order
        top_q = -np.sort(-lab_q, axis=1)[:, :self.optimize_pos_at]
        dcg = np.zeros(nq, np.float64)
        kept = np.minimum(counts, top_q.shape[1])
        for m in np.unique(kept):
            rows = kept == m
            dcg[rows] = np.sum(self.label_gain[top_q[rows, :m]]
                               / np.log2(np.arange(m) + 2.0), axis=1)
        self.inv_max_dcg = np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0),
                                    0.0)

        widths = pair_class_widths(counts, bucket(nq))
        cls = np.searchsorted(widths, counts)
        src = np.zeros(int(qb[-1]), np.int64)
        classes, at = [], 1               # flat[0] is the pad rows' exact +0.0
        for c, w in enumerate(widths):
            qs = np.nonzero(cls == c)[0]
            nq_p = bucket(len(qs))
            start = np.zeros(nq_p, np.int32)
            imd = np.zeros(nq_p, np.float32)
            labels = np.full((nq_p, w), -1, np.int32)
            start[:len(qs)] = qb[qs]
            imd[:len(qs)] = self.inv_max_dcg[qs]
            labels[:len(qs)] = lab_q[qs, :w]
            classes.append({"start": start, "imd": imd, "lab": labels})
            # row qb[q] + p stands at flat[at + k * w + p], k the query's
            # place in its class
            p_in = _within(counts[qs])
            src[np.repeat(qb[qs], counts[qs]) + p_in] = np.repeat(
                at + np.arange(len(qs), dtype=np.int64) * w, counts[qs]) + p_in
            at += nq_p * w
        if at >= 2 ** 31:
            log.fatal("Lambdarank: query tables exceed int32 positions")
        self._pair_classes = tuple(classes)
        self._pair_src = src.astype(np.int32)
        obs.gauge("rank/pairs_real").set(float(np.sum(counts ** 2)))
        obs.gauge("rank/pair_slots").set(float(sum(
            c["lab"].shape[0] * c["lab"].shape[1] ** 2 for c in classes)))

    def gradient_aux(self):
        return {
            "w": self.weights,
            "src": self._pair_src,
            # [nq_c(, w_c)] tables, NOT row-shaped: the ``_`` prefix tells
            # the caller to place them unpadded
            "_classes": self._pair_classes,
            "_label_gain": self.label_gain.astype(np.float32),
        }

    def gradient_builder(self):
        sigmoid = self.sigmoid
        weighted = self.weights is not None

        def fn(score, aux):
            with jax.named_scope("rank_pairs"):
                lam, hes = _lambdarank_grads(
                    score, aux["_classes"], jnp.asarray(aux["src"]),
                    jnp.asarray(aux["_label_gain"]), sigmoid)
            if weighted:
                w = jnp.asarray(aux["w"])
                lam, hes = lam * w, hes * w
            return lam, hes
        return fn

    def static_key(self):
        return ("lambdarank", float(self.sigmoid))

    def get_gradients(self, score):
        return self._eager_grads(score, self.gradient_aux())

    def to_string(self):
        return "lambdarank"


def _within(counts):
    """0..c-1 for each c of ``counts``, concatenated."""
    counts = np.asarray(counts, np.int64)
    ends = np.cumsum(counts)
    return np.arange(int(ends[-1]) if len(ends) else 0) - np.repeat(
        ends - counts, counts)


def _lambdarank_query(score, label_gain, sigmoid, start, imd, lab):
    """One query's lambdas and hessians [w] (rank_objective.hpp:81-166);
    ``lab`` [w] is -1 beyond the query's documents."""
    w = lab.shape[0]
    pos = jnp.arange(w, dtype=jnp.int32)
    valid = lab >= 0
    idx = jnp.minimum(start + pos, score.shape[0] - 1)
    s = jnp.where(valid, score[idx], -jnp.inf)
    # rank under the stable descending order: the documents that beat
    # this one (a higher score, or the same score earlier in the query)
    beats = (s[:, None] > s[None, :]) | ((s[:, None] == s[None, :])
                                         & (pos[:, None] < pos[None, :]))
    rank_of = jnp.sum(beats & valid[:, None], axis=0, dtype=jnp.int32)
    discount = 1.0 / jnp.log2(rank_of.astype(jnp.float32) + 2.0)
    best = jnp.max(jnp.where(valid, s, -jnp.inf))
    worst = jnp.min(jnp.where(valid, s, jnp.inf))
    norm_on = best != worst

    gain = label_gain[jnp.clip(lab, 0)]
    # pair (i, j): i=high (larger label), j=low
    hi_l = lab[:, None]
    lo_l = lab[None, :]
    pair_ok = (hi_l > lo_l) & valid[:, None] & valid[None, :]
    ds = s[:, None] - s[None, :]
    dcg_gap = gain[:, None] - gain[None, :]
    paired_disc = jnp.abs(discount[:, None] - discount[None, :])
    delta = dcg_gap * paired_disc * imd
    delta = jnp.where(norm_on, delta / (0.01 + jnp.abs(ds)), delta)
    p_lambda = 2.0 / (1.0 + jnp.exp(2.0 * ds * sigmoid))
    p_hess = p_lambda * (2.0 - p_lambda)
    p_lambda = jnp.where(pair_ok, -p_lambda * delta, 0.0)
    p_hess = jnp.where(pair_ok, 2.0 * p_hess * delta, 0.0)
    lam = jnp.sum(p_lambda, axis=1) - jnp.sum(p_lambda, axis=0)
    hes = jnp.sum(p_hess, axis=1) + jnp.sum(p_hess, axis=0)
    return lam, hes


def _lambdarank_grads(score, classes, src, label_gain, sigmoid):
    """The pair block of every width class, a block of queries at a time
    where a class's ``[nq_c, w, w]`` would pass ``PAIR_BLOCK_BYTES``, then
    each row's sums by a gather from (class, query, position)."""
    flat_l = [jnp.zeros(1, score.dtype)]
    flat_h = [jnp.zeros(1, score.dtype)]
    for c in classes:
        lab = jnp.asarray(c["lab"])
        nq, w = lab.shape
        args = (jnp.asarray(c["start"]), jnp.asarray(c["imd"]), lab)

        def one(a):
            return _lambdarank_query(score, label_gain, sigmoid, *a)
        block = max(1, PAIR_BLOCK_BYTES // (4 * w * w))
        if nq <= block:
            lam, hes = jax.vmap(one)(args)
        else:
            lam, hes = jax.lax.map(one, args, batch_size=block)
        flat_l.append(lam.reshape(-1))
        flat_h.append(hes.reshape(-1))
    return (jnp.concatenate(flat_l)[src], jnp.concatenate(flat_h)[src])


# --------------------------------------------------------------------------
# Factory (src/objective/objective_function.cpp:10-46)
# --------------------------------------------------------------------------

_OBJECTIVES = {
    "regression": RegressionL2Loss,
    "regression_l2": RegressionL2Loss,
    "l2": RegressionL2Loss,
    "mean_squared_error": RegressionL2Loss,
    "mse": RegressionL2Loss,
    "l2_root": RegressionL2Loss,
    "root_mean_squared_error": RegressionL2Loss,
    "rmse": RegressionL2Loss,
    "regression_l1": RegressionL1Loss,
    "l1": RegressionL1Loss,
    "mean_absolute_error": RegressionL1Loss,
    "mae": RegressionL1Loss,
    "huber": RegressionHuberLoss,
    "fair": RegressionFairLoss,
    "poisson": RegressionPoissonLoss,
    "quantile": RegressionQuantileLoss,
    "mape": RegressionMAPELoss,
    "mean_absolute_percentage_error": RegressionMAPELoss,
    "gamma": RegressionGammaLoss,
    "tweedie": RegressionTweedieLoss,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "softmax": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "multiclass_ova": MulticlassOVA,
    "ova": MulticlassOVA,
    "ovr": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "xentropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "xentlambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(name: str, config) -> Optional[ObjectiveFunction]:
    name = name.strip().lower()
    if name in ("none", "null", "custom", "na", ""):
        return None
    # l2_root/rmse use sqrt transform
    if name in ("l2_root", "root_mean_squared_error", "rmse"):
        config.reg_sqrt = True
    if name not in _OBJECTIVES:
        log.fatal(f"Unknown objective type name: {name}")
    return _OBJECTIVES[name](config)


def parse_objective_from_model_string(s: str, config):
    """Recreate an objective from its model-file string, e.g.
    'binary sigmoid:1' or 'multiclass num_class:3'
    (objective_function.cpp:49-84)."""
    parts = s.strip().split()
    if not parts:
        return None
    name = parts[0]
    for tok in parts[1:]:
        if ":" in tok:
            k, v = tok.split(":", 1)
            if k == "num_class":
                config.num_class = int(v)
            elif k == "sigmoid":
                config.sigmoid = float(v)
    return create_objective(name, config)


def _weighted_percentile(values, weights, alpha):
    """PercentileFun / WeightedPercentileFun
    (regression_objective.hpp:23-60)."""
    values = np.asarray(values, np.float64)
    if len(values) == 0:
        return 0.0
    if weights is None:
        sorted_v = np.sort(values)
        pos = alpha * len(values)
        k = int(np.ceil(pos)) - 1
        k = min(max(k, 0), len(values) - 1)
        if np.ceil(pos) == pos and k + 1 < len(values):
            return float((sorted_v[k] + sorted_v[k + 1]) / 2.0)
        return float(sorted_v[k])
    order = np.argsort(values)
    sv, sw = values[order], np.asarray(weights, np.float64)[order]
    cum = np.cumsum(sw) - sw * (1.0 - alpha)
    thresh = alpha * np.sum(sw)
    k = int(np.searchsorted(cum, thresh, side="left"))
    k = min(max(k, 0), len(values) - 1)
    return float(sv[k])
