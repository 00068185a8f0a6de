"""Objective functions: pure-jnp gradient/hessian pairs + output transforms.

The TPU equivalent of libxgboost's C++ objective registry (reference trains
via ``xgb.train(cfg, ...)`` — algorithm_mode/train.py:367-376 — with the
objective resolved inside the C++ core). Every objective is three pure
functions over jnp arrays, so the whole round step stays inside one XLA
program:

* ``grad_hess(margin, label, weight)`` -> (g, h) per row (per class for multi)
* ``margin_to_prediction(margin)``      -> what ``predict()`` returns
* ``base_margin(base_score)``           -> initial margin from base_score

Gradient formulas follow the published XGBoost objective definitions
(elementwise; no data-dependent control flow — everything is jnp.where).
"""

import math

import jax.numpy as jnp
import numpy as np

from ..toolkit import exceptions as exc
from ..constants import (
    LOGISTIC_REGRESSION_LABEL_RANGE_ERROR,
    MULTI_CLASS_LABEL_RANGE_ERROR,
    POISSON_REGRESSION_ERROR,
    TWEEDIE_REGRESSION_ERROR,
)

_EPS = 1e-16
_HESS_EPS = 1e-6


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


class Objective:
    """Base: binary/regression single-output objective."""

    name = None
    num_output_group = 1
    default_metric = "rmse"
    # prediction transform applied at serve time
    prob_transform = False

    def __init__(self, params=None):
        self.params = params or {}
        self.scale_pos_weight = float(self.params.get("scale_pos_weight", 1.0))

    # -- training ------------------------------------------------------------
    def grad_hess(self, margin, label, weight):
        raise NotImplementedError

    # -- label sanity (host-side, before training) ---------------------------
    def validate_labels(self, labels):
        pass

    # -- transforms ----------------------------------------------------------
    def base_margin(self, base_score):
        return float(base_score)

    def margin_to_prediction(self, margin):
        return margin


class SquaredError(Objective):
    name = "reg:squarederror"

    def grad_hess(self, margin, label, weight):
        return (margin - label) * weight, jnp.ones_like(margin) * weight


class SquaredLogError(Objective):
    name = "reg:squaredlogerror"
    default_metric = "rmsle"

    def grad_hess(self, margin, label, weight):
        p = jnp.maximum(margin, -1 + 1e-6)
        z = jnp.log1p(p) - jnp.log1p(label)
        g = z / (p + 1.0)
        h = jnp.maximum((1.0 - z) / ((p + 1.0) ** 2), _HESS_EPS)
        return g * weight, h * weight


class PseudoHuber(Objective):
    name = "reg:pseudohubererror"
    default_metric = "mphe"

    def grad_hess(self, margin, label, weight):
        delta = float(self.params.get("huber_slope", 1.0))
        z = margin - label
        scale = jnp.sqrt(1.0 + (z / delta) ** 2)
        g = z / scale
        h = 1.0 / (scale**3)
        return g * weight, h * weight


class AbsoluteError(Objective):
    name = "reg:absoluteerror"
    default_metric = "mae"

    def grad_hess(self, margin, label, weight):
        g = jnp.sign(margin - label)
        h = jnp.ones_like(margin)
        return g * weight, h * weight


class LogisticRegression(Objective):
    """reg:logistic — logistic loss, label in [0,1], prediction is probability."""

    name = "reg:logistic"
    default_metric = "rmse"
    prob_transform = True

    def validate_labels(self, labels):
        if labels.size and ((labels < 0).any() or (labels > 1).any()):
            raise exc.UserError(LOGISTIC_REGRESSION_LABEL_RANGE_ERROR)

    def base_margin(self, base_score):
        base_score = float(base_score)
        if not 0.0 < base_score < 1.0:
            raise exc.UserError(
                "base_score must be in (0,1) for logistic loss"
            )
        return math.log(base_score / (1.0 - base_score))

    def grad_hess(self, margin, label, weight):
        p = _sigmoid(margin)
        w = jnp.where(label == 1.0, weight * self.scale_pos_weight, weight)
        g = (p - label) * w
        h = jnp.maximum(p * (1.0 - p), _EPS) * w
        return g, h

    def margin_to_prediction(self, margin):
        return 1.0 / (1.0 + np.exp(-margin))


class BinaryLogistic(LogisticRegression):
    name = "binary:logistic"
    default_metric = "logloss"


class BinaryLogitRaw(LogisticRegression):
    """binary:logitraw — logistic gradient, raw margin as prediction."""

    name = "binary:logitraw"
    default_metric = "logloss"
    prob_transform = False

    def margin_to_prediction(self, margin):
        return margin


class BinaryHinge(Objective):
    name = "binary:hinge"
    default_metric = "error"

    def validate_labels(self, labels):
        if labels.size and ((labels < 0).any() or (labels > 1).any()):
            raise exc.UserError(LOGISTIC_REGRESSION_LABEL_RANGE_ERROR)

    def grad_hess(self, margin, label, weight):
        y = 2.0 * label - 1.0
        in_margin = margin * y < 1.0
        g = jnp.where(in_margin, -y, 0.0) * weight
        h = jnp.where(in_margin, 1.0, _HESS_EPS) * weight
        return g, h

    def margin_to_prediction(self, margin):
        return (margin > 0).astype(np.float32)


class PoissonRegression(Objective):
    name = "count:poisson"
    default_metric = "poisson-nloglik"

    def validate_labels(self, labels):
        if labels.size and (labels < 0).any():
            raise exc.UserError(POISSON_REGRESSION_ERROR)

    def base_margin(self, base_score):
        return math.log(max(float(base_score), 1e-16))

    def grad_hess(self, margin, label, weight):
        p = jnp.exp(margin)
        g = (p - label) * weight
        h = p * weight
        return g, h

    def margin_to_prediction(self, margin):
        return np.exp(margin)


class GammaRegression(PoissonRegression):
    name = "reg:gamma"
    default_metric = "gamma-nloglik"

    def validate_labels(self, labels):
        if labels.size and (labels < 0).any():
            raise exc.UserError("label must be nonnegative for gamma regression")

    def grad_hess(self, margin, label, weight):
        ey = label * jnp.exp(-margin)
        g = (1.0 - ey) * weight
        h = jnp.maximum(ey, _HESS_EPS) * weight
        return g, h


class TweedieRegression(PoissonRegression):
    name = "reg:tweedie"

    def __init__(self, params=None):
        super().__init__(params)
        self.rho = float(self.params.get("tweedie_variance_power", 1.5))

    @property
    def default_metric(self):  # noqa: A003 - mirrors xgboost's dynamic default
        return "tweedie-nloglik@{}".format(self.rho)

    def validate_labels(self, labels):
        if labels.size and (labels < 0).any():
            raise exc.UserError(TWEEDIE_REGRESSION_ERROR)

    def grad_hess(self, margin, label, weight):
        rho = self.rho
        a = label * jnp.exp((1.0 - rho) * margin)
        b = jnp.exp((2.0 - rho) * margin)
        g = (-a + b) * weight
        h = jnp.maximum(-a * (1.0 - rho) + b * (2.0 - rho), _HESS_EPS) * weight
        return g, h


class SoftmaxMulti(Objective):
    """multi:softmax / multi:softprob — margin is [n, num_class]."""

    name = "multi:softmax"
    default_metric = "merror"

    def __init__(self, params=None):
        super().__init__(params)
        self.num_class = int(self.params.get("num_class", 0))
        if self.num_class < 2:
            raise exc.UserError(
                "Require input for parameter 'num_class' for multi-classification"
            )
        self.num_output_group = self.num_class

    def validate_labels(self, labels):
        if labels.size and ((labels < 0).any() or (labels >= self.num_class).any()):
            raise exc.UserError(MULTI_CLASS_LABEL_RANGE_ERROR)

    def base_margin(self, base_score):
        return 0.5

    def grad_hess(self, margin, label, weight):
        # margin [n, C]; label [n]; weight [n]
        p = jnp.exp(margin - jnp.max(margin, axis=1, keepdims=True))
        p = p / jnp.sum(p, axis=1, keepdims=True)
        onehot = (label[:, None] == jnp.arange(p.shape[1])[None, :]).astype(p.dtype)
        g = (p - onehot) * weight[:, None]
        h = jnp.maximum(2.0 * p * (1.0 - p), _EPS) * weight[:, None]
        return g, h

    def margin_to_prediction(self, margin):
        return np.argmax(margin, axis=1).astype(np.float32)


class SoftprobMulti(SoftmaxMulti):
    name = "multi:softprob"
    default_metric = "mlogloss"
    prob_transform = True

    def margin_to_prediction(self, margin):
        e = np.exp(margin - margin.max(axis=1, keepdims=True))
        return e / e.sum(axis=1, keepdims=True)


class SurvivalAft(Objective):
    """survival:aft — accelerated failure time with point labels.

    The SageMaker data contract carries a single label column, so the
    censoring interval degenerates to y_lower == y_upper == label
    (uncensored); the distribution/scale hyperparameters
    (aft_loss_distribution[_scale]) behave as in xgboost.
    """

    name = "survival:aft"
    default_metric = "aft-nloglik"

    def __init__(self, params=None):
        super().__init__(params)
        self.dist = str(self.params.get("aft_loss_distribution", "normal"))
        self.sigma = float(self.params.get("aft_loss_distribution_scale", 1.0))

    def validate_labels(self, labels):
        if labels.size and (labels <= 0).any():
            raise exc.UserError("survival:aft labels (event times) must be positive")

    def base_margin(self, base_score):
        return math.log(max(float(base_score), 1e-16))

    def grad_hess(self, margin, label, weight):
        s = self.sigma
        z = (jnp.log(jnp.maximum(label, 1e-12)) - margin) / s
        if self.dist == "normal":
            g = -z / s
            h = jnp.full_like(margin, 1.0 / (s * s))
        elif self.dist == "logistic":
            ez = jnp.exp(-jnp.abs(z))
            sig = jnp.where(z >= 0, 1.0 / (1.0 + ez), ez / (1.0 + ez))
            g = -(2.0 * sig - 1.0) / s
            h = jnp.maximum(2.0 * sig * (1.0 - sig) / (s * s), _HESS_EPS)
        else:  # extreme (Gumbel)
            w = jnp.exp(jnp.clip(z, -30.0, 30.0))
            g = (1.0 - w) / s
            h = jnp.maximum(w / (s * s), _HESS_EPS)
        return g * weight, h * weight

    def margin_to_prediction(self, margin):
        return np.exp(margin)


class SurvivalCox(Objective):
    """survival:cox — proportional-hazards partial likelihood.

    Labels follow xgboost's convention: positive = event time (uncensored),
    negative = |censoring time| (right-censored). Risk sets are evaluated via
    cumulative sums over a host-precomputed time ordering captured at first
    call (the label vector is static across rounds).
    """

    name = "survival:cox"
    default_metric = "cox-nloglik"

    def base_margin(self, base_score):
        return 0.0

    def grad_hess(self, margin, label, weight):
        abs_time = jnp.abs(label)
        is_event = (label > 0).astype(margin.dtype)
        # risk set of i: rows with abs_time >= abs_time_i. Sort descending by
        # time; cumulative sums give risk-set aggregates.
        order = jnp.argsort(-abs_time)
        inv = jnp.argsort(order)
        exp_m = jnp.exp(margin - jnp.max(margin)) * weight
        exp_sorted = exp_m[order]
        cum_risk = jnp.cumsum(exp_sorted)[inv]          # sum over risk set of i
        # accumulate, over events e with t_e <= t_i, of 1/risk(e) and 1/risk(e)^2
        ev_sorted = (is_event * weight)[order]
        inv_risk = ev_sorted[::-1] / cum_risk[order][::-1]
        inv_risk2 = ev_sorted[::-1] / (cum_risk[order][::-1] ** 2)
        cum_inv = jnp.cumsum(inv_risk)[::-1][inv]
        cum_inv2 = jnp.cumsum(inv_risk2)[::-1][inv]
        g = -is_event * weight + exp_m * cum_inv
        h = jnp.maximum(exp_m * cum_inv - (exp_m**2) * cum_inv2, _HESS_EPS)
        return g, h

    def margin_to_prediction(self, margin):
        return np.exp(margin)


class LambdaRankObjective(Objective):
    """rank:pairwise / rank:ndcg / rank:map — LambdaMART gradients.

    Gradients need the query-group layout, so the booster routes these through
    ``ops.ranking.lambdarank_grad_hess`` over a ``GroupLayout`` (groups
    bucketed by size) built once per dataset. This class carries scheme
    metadata only.
    """

    name = "rank:pairwise"
    default_metric = "map"
    needs_groups = True

    def __init__(self, params=None):
        super().__init__(params)
        self.scheme = self.name.split(":")[1]

    def base_margin(self, base_score):
        return float(base_score)

    def grad_hess(self, margin, label, weight):
        raise exc.AlgorithmError(
            "ranking objectives need group info; the booster must route through "
            "ops.ranking.lambdarank_grad_hess"
        )


class RankNdcg(LambdaRankObjective):
    name = "rank:ndcg"
    default_metric = "ndcg"


class RankMap(LambdaRankObjective):
    name = "rank:map"
    default_metric = "map"


_REGISTRY = {
    cls.name: cls
    for cls in [
        SquaredError,
        SquaredLogError,
        PseudoHuber,
        AbsoluteError,
        LogisticRegression,
        BinaryLogistic,
        BinaryLogitRaw,
        BinaryHinge,
        PoissonRegression,
        GammaRegression,
        TweedieRegression,
        SoftmaxMulti,
        SoftprobMulti,
        SurvivalAft,
        SurvivalCox,
        LambdaRankObjective,
        RankNdcg,
        RankMap,
    ]
}
_REGISTRY["reg:linear"] = SquaredError  # deprecated alias


def create_objective(name, params=None):
    """Instantiate an objective by its xgboost name."""
    name = name or "reg:squarederror"
    cls = _REGISTRY.get(name)
    if cls is None:
        raise exc.UserError(
            "Objective '{}' is not supported yet. Supported: {}".format(
                name, ", ".join(sorted(_REGISTRY))
            )
        )
    return cls(params)


def default_base_score(name):
    """XGBoost's default base_score is 0.5 for every objective family."""
    return 0.5
