"""Objective functions: gradients/hessians as pure JAX functions.

reference: src/objective/ — ObjectiveFunction interface
(include/LightGBM/objective_function.h:19) and the factory
(src/objective/objective_function.cpp:17-47).  Formulas match the reference
implementations cited per class.  Scores/gradients for multiclass use
[K, n] layout (class-major, like the reference's flattened num_data*k+i).

Each objective provides:
- ``get_gradients(score, tables) -> (grad, hess)`` — jittable, shapes [n] or
  [K, n]; ``tables`` is the objective's ``device_tables`` (None but for ranking)
- ``boost_from_score(class_id)`` — host-side init score
- ``convert_output(score)`` — raw score -> prediction space (jittable)
- ``renew_percentile`` — not None for objectives that re-fit leaf outputs
  as residual percentiles (RenewTreeOutput, regression_objective.hpp:250)
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .dataset import Metadata


class ObjectiveFunction:
    name = "none"
    num_model_per_iteration = 1
    is_constant_hessian = False
    renew_percentile: Optional[float] = None
    need_group = False
    # device arrays ``get_gradients(score, tables)`` takes as runtime
    # arguments of the jitted round program (the ranking objectives' query
    # tables); None for an objective whose state is the label array
    device_tables = None

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = jnp.asarray(metadata.label, jnp.float32)
        self.weight = (jnp.asarray(metadata.weight, jnp.float32)
                       if metadata.weight is not None else None)
        self.metadata = metadata

    def _w(self, g, h):
        if self.weight is not None:
            return g * self.weight, h * self.weight
        return g, h

    def get_gradients(self, score: jax.Array,
                      tables=None) -> Tuple[jax.Array, jax.Array]:
        """``tables``: the objective's ``device_tables``, which the booster
        always passes."""
        raise NotImplementedError

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, score: jax.Array) -> jax.Array:
        return score

    def _weighted_mean_label(self) -> float:
        lbl = np.asarray(self.label, np.float64)
        if self.weight is not None:
            w = np.asarray(self.weight, np.float64)
            return float((lbl * w).sum() / w.sum())
        return float(lbl.mean())


# ---------------------------------------------------------------------------
# Regression family (reference: src/objective/regression_objective.hpp)
# ---------------------------------------------------------------------------

class RegressionL2(ObjectiveFunction):
    """reference: RegressionL2loss (regression_objective.hpp:93)."""

    name = "regression"
    is_constant_hessian = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.config.reg_sqrt:
            lbl = np.asarray(metadata.label, np.float64)
            self.label = jnp.asarray(np.sign(lbl) * np.sqrt(np.abs(lbl)), jnp.float32)

    def get_gradients(self, score, tables=None):
        return self._w(score - self.label, jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        return self._weighted_mean_label()

    def convert_output(self, score):
        if self.config.reg_sqrt:
            return jnp.sign(score) * score * score
        return score


class RegressionL1(RegressionL2):
    """reference: RegressionL1loss (regression_objective.hpp:204)."""

    name = "regression_l1"
    renew_percentile = 0.5

    def get_gradients(self, score, tables=None):
        return self._w(jnp.sign(score - self.label), jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self.label, np.float64)
        w = None if self.weight is None else np.asarray(self.weight, np.float64)
        return _percentile(lbl, w, 0.5)


class RegressionHuber(RegressionL2):
    """reference: RegressionHuberLoss (regression_objective.hpp:290)."""

    name = "huber"
    renew_percentile = 0.5

    def get_gradients(self, score, tables=None):
        diff = score - self.label
        a = self.config.alpha
        g = jnp.where(jnp.abs(diff) <= a, diff, jnp.sign(diff) * a)
        return self._w(g, jnp.ones_like(score))


class RegressionFair(ObjectiveFunction):
    """reference: RegressionFairLoss (regression_objective.hpp:352)."""

    name = "fair"

    def get_gradients(self, score, tables=None):
        c = self.config.fair_c
        x = score - self.label
        g = c * x / (jnp.abs(x) + c)
        h = c * c / (jnp.abs(x) + c) ** 2
        return self._w(g, h)


class RegressionPoisson(ObjectiveFunction):
    """reference: RegressionPoissonLoss (regression_objective.hpp:399)."""

    name = "poisson"

    def get_gradients(self, score, tables=None):
        g = jnp.exp(score) - self.label
        h = jnp.exp(score + self.config.poisson_max_delta_step)
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        return math.log(max(self._weighted_mean_label(), 1e-20))

    def convert_output(self, score):
        return jnp.exp(score)


class RegressionQuantile(ObjectiveFunction):
    """reference: RegressionQuantileloss (regression_objective.hpp:480)."""

    name = "quantile"
    is_constant_hessian = True

    @property
    def renew_percentile(self):
        return self.config.alpha

    def get_gradients(self, score, tables=None):
        a = self.config.alpha
        g = jnp.where(score > self.label, 1.0 - a, -a)
        return self._w(g, jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self.label, np.float64)
        w = None if self.weight is None else np.asarray(self.weight, np.float64)
        return _percentile(lbl, w, self.config.alpha)


class RegressionMAPE(ObjectiveFunction):
    """reference: RegressionMAPELOSS (regression_objective.hpp:579)."""

    name = "mape"
    renew_percentile = 0.5

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lw = 1.0 / np.maximum(1.0, np.abs(np.asarray(metadata.label, np.float64)))
        self.label_weight = jnp.asarray(lw, jnp.float32)

    def get_gradients(self, score, tables=None):
        diff = score - self.label
        g = jnp.sign(diff) * self.label_weight
        h = jnp.ones_like(score) if self.weight is None else self.weight
        if self.weight is not None:
            g = g * self.weight
        return g, h

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self.label, np.float64)
        w = np.asarray(self.label_weight, np.float64)
        if self.weight is not None:
            w = w * np.asarray(self.weight, np.float64)
        return _percentile(lbl, w, 0.5)


class RegressionGamma(RegressionPoisson):
    """reference: RegressionGammaLoss (regression_objective.hpp:674)."""

    name = "gamma"

    def get_gradients(self, score, tables=None):
        g = 1.0 - self.label * jnp.exp(-score)
        h = self.label * jnp.exp(-score)
        return self._w(g, h)


class RegressionTweedie(RegressionPoisson):
    """reference: RegressionTweedieLoss (regression_objective.hpp:711)."""

    name = "tweedie"

    def get_gradients(self, score, tables=None):
        rho = self.config.tweedie_variance_power
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        g = -self.label * e1 + e2
        h = -self.label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return self._w(g, h)


# ---------------------------------------------------------------------------
# Binary (reference: src/objective/binary_objective.hpp:21)
# ---------------------------------------------------------------------------

class BinaryLogloss(ObjectiveFunction):
    name = "binary"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label, np.float64)
        # reference: is_pos = label > 0 (binary_objective.hpp:35) — any
        # positive value counts as the positive class, no {0,1} check
        self.label_sign = jnp.asarray(np.where(lbl > 0, 1.0, -1.0), jnp.float32)
        cnt_pos = float((lbl > 0).sum())
        cnt_neg = float(len(lbl) - cnt_pos)
        c = self.config
        if c.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                self.label_weight_pos, self.label_weight_neg = 1.0, cnt_pos / cnt_neg
            else:
                self.label_weight_pos, self.label_weight_neg = cnt_neg / cnt_pos, 1.0
        else:
            self.label_weight_pos, self.label_weight_neg = c.scale_pos_weight, 1.0
        self._pavg = None
        if cnt_pos + cnt_neg > 0:
            if self.weight is not None:
                w = np.asarray(self.weight, np.float64)
                spos = float((w * (lbl > 0)).sum())
                self._pavg = spos / w.sum()
            else:
                self._pavg = cnt_pos / (cnt_pos + cnt_neg)

    def get_gradients(self, score, tables=None):
        sig = self.config.sigmoid
        lb = self.label_sign
        lw = jnp.where(lb > 0, self.label_weight_pos, self.label_weight_neg)
        response = -lb * sig / (1.0 + jnp.exp(lb * sig * score))
        abs_resp = jnp.abs(response)
        g = response * lw
        h = abs_resp * (sig - abs_resp) * lw
        return self._w(g, h)

    def boost_from_score(self, class_id=0):
        if not self.config.boost_from_average or self._pavg is None:
            return 0.0
        pavg = min(max(self._pavg, 1e-15), 1.0 - 1e-15)
        return math.log(pavg / (1.0 - pavg)) / self.config.sigmoid

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * score))


# ---------------------------------------------------------------------------
# Multiclass (reference: src/objective/multiclass_objective.hpp:24,180)
# ---------------------------------------------------------------------------

class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_model_per_iteration = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label, np.int32)
        if lbl.min() < 0 or lbl.max() >= self.num_class:
            raise ValueError("multiclass labels must be in [0, num_class)")
        self.label_int = jnp.asarray(lbl)
        onehot = np.zeros((self.num_class, len(lbl)), np.float32)
        onehot[lbl, np.arange(len(lbl))] = 1.0
        self.label_onehot = jnp.asarray(onehot)
        w = np.asarray(metadata.weight, np.float64) if metadata.weight is not None else np.ones(len(lbl))
        probs = np.array([(w * (lbl == k)).sum() for k in range(self.num_class)])
        self.class_init_probs = probs / w.sum()

    def get_gradients(self, score, tables=None):
        # score: [K, n]
        p = jax.nn.softmax(score, axis=0)
        g = p - self.label_onehot
        # reference uses a flat 2.0 factor (multiclass_objective.hpp:100),
        # not the K/(K-1) Newton factor some other GBDTs use
        h = 2.0 * p * (1.0 - p)
        if self.weight is not None:
            g = g * self.weight[None, :]
            h = h * self.weight[None, :]
        return g, h

    def boost_from_score(self, class_id=0):
        if not self.config.boost_from_average:
            return 0.0
        return math.log(max(float(self.class_init_probs[class_id]), 1e-15))

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=0)


class MulticlassOVA(ObjectiveFunction):
    """One-vs-all: K independent binary objectives
    (reference: multiclass_objective.hpp:180)."""

    name = "multiclassova"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_model_per_iteration = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label, np.int32)
        onehot = np.zeros((self.num_class, len(lbl)), np.float32)
        onehot[lbl, np.arange(len(lbl))] = 1.0
        self.label_onehot = jnp.asarray(onehot)
        self.binary_objs = []
        for k in range(self.num_class):
            sub = BinaryLogloss(self.config)
            md = Metadata(label=(np.asarray(lbl) == k).astype(np.float32),
                          weight=metadata.weight)
            sub.init(md, num_data)
            self.binary_objs.append(sub)

    def get_gradients(self, score, tables=None):
        gs, hs = [], []
        for k in range(self.num_class):
            g, h = self.binary_objs[k].get_gradients(score[k])
            gs.append(g)
            hs.append(h)
        return jnp.stack(gs), jnp.stack(hs)

    def boost_from_score(self, class_id=0):
        return self.binary_objs[class_id].boost_from_score()

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * score))


# ---------------------------------------------------------------------------
# Cross-entropy (reference: src/objective/xentropy_objective.hpp:44,148)
# ---------------------------------------------------------------------------

class CrossEntropy(ObjectiveFunction):
    name = "cross_entropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label, np.float64)
        if lbl.min() < 0 or lbl.max() > 1:
            raise ValueError("cross_entropy labels must be in [0, 1]")

    def get_gradients(self, score, tables=None):
        z = 1.0 / (1.0 + jnp.exp(-score))
        return self._w(z - self.label, z * (1.0 - z))

    def boost_from_score(self, class_id=0):
        pavg = min(max(self._weighted_mean_label(), 1e-15), 1 - 1e-15)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-score))


class CrossEntropyLambda(ObjectiveFunction):
    """reference: CrossEntropyLambda (xentropy_objective.hpp:148)."""

    name = "cross_entropy_lambda"

    def get_gradients(self, score, tables=None):
        # reference: xentropy_objective.hpp:185-212 (weighted branch; the
        # unweighted branch degenerates to plain sigmoid cross-entropy)
        if self.weight is None:
            z = 1.0 / (1.0 + jnp.exp(-score))
            return z - self.label, z * (1.0 - z)
        w = self.weight
        y = self.label
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = jnp.maximum(1.0 - jnp.exp(-w * hhat), 1e-15)
        enf = 1.0 / epf
        g = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        a = w * epf / ((1.0 + epf) * (1.0 + epf))
        d = c - 1.0
        b = (c / (d * d)) * (1.0 + w * epf - c)
        h = a * (1.0 + y * b)
        return g, h

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self.label, np.float64)
        if self.weight is not None:
            w = np.asarray(self.weight, np.float64)
            havg = float((lbl * w).sum() / w.sum())
        else:
            havg = float(lbl.mean())
        return math.log(max(math.expm1(max(havg, 1e-15)), 1e-15))

    def convert_output(self, score):
        return jnp.log1p(jnp.exp(score))


def _percentile(values: np.ndarray, weights: Optional[np.ndarray], alpha: float) -> float:
    """Weighted percentile matching reference Common::*Percentile
    (regression_objective.hpp:23-82)."""
    if len(values) == 0:
        return 0.0
    order = np.argsort(values, kind="stable")
    v = values[order]
    if weights is None:
        pos = alpha * (len(v) - 1)
        lo = int(math.floor(pos))
        hi = min(lo + 1, len(v) - 1)
        frac = pos - lo
        return float(v[lo] * (1 - frac) + v[hi] * frac)
    w = weights[order]
    cw = np.cumsum(w) - w / 2.0
    tot = w.sum()
    p = cw / tot
    idx = np.searchsorted(p, alpha)
    if idx <= 0:
        return float(v[0])
    if idx >= len(v):
        return float(v[-1])
    p0, p1 = p[idx - 1], p[idx]
    frac = 0.0 if p1 == p0 else (alpha - p0) / (p1 - p0)
    return float(v[idx - 1] * (1 - frac) + v[idx] * frac)


_REGISTRY = {}
for _cls in (RegressionL2, RegressionL1, RegressionHuber, RegressionFair,
             RegressionPoisson, RegressionQuantile, RegressionMAPE,
             RegressionGamma, RegressionTweedie, BinaryLogloss,
             MulticlassSoftmax, MulticlassOVA, CrossEntropy, CrossEntropyLambda):
    _REGISTRY[_cls.name] = _cls


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """reference: ObjectiveFunction::CreateObjectiveFunction
    (src/objective/objective_function.cpp:17-47)."""
    name = config.objective
    if name == "none":
        return None
    if name in ("lambdarank", "rank_xendcg"):
        from .objective_rank import LambdarankNDCG, RankXENDCG
        return LambdarankNDCG(config) if name == "lambdarank" else RankXENDCG(config)
    if name not in _REGISTRY:
        raise ValueError(f"unknown objective {name!r}")
    return _REGISTRY[name](config)
