"""Deferred host-tree materialization (round 5).

On an accelerator every device->host copy is a sync, so
GBDT._finish_iter banks stacked DEVICE trees and
converts the backlog in ONE bulk transfer when the host model list is
actually needed (GBDT._drain_pending).  These tests force the deferred path
on the CPU backend (LGBT_DEFER_HOST_TREES=1) and pin down that it is
bit-identical to the eager path — models, predictions, stop semantics,
rollback, and iteration-0 init-score bias.
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture()
def defer_env():
    os.environ["LGBT_DEFER_HOST_TREES"] = "1"
    yield
    os.environ.pop("LGBT_DEFER_HOST_TREES", None)


def _data(n=2000, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.1 * rng.randn(n) > 1.0).astype(
        np.float32)
    return X, y


def _fit(X, y, params, rounds, defer):
    os.environ["LGBT_DEFER_HOST_TREES"] = "1" if defer else "0"
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(rounds):
        if bst.update():
            break
    return bst


def test_deferred_matches_eager_bitwise(defer_env):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
              "verbosity": -1, "bagging_fraction": 0.8, "bagging_freq": 1,
              "feature_fraction": 0.9}
    b0 = _fit(X, y, params, 30, defer=False)
    b1 = _fit(X, y, params, 30, defer=True)
    assert b1.num_trees() == b0.num_trees() == 30
    assert np.array_equal(b0.predict(X), b1.predict(X))
    assert b0.model_to_string() == b1.model_to_string()


def test_deferred_stop_truncates_like_eager(defer_env):
    # nothing splittable: reference stops with the iteration-0 constant
    # tree kept (gbdt.cpp:387-405); the deferred drain truncates to match
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_in_leaf": 5000, "verbosity": -1}
    b0 = _fit(X, y, params, 5, defer=False)
    b1 = _fit(X, y, params, 5, defer=True)
    assert b1.num_trees() == b0.num_trees() == 1
    assert b1.boosting.iter == b0.boosting.iter == 0
    assert np.allclose(b0.predict(X), b1.predict(X))


def test_deferred_rollback_and_continue(defer_env):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    b0 = _fit(X, y, params, 6, defer=False)
    b0.rollback_one_iter()

    os.environ["LGBT_DEFER_HOST_TREES"] = "1"
    ds = lgb.Dataset(X, label=y, params=params)
    b1 = lgb.Booster(params=params, train_set=ds)
    for _ in range(6):
        b1.update()
    b1.rollback_one_iter()   # drains, then trims the host list
    assert b1.num_trees() == b0.num_trees() == 5
    assert np.array_equal(b0.predict(X), b1.predict(X))
    b1.update()              # deferral resumes after a drain
    assert b1.num_trees() == 6


def test_deferred_init_score_bias(defer_env):
    X, y = _data()
    init = np.full(len(y), 0.7, np.float32)
    params = {"objective": "regression", "num_leaves": 7, "verbosity": -1}

    def fit(defer):
        os.environ["LGBT_DEFER_HOST_TREES"] = "1" if defer else "0"
        ds = lgb.Dataset(X, label=y, params=params,
                         init_score=init)
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(3):
            bst.update()
        return bst

    b0, b1 = fit(False), fit(True)
    assert b0.model_to_string() == b1.model_to_string()


def test_deferred_eval_during_training(defer_env):
    # eval_valid reads device scores, not host trees: per-iteration eval
    # must not force a drain (pending backlog survives)
    X, y = _data()
    Xv, yv = _data(seed=1)
    params = {"objective": "binary", "metric": "auc", "num_leaves": 15,
              "verbosity": -1}
    os.environ["LGBT_DEFER_HOST_TREES"] = "1"
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(lgb.Dataset(Xv, label=yv, params=params, reference=ds),
                  "v0")
    aucs = []
    for _ in range(5):
        bst.update()
        aucs.append(bst.eval_valid()[0][2])
    assert len(bst.boosting._pending) == 5      # nothing drained yet
    assert aucs[-1] > aucs[0]
    assert bst.num_trees() == 5                 # drain on demand
    assert len(bst.boosting._pending) == 0
