"""End-to-end training tests on seeded data in the shapes of the reference
example datasets (``tests/example_data.py``).

Mirrors the reference test strategy (tests/python_package_test/test_engine.py):
train small models per objective and assert metric thresholds.  A threshold
with "measured" beside it was measured on that data at commit 961dc8b.
"""

import numpy as np
import pytest

import example_data
import lightgbm_tpu as lgb


def _arrays(splits):
    train, test = splits
    return train.X, train.y, test.X, test.y


@pytest.fixture(scope="module")
def binary_data():
    return _arrays(example_data.binary())


def test_binary(binary_data):
    X, y, Xt, yt = binary_data
    params = {"objective": "binary", "metric": "auc", "verbosity": -1,
              "num_leaves": 31, "learning_rate": 0.1}
    train = lgb.Dataset(X, label=y)
    valid = lgb.Dataset(Xt, label=yt, reference=train)
    evals = {}
    bst = lgb.train(params, train, num_boost_round=50, valid_sets=[valid],
                    evals_result=evals, verbose_eval=False)
    # measured 0.8073 on example_data.binary()'s test split
    auc = evals["valid_0"]["auc"][-1]
    assert auc > 0.78
    pred = bst.predict(Xt)
    assert pred.min() >= 0 and pred.max() <= 1
    from sklearn.metrics import roc_auc_score
    np.testing.assert_allclose(roc_auc_score(yt, pred), auc, atol=1e-6)


def test_regression():
    X, y, Xt, yt = _arrays(example_data.regression())
    params = {"objective": "regression", "metric": "l2", "verbosity": -1}
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=50,
                    valid_sets=[lgb.Dataset(Xt, label=yt, reference=train)],
                    evals_result=evals, verbose_eval=False)
    l2_start = evals["valid_0"]["l2"][0]
    l2_end = evals["valid_0"]["l2"][-1]
    assert l2_end < l2_start
    # measured 2.5047 -> 1.0415 on example_data.regression()'s test split
    # (label variance 2.84, of which the law's noise is 1.0)
    assert l2_end < 1.10


def test_regression_l1():
    X, y, _, _ = _arrays(example_data.regression())
    params = {"objective": "regression_l1", "metric": "l1", "verbosity": -1}
    evals = {}
    train = lgb.Dataset(X, label=y)
    lgb.train(params, train, num_boost_round=30,
              valid_sets=[lgb.Dataset(X, label=y, reference=train)],
              evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["l1"][-1] < evals["valid_0"]["l1"][0]


def test_multiclass():
    X, y, _, _ = _arrays(example_data.multiclass())
    params = {"objective": "multiclass", "num_class": 5,
              "metric": "multi_logloss", "verbosity": -1}
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=30,
                    valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                    evals_result=evals, verbose_eval=False)
    # measured 1.0412 @30 rounds on example_data.multiclass()
    assert evals["valid_0"]["multi_logloss"][-1] < 1.08
    pred = bst.predict(X)
    assert pred.shape == (len(y), 5)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-5)
    acc = (pred.argmax(axis=1) == y).mean()
    assert acc > 0.65  # measured 0.6871


def test_lambdarank():
    X, y, _, group = example_data.rank()[0]
    params = {"objective": "lambdarank", "metric": "ndcg", "verbosity": -1,
              "eval_at": [1, 3, 5]}
    evals = {}
    train = lgb.Dataset(X, label=y, group=group)
    lgb.train(params, train, num_boost_round=30,
              valid_sets=[lgb.Dataset(X, label=y, group=group, reference=train)],
              evals_result=evals, verbose_eval=False)
    # measured 0.6973 after one round, 0.9939 after 30, on
    # example_data.rank()'s train split (the validation set is the train set)
    assert evals["valid_0"]["ndcg@3"][-1] > 0.95


def test_early_stopping():
    X, y, Xt, yt = _arrays(example_data.binary())
    params = {"objective": "binary", "metric": "binary_logloss", "verbosity": -1}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=500,
                    valid_sets=[lgb.Dataset(Xt, label=yt, reference=train)],
                    early_stopping_rounds=5, verbose_eval=False)
    assert bst.best_iteration < 500
    assert bst.current_iteration() <= bst.best_iteration + 5 + 1


def test_missing_values_nan():
    rng = np.random.RandomState(0)
    n = 1000
    X = rng.randn(n, 3)
    y = (X[:, 0] > 0).astype(float)
    X[rng.rand(n) < 0.3, 0] = np.nan  # 30% missing in the signal feature
    params = {"objective": "binary", "metric": "auc", "verbosity": -1,
              "num_leaves": 7}
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=20,
                    valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                    evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["auc"][-1] > 0.9
    # NaN rows must predict without error
    p = bst.predict(X)
    assert np.isfinite(p).all()


def test_categorical_feature():
    rng = np.random.RandomState(1)
    n = 2000
    cat = rng.randint(0, 10, n).astype(np.float64)
    other = rng.randn(n)
    y = (np.isin(cat, [2, 5, 7]).astype(float) + 0.1 * rng.randn(n) > 0.5)
    X = np.column_stack([cat, other])
    params = {"objective": "binary", "metric": "auc", "verbosity": -1,
              "num_leaves": 7, "min_data_in_leaf": 5}
    evals = {}
    train = lgb.Dataset(X, label=y.astype(float), categorical_feature=[0])
    bst = lgb.train(params, train, num_boost_round=20,
                    valid_sets=[lgb.Dataset(X, label=y.astype(float),
                                            reference=train)],
                    evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["auc"][-1] > 0.95
    assert np.isfinite(bst.predict(X)).all()


def test_goss():
    X, y, _, _ = _arrays(example_data.binary())
    params = {"objective": "binary", "boosting": "goss", "metric": "auc",
              "verbosity": -1, "learning_rate": 0.1}
    evals = {}
    train = lgb.Dataset(X, label=y)
    lgb.train(params, train, num_boost_round=30,
              valid_sets=[lgb.Dataset(X, label=y, reference=train)],
              evals_result=evals, verbose_eval=False)
    # measured 0.9013 on example_data.binary()'s train split (plain gbdt at
    # the same config: 0.9042).  This repo implements GOSS's intended
    # sampling; what the reference checkout does instead is in
    # tests/test_parity.py's docstring.
    assert evals["valid_0"]["auc"][-1] > 0.893


def test_bagging():
    X, y, _, _ = _arrays(example_data.binary())
    params = {"objective": "binary", "metric": "auc", "verbosity": -1,
              "bagging_fraction": 0.7, "bagging_freq": 1, "bagging_seed": 7}
    evals = {}
    train = lgb.Dataset(X, label=y)
    lgb.train(params, train, num_boost_round=30,
              valid_sets=[lgb.Dataset(X, label=y, reference=train)],
              evals_result=evals, verbose_eval=False)
    # measured 0.9049 on example_data.binary()'s train split
    assert evals["valid_0"]["auc"][-1] > 0.893


def test_model_save_load_roundtrip(tmp_path, binary_data):
    X, y, Xt, yt = binary_data
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10,
                    verbose_eval=False)
    pred = bst.predict(Xt)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    bst2 = lgb.Booster(model_file=path)
    pred2 = bst2.predict(Xt)
    np.testing.assert_allclose(pred, pred2, rtol=1e-9, atol=1e-12)


def test_continue_train(binary_data):
    X, y, Xt, yt = binary_data
    params = {"objective": "binary", "metric": "auc", "verbosity": -1}
    b1 = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10,
                   verbose_eval=False)
    auc1 = _auc(yt, b1.predict(Xt))
    train2 = lgb.Dataset(X, label=y, free_raw_data=False)
    b2 = lgb.train(params, train2, num_boost_round=10, init_model=b1,
                   verbose_eval=False)
    auc2 = _auc(yt, b2.predict(Xt))
    assert b2.num_trees() == 20
    assert auc2 >= auc1 - 0.005


def test_custom_objective(binary_data):
    X, y, Xt, yt = binary_data

    def logloss_obj(score, dataset):
        lbl = dataset.get_label()
        p = 1.0 / (1.0 + np.exp(-score))
        return p - lbl, p * (1 - p)

    params = {"objective": "none", "verbosity": -1}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=30, fobj=logloss_obj,
                    verbose_eval=False)
    auc = _auc(yt, bst.predict(Xt, raw_score=True))
    # measured 0.8033 on example_data.binary()'s test split (test_binary's
    # 50 rounds reach 0.8073)
    assert auc > 0.775


def test_weights():
    X, y, w, _ = example_data.binary()[0]
    params = {"objective": "binary", "metric": "auc", "verbosity": -1}
    evals = {}
    train = lgb.Dataset(X, label=y, weight=w)
    lgb.train(params, train, num_boost_round=20,
              valid_sets=[lgb.Dataset(X, label=y, weight=w, reference=train)],
              evals_result=evals, verbose_eval=False)
    # measured 0.8917 on example_data.binary()'s train split and weights
    assert evals["valid_0"]["auc"][-1] > 0.884


def test_cv():
    X, y, _, _ = _arrays(example_data.binary())
    params = {"objective": "binary", "metric": "binary_logloss", "verbosity": -1}
    res = lgb.cv(params, lgb.Dataset(X, label=y), num_boost_round=10, nfold=3,
                 stratified=True, shuffle=True)
    assert len(res["binary_logloss-mean"]) == 10
    assert res["binary_logloss-mean"][-1] < res["binary_logloss-mean"][0]


def test_feature_importance(binary_data):
    X, y, _, _ = binary_data
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=10,
                    verbose_eval=False)
    imp = bst.feature_importance("split")
    assert imp.sum() > 0
    gain = bst.feature_importance("gain")
    assert (gain >= 0).all() and gain.sum() > 0


def test_dataset_save_binary(tmp_path):
    X, y, _, _ = _arrays(example_data.binary())
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    path = str(tmp_path / "data.bin")
    ds.save_binary(path)
    ds2 = lgb.Dataset.load_binary(path)
    np.testing.assert_array_equal(ds.binned, ds2.binned)
    np.testing.assert_array_equal(ds.get_label(), ds2.get_label())
    # trainable from the reloaded dataset
    bst = lgb.train({"objective": "binary", "verbosity": -1}, ds2,
                    num_boost_round=5, verbose_eval=False)
    assert bst.num_trees() == 5


def _auc(y, p):
    from sklearn.metrics import roc_auc_score
    return roc_auc_score(y, p)


def test_dart():
    X, y, _, _ = _arrays(example_data.binary())
    params = {"objective": "binary", "boosting": "dart", "metric": "auc",
              "verbosity": -1, "drop_rate": 0.5, "skip_drop": 0.0}
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=25,
                    valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                    evals_result=evals, verbose_eval=False)
    traj = evals["valid_0"]["auc"]
    # drop_rate=0.5 + skip_drop=0 is aggressive dropout; measured 0.8525
    # on example_data.binary()'s train split
    assert traj[-1] > 0.834
    p = bst.predict(X)
    assert np.isfinite(p).all() and 0 <= p.min() and p.max() <= 1


def test_random_forest():
    X, y, _, _ = _arrays(example_data.binary())
    params = {"objective": "binary", "boosting": "rf", "metric": "auc",
              "verbosity": -1, "bagging_freq": 1, "bagging_fraction": 0.6,
              "feature_fraction": 0.8}
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=20,
                    valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                    evals_result=evals, verbose_eval=False)
    # measured 0.8721 on example_data.binary()'s train split
    assert evals["valid_0"]["auc"][-1] > 0.855
    p = bst.predict(X)
    # averaged probabilities, not a boosted sum
    assert np.isfinite(p).all() and 0 <= p.min() and p.max() <= 1
    # rf without bagging must be rejected (reference CHECK, rf.hpp:28)
    with pytest.raises(ValueError):
        lgb.train({"objective": "binary", "boosting": "rf", "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2)


def test_dart_rf_model_roundtrip(tmp_path):
    X, y, _, _ = _arrays(example_data.binary())
    for boosting, extra in (("dart", {"drop_rate": 0.3, "skip_drop": 0.2}),
                            ("rf", {"bagging_freq": 1, "bagging_fraction": 0.7})):
        params = {"objective": "binary", "verbosity": -1, "boosting": boosting,
                  "num_leaves": 7, **extra}
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                        verbose_eval=False)
        pred = bst.predict(X)
        path = str(tmp_path / f"{boosting}.txt")
        bst.save_model(path)
        pred2 = lgb.Booster(model_file=path).predict(X)
        np.testing.assert_allclose(pred, pred2, rtol=1e-6, atol=1e-9)


def test_monotone_constraints():
    # reference: test_engine.py:1000 test_monotone_constraint — but stricter:
    # we assert actual prediction monotonicity (needs descendant bound
    # propagation, monotone_constraints.hpp:44, not just the local check)
    rng = np.random.RandomState(42)
    n = 2000
    x0, x1, x2 = rng.rand(n), rng.rand(n), rng.rand(n)
    y = (5 * x0 + np.sin(10 * np.pi * x0)
         - 5 * x1 - np.cos(10 * np.pi * x1)
         + 10 * x2 + rng.rand(n))
    X = np.column_stack([x0, x1, x2])
    params = {"objective": "regression", "metric": "l2", "verbosity": -1,
              "monotone_constraints": [1, -1, 0], "num_leaves": 31,
              "min_data_in_leaf": 5}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=30)

    grid = np.linspace(0.0, 1.0, 101)
    base = rng.rand(10, 3)
    for row in base:
        sweep = np.tile(row, (grid.size, 1))
        sweep[:, 0] = grid
        p = bst.predict(sweep)
        assert (np.diff(p) >= -1e-10).all(), "feature 0 must be non-decreasing"
        sweep = np.tile(row, (grid.size, 1))
        sweep[:, 1] = grid
        p = bst.predict(sweep)
        assert (np.diff(p) <= 1e-10).all(), "feature 1 must be non-increasing"


def test_dart_boost_from_average_applied_once():
    # regression with a large label mean: a double-added init score (the
    # round-1 DART bug) shifts every gradient by ~mean and wrecks the fit
    rng = np.random.RandomState(0)
    X = rng.rand(600, 5)
    y = 100.0 + X @ np.arange(1.0, 6.0) + rng.randn(600) * 0.1
    params = {"objective": "regression", "boosting": "dart", "metric": "l2",
              "verbosity": -1, "num_leaves": 15, "min_data_in_leaf": 5,
              "drop_rate": 0.2, "learning_rate": 0.2}
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train(params, train, num_boost_round=30,
                    valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                    evals_result=evals, verbose_eval=False)
    pred = bst.predict(X)
    # eval metric must agree with saved-model predictions: with the init
    # score double-added, internal scores sit ~100 above what the saved
    # model predicts and the two RMSEs diverge wildly.  (Mean drift of a
    # few units is genuine DART: dropped early trees carry the folded-in
    # init bias and are renormalized — the reference behaves the same.)
    rmse_pred = float(np.sqrt(np.mean((pred - y) ** 2)))
    rmse_eval = float(np.sqrt(evals["valid_0"]["l2"][-1]))
    assert abs(rmse_pred - rmse_eval) < 0.05 * max(rmse_eval, 1e-3)
    # and the fit must actually converge toward the target, not to a
    # double-shifted score (which plateaus ~100 away)
    assert rmse_pred < 8.0


def test_dart_continue_training_drops_only_new_trees(tmp_path):
    # reference: dart.hpp:108 drops num_init_iteration_ + i — init-model
    # trees are never dropped/rescaled during continued DART training
    X, y, _, _ = _arrays(example_data.binary())
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15}
    base = lgb.train(params, lgb.Dataset(X, label=y, free_raw_data=False),
                     num_boost_round=5)
    init_leaf_values = [m.leaf_value.copy() for m in base.boosting.models]

    dart_params = dict(params, boosting="dart", drop_rate=1.0, skip_drop=0.0)
    bst = lgb.train(dart_params,
                    lgb.Dataset(X, label=y, free_raw_data=False),
                    num_boost_round=5, init_model=base)
    assert bst.boosting.num_init_iteration == 5
    assert len(bst.boosting.models) == 10
    # init trees untouched (drop_rate=1 rescales every this-run tree)
    for m, lv in zip(bst.boosting.models[:5], init_leaf_values):
        np.testing.assert_array_equal(m.leaf_value, lv)
    p = bst.predict(X)
    assert np.isfinite(p).all()


def test_extra_trees(binary_data):
    # reference: test_engine.py:1961 — extra_trees must change the trained
    # model (it was a parsed-but-ignored parameter in round 1) and still learn
    X, y, Xt, yt = binary_data
    base = {"objective": "binary", "metric": "auc", "verbosity": -1,
            "num_leaves": 15}
    ev_n, ev_x, ev_x2 = {}, {}, {}

    def run(extra, seed, ev):
        params = dict(base, extra_trees=extra, extra_trees_seed=seed)
        train = lgb.Dataset(X, label=y)
        return lgb.train(params, train, num_boost_round=10,
                         valid_sets=[lgb.Dataset(Xt, label=yt, reference=train)],
                         evals_result=ev, verbose_eval=False)

    bst_n = run(False, 6, ev_n)
    bst_x = run(True, 6, ev_x)
    bst_x2 = run(True, 6, ev_x2)
    # deterministic under a fixed seed
    for m1, m2 in zip(bst_x.boosting.models, bst_x2.boosting.models):
        np.testing.assert_array_equal(m1.threshold_in_bin, m2.threshold_in_bin)
    # random thresholds actually used: models differ from exact search
    same = all(
        np.array_equal(mn.threshold_in_bin, mx.threshold_in_bin)
        and np.array_equal(mn.split_feature, mx.split_feature)
        for mn, mx in zip(bst_n.boosting.models, bst_x.boosting.models))
    assert not same, "extra_trees must alter threshold selection"
    # and still learn (measured on example_data.binary()'s test split:
    # 0.7935 at 10 rounds; exact search 0.7941)
    assert ev_x["valid_0"]["auc"][-1] > 0.754


def test_feature_fraction_bynode(binary_data):
    X, y, Xt, yt = binary_data
    base = {"objective": "binary", "metric": "auc", "verbosity": -1,
            "num_leaves": 31, "feature_fraction_seed": 3}
    ev = {}

    def run(frac, ev_):
        params = dict(base, feature_fraction_bynode=frac)
        train = lgb.Dataset(X, label=y)
        return lgb.train(params, train, num_boost_round=10,
                         valid_sets=[lgb.Dataset(Xt, label=yt, reference=train)],
                         evals_result=ev_, verbose_eval=False)

    bst_full = run(1.0, {})
    bst_bn = run(0.25, ev)
    # per-node sampling must change which features are split on
    feats_full = [m.split_feature.copy() for m in bst_full.boosting.models]
    feats_bn = [m.split_feature.copy() for m in bst_bn.boosting.models]
    assert any(not np.array_equal(a, b) for a, b in zip(feats_full, feats_bn))
    # a single node sees only ~7 of 28 features, but across nodes coverage
    # stays broad and the model still learns (measured on
    # example_data.binary()'s test split: 0.7954 at 10 rounds; all
    # features at every node 0.7995)
    assert ev["valid_0"]["auc"][-1] > 0.757


def test_refit(binary_data, tmp_path):
    # reference: test_engine.py:1083 test_refit + GBDT::RefitTree
    X, y, Xt, yt = binary_data
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbosity": -1, "num_leaves": 15, "min_data_in_leaf": 20}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)
    err_orig = float(np.mean((bst.predict(Xt) > 0.5) != yt))

    # decay 0: leaf values entirely re-fit to the new (test) data
    refitted = bst.refit(Xt, yt, decay_rate=0.0)
    err_refit = float(np.mean((refitted.predict(Xt) > 0.5) != yt))
    assert err_refit < err_orig  # reference asserts the same inequality
    # structures untouched, only leaf values changed
    for m0, m1 in zip(bst.models, refitted.models):
        np.testing.assert_array_equal(m0.split_feature, m1.split_feature)
        np.testing.assert_array_equal(m0.threshold_in_bin, m1.threshold_in_bin)
        assert not np.allclose(m0.leaf_value, m1.leaf_value)
    # decay 1: leaf values unchanged
    kept = bst.refit(Xt, yt, decay_rate=1.0)
    for m0, m1 in zip(bst.models, kept.models):
        np.testing.assert_allclose(m0.leaf_value, m1.leaf_value, rtol=1e-12)

    # refit from a loaded model file (no training state)
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    loaded = lgb.Booster(model_file=path)
    refit2 = loaded.refit(Xt, yt, decay_rate=0.0)
    np.testing.assert_allclose(refit2.predict(Xt), refitted.predict(Xt),
                               rtol=1e-5, atol=1e-7)


def test_early_stopped_model_round_trips_at_best_iteration(binary_data):
    """reference: Booster.save_model defaults num_iteration=best_iteration
    (basic.py:2407) — a save/load round trip must not change predictions."""
    X, y, Xt, yt = binary_data
    tr = lgb.Dataset(X, label=y)
    va = tr.create_valid(Xt, label=yt)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "metric": "auc", "verbosity": -1},
                    tr, num_boost_round=50, valid_sets=[va],
                    callbacks=[lgb.early_stopping(3, verbose=False)])
    assert 0 < bst.best_iteration < 50
    pred = bst.predict(X)
    re = lgb.Booster(model_str=bst.model_to_string())
    assert re.num_trees() == bst.best_iteration
    np.testing.assert_allclose(re.predict(X), pred, rtol=1e-9)
    # explicit num_iteration=0 still saves everything
    full = lgb.Booster(model_str=bst.model_to_string(num_iteration=0))
    assert full.num_trees() == bst.num_trees()


def test_compile_cache_on_by_default(tmp_path, monkeypatch):
    """The persistent XLA compile cache is wired at engine init with no
    flag: the resolved directory gets created and populated, and a second
    (warm) training of the same shape reuses it byte-for-byte.  (Which
    directory is resolved: tests/test_platform_rules.py.)"""
    import jax

    from lightgbm_tpu.utils import platform as PF
    rng = np.random.RandomState(0)
    X = rng.randn(400, 6)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    cache = tmp_path / "xla_cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(PF, "compile_cache_dir", lambda: str(cache))
    prev = jax.config.jax_compilation_cache_dir
    try:
        params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
        m1 = lgb.train(params, lgb.Dataset(X, label=y), 3,
                       verbose_eval=False).model_to_string()
        assert jax.config.jax_compilation_cache_dir == str(cache)
        assert cache.is_dir()
        n_cold = PF.compile_cache_entries(str(cache))
        m2 = lgb.train(params, lgb.Dataset(X, label=y), 3,
                       verbose_eval=False).model_to_string()
        assert m1 == m2
        assert PF.compile_cache_entries(str(cache)) >= n_cold
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
