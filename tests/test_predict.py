"""Fast-prediction paths: stacked forest, native lib, device forest,
early stop, CSR, batched SHAP.

reference analogues: src/application/predictor.hpp (row-parallel predictor),
src/boosting/prediction_early_stop.cpp, c_api.h:698 (CSR predict).
"""

import numpy as np
import pytest

import example_data
import lightgbm_tpu as lgb
from lightgbm_tpu.predict import StackedForest


@pytest.fixture(scope="module")
def binary_model():
    (X, y, _, _), (Xt, _, _, _) = example_data.binary()
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 31},
                    lgb.Dataset(X, label=y), num_boost_round=20,
                    verbose_eval=False)
    return bst, Xt


@pytest.fixture(scope="module")
def nan_cat_model():
    rng = np.random.RandomState(3)
    n = 2000
    cat = rng.randint(0, 12, n).astype(np.float64)
    other = rng.randn(n)
    other[rng.rand(n) < 0.25] = np.nan
    y = (np.isin(cat, [1, 4, 9]).astype(float) + 0.3 * np.nan_to_num(other)
         > 0.5).astype(float)
    X = np.column_stack([cat, other])
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y, categorical_feature=[0]),
                    num_boost_round=15, verbose_eval=False)
    return bst, X


def _per_tree_raw(bst, X):
    out = np.zeros(len(X))
    for m in bst.models:
        out += m.predict_np(X)
    return out


def test_forest_matches_per_tree(binary_model):
    bst, Xt = binary_model
    np.testing.assert_allclose(bst.predict(Xt, raw_score=True),
                               _per_tree_raw(bst, Xt), rtol=0, atol=0)


def test_forest_matches_per_tree_nan_cat(nan_cat_model):
    bst, X = nan_cat_model
    np.testing.assert_allclose(bst.predict(X, raw_score=True),
                               _per_tree_raw(bst, X), rtol=0, atol=0)


def test_numpy_fallback_matches_native(binary_model):
    bst, Xt = binary_model
    native = bst.predict(Xt, raw_score=True)
    forest = bst._forest(0, 20)
    if forest._native() is None:
        pytest.skip("native lib unavailable")
    forest._native_lib = None
    try:
        fallback = bst.predict(Xt, raw_score=True)
    finally:
        del forest._native_lib  # re-probe on next use
    np.testing.assert_allclose(native, fallback, rtol=0, atol=0)


def test_pred_leaf_layout(binary_model):
    bst, Xt = binary_model
    leaves = bst.predict(Xt, pred_leaf=True)
    assert leaves.shape == (len(Xt), 20)
    per_tree = np.column_stack([m.predict_leaf_np(Xt) for m in bst.models])
    np.testing.assert_array_equal(leaves, per_tree)


def test_device_forest(binary_model):
    bst, Xt = binary_model
    host = bst.predict(Xt, raw_score=True)
    dev = bst.predict(Xt, raw_score=True, device=True)
    # f32 accumulation: equal routing, tiny value drift
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(bst.predict(Xt, pred_leaf=True, device=True),
                                  bst.predict(Xt, pred_leaf=True))


def test_early_stop_binary(binary_model):
    bst, Xt = binary_model
    full = bst.predict(Xt)
    es = bst.predict(Xt, pred_early_stop=True, pred_early_stop_freq=5,
                     pred_early_stop_margin=10.0)
    # margin 10 is the reference default and effectively never fires here
    np.testing.assert_allclose(es, full, rtol=0, atol=0)
    es_tight = bst.predict(Xt, pred_early_stop=True, pred_early_stop_freq=2,
                           pred_early_stop_margin=0.5)
    # the stop must actually fire (scores frozen early) ...
    assert np.abs(es_tight - full).max() > 0
    # ... while decisions agree for confident rows (measured 0.996 on
    # example_data.binary()'s test split)
    agree = ((es_tight > 0.5) == (full > 0.5)).mean()
    assert agree > 0.95


def test_early_stop_multiclass():
    X, y, _, _ = example_data.multiclass()[0]
    bst = lgb.train({"objective": "multiclass", "num_class": 5,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=10, verbose_eval=False)
    full = bst.predict(X)
    es = bst.predict(X, pred_early_stop=True, pred_early_stop_freq=3,
                     pred_early_stop_margin=10.0)
    np.testing.assert_allclose(es, full, rtol=0, atol=0)


def test_csr_predict_no_densify(binary_model):
    scipy_sparse = pytest.importorskip("scipy.sparse")
    bst, Xt = binary_model
    sp = scipy_sparse.csr_matrix(Xt)
    np.testing.assert_allclose(bst.predict(sp), bst.predict(Xt),
                               rtol=0, atol=0)
    # leaf + contrib shapes survive the chunked path
    assert bst.predict(sp, pred_leaf=True).shape == (len(Xt), 20)


def test_batched_shap_matches_scalar(nan_cat_model):
    bst, X = nan_cat_model
    sub = X[:40]
    F = X.shape[1]
    batched = bst.predict(sub, pred_contrib=True)
    scalar = np.zeros((len(sub), F + 1))
    for m in bst.models:
        scalar += m.predict_contrib_np(sub, F)
    np.testing.assert_allclose(batched, scalar, rtol=1e-9, atol=1e-12)
    # SHAP sums to raw prediction
    np.testing.assert_allclose(batched.sum(axis=1),
                               bst.predict(sub, raw_score=True),
                               rtol=1e-9, atol=1e-10)


def test_single_leaf_trees_predict():
    # the stacked forest's sentinel node must route every row of a
    # single-leaf (constant) tree to leaf 0, on all three backends
    from lightgbm_tpu.tree import HostTree
    forest = StackedForest([HostTree.constant(2.5), HostTree.constant(-1.0)])
    X = np.random.RandomState(0).rand(64, 3)
    np.testing.assert_allclose(forest.predict_raw(X)[0], 1.5, rtol=0)
    forest._native_lib = None   # numpy fallback
    np.testing.assert_allclose(forest.predict_raw(X)[0], 1.5, rtol=0)
    from lightgbm_tpu.predict import DeviceForest
    np.testing.assert_allclose(
        DeviceForest(forest, chunk_rows=64).predict_raw(X)[0], 1.5, rtol=1e-6)
