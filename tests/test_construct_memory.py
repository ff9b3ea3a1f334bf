"""Streaming dataset construction + sparse input paths.

reference: the two-pass DatasetLoader never materializes a dense double
matrix (SampleTextDataFromFile / ExtractFeaturesFromFile push rows,
src/io/dataset_loader.cpp:775,1101); here construction walks one column at
a time so peak host memory stays near the caller's input + the uint8
binned matrix (round-3 review, item 8).
"""
import tracemalloc

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import Dataset


def test_construct_no_full_float64_copy():
    """Peak PYTHON-heap growth during construct must stay well under the
    old full-float64-copy cost (n*f*8 bytes).  tracemalloc (numpy hooks
    into it) measures this process-locally, unlike ru_maxrss, whose
    process-lifetime high-water mark earlier tests can poison."""
    n, f = 1_500_000, 20
    X = np.random.RandomState(0).rand(n, f).astype(np.float32)
    ds = Dataset(X, label=np.zeros(n, np.float32), free_raw_data=False)
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        ds.construct()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    full_copy = n * f * 8
    binned = n * f  # uint8 result matrix, the legitimate allocation
    # budget: the binned matrix + one float64 column of scratch, with 2x
    # headroom — far under the old full-copy cost
    assert peak < binned + 0.25 * full_copy, (
        f"construct peak-allocated {peak / 1e6:.0f} MB "
        f"(old full-copy cost {full_copy / 1e6:.0f} MB)")
    assert ds.binned.shape == (n, len(ds.used_features))


def test_construct_float32_matches_float64():
    """Column-wise widening must bin identically to an up-front cast."""
    rng = np.random.RandomState(1)
    X32 = rng.rand(4000, 8).astype(np.float32)
    y = (X32[:, 0] > 0.5).astype(np.float32)
    d32 = Dataset(X32, label=y).construct()
    d64 = Dataset(X32.astype(np.float64), label=y).construct()
    np.testing.assert_array_equal(d32.binned, d64.binned)


def test_sparse_csr_end_to_end():
    """scipy CSR input constructs column-streamed (one dense column of
    scratch at a time) and trains; predictions agree with the dense path."""
    sps = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(0)
    n, f = 3000, 30
    X = sps.random(n, f, density=0.08, random_state=0, format="csr")
    Xd = X.toarray()
    y = (np.asarray(X.sum(axis=1)).ravel()
         > np.median(np.asarray(X.sum(axis=1)))).astype(np.float32)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5}
    bs = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    bd = lgb.train(params, lgb.Dataset(Xd, label=y), num_boost_round=5)
    np.testing.assert_allclose(bs.predict(Xd), bd.predict(Xd), rtol=1e-6)
