"""Synthetic Criteo-like rows: 13 heavy-tailed counts and 54 dense codes.

The public Criteo set as LightGBM's parallel experiment used it is 67
dense columns (13 integer counts, 26 categoricals expanded by count
statistics): here the first 13 columns are log-normal counts, the rest
uniform, and the click label follows a sparse linear concept with 25%
positives.  Chunked and seeded like ``higgs_like``: every ``seed`` gives
other rows of the same law.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 20
COUNT_COLUMNS = 13


def _chunk(seed, ci, rows, f, w):
    rng = np.random.default_rng([seed, ci])
    X = rng.random((rows, f), dtype=np.float32)
    k = min(COUNT_COLUMNS, f)
    X[:, :k] = np.floor(np.exp(3.0 * X[:, :k] * X[:, :k] * 2.0)) - 1.0
    signal = np.log1p(X[:, :k]) @ w[:k] + X[:, k:] @ w[k:]
    signal += rng.standard_normal(rows, dtype=np.float32) * 0.3 * signal.std()
    return X, signal


def generate(seed, rows, features, threads=12, positive_share=0.25):
    w = np.random.RandomState(54321).randn(features).astype(np.float32)
    w[np.random.RandomState(999).rand(features) < 0.5] = 0.0
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, CHUNK_ROWS))

    def fill(ci, thresh):
        lo = starts[ci]
        hi = min(lo + CHUNK_ROWS, rows)
        Xc, signal = _chunk(seed, ci, hi - lo, features, w)
        if thresh is None:
            thresh = float(np.quantile(signal, 1.0 - positive_share))
        X[lo:hi] = Xc
        y[lo:hi] = signal > thresh
        return thresh

    thresh = fill(0, None)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for fut in [pool.submit(fill, ci, thresh)
                    for ci in range(1, len(starts))]:
            fut.result()
    return X, y
