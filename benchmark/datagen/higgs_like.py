"""Synthetic HIGGS-like rows from a seed (no network, so no real HIGGS).

A copy of ``bench.py``'s ``higgs_like_chunks`` formula (the original is
listed in PERF.md for a later PR to delete): uniform f32 features, a linear
concept drawn from one fixed stream plus two interactions and 20% noise,
labels cut at the first chunk's median.  Chunks are i.i.d. and seeded one
by one, so they are generated in parallel.

Every ``seed`` gives other rows of the same law (the concept ``w`` is the
law's, not the seed's), so a run on another seed trains on another data
set of the same size.  Any whole seed from 0 up works.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 20


def _chunk(seed, ci, rows, f, w):
    rng = np.random.default_rng([seed, ci])
    X = rng.random((rows, f), dtype=np.float32)
    signal = X @ w
    signal += 2.0 * X[:, 0] * X[:, 1] - 1.5 * (X[:, 2] > 0.5) * X[:, 3]
    signal += rng.standard_normal(rows, dtype=np.float32) * 0.2 * signal.std()
    return X, signal


def generate(seed, rows, features, threads=8):
    """-> (X [rows, features] f32, y [rows] f32 in {0, 1})."""
    w = np.random.RandomState(12345).randn(features).astype(np.float32)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, CHUNK_ROWS))

    def fill(ci, thresh):
        lo = starts[ci]
        hi = min(lo + CHUNK_ROWS, rows)
        Xc, signal = _chunk(seed, ci, hi - lo, features, w)
        if thresh is None:
            thresh = float(np.median(signal))
        X[lo:hi] = Xc
        y[lo:hi] = signal > thresh
        return thresh

    thresh = fill(0, None)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for fut in [pool.submit(fill, ci, thresh)
                    for ci in range(1, len(starts))]:
            fut.result()
    return X, y
