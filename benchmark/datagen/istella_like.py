"""Synthetic Istella-like learning-to-rank rows: ``features`` float32 columns
in whole queries, five relevance grades.

The public Istella LETOR set is 220 features a document, queries of very
uneven length and about 96% of documents at grade 0.  Here, from the seed:

- **query lengths** are log-normal around ``rows / queries``, clipped to
  ``min_len``..``max_len`` and then moved by single documents until they sum
  to ``rows`` exactly, so every query is whole and no row is outside one;
- **columns**: the first ``COUNT_SHARE`` are heavy-tailed counts (as
  ``criteo_like``'s), the next ``DENSE_SHARE`` dense uniform codes, the rest
  mostly zero (80% zeros, the others a squared uniform);
- **relevance** of a document is a sparse linear concept of its columns plus
  noise.  A query's own offset sets how many of its documents clear grade 0:
  ``zero_queries`` of the queries hold none (real logs have them; their
  gradients are zero), every other holds at least one, a share of
  ``relevant_share`` x a log-normal factor of its own.  Inside a query the
  documents are cut into grades by their relevance: of those that clear
  grade 0, the upper ``GRADE_CUTS`` shares take grades 4, 3, 2, the rest 1.

Chunked, threaded and keyed by the seed like ``criteo_like``: every ``seed``
gives other rows, other lengths and other grades of the same law, whatever
the number of threads.  Returns ``(X, y, {"group": sizes})``.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 18
COUNT_SHARE = 0.27      # 60 of 220 columns
DENSE_SHARE = 0.46      # 100 of 220
GRADE_CUTS = (0.11, 0.28, 0.55)     # upper shares of the relevant: 4, 3, 2
LENGTHS_KEY = (1 << 40) + 1         # no chunk index reaches these
GRADES_KEY = (1 << 40) + 2


def query_lengths(seed, rows, queries, min_len=10, max_len=1200, sigma=0.9):
    """``queries`` whole lengths in ``min_len``..``max_len`` that sum to
    ``rows``: log-normal, scaled to the mean, clipped, then single documents
    added to (or taken from) queries drawn at random among those not at a
    bound, until the sum is exact."""
    if not queries * min_len <= rows <= queries * max_len:
        raise ValueError(f"{queries} queries of {min_len}-{max_len} "
                         f"documents cannot hold {rows} rows")
    rng = np.random.default_rng([seed, LENGTHS_KEY])
    raw = rng.lognormal(-0.5 * sigma * sigma, sigma, queries)
    scale = rows / queries
    for _ in range(40):     # the clip moves the mean: scale until it fits
        sizes = np.clip(np.rint(raw * scale), min_len, max_len)
        scale *= rows / sizes.sum()
    sizes = sizes.astype(np.int64)
    while (diff := rows - int(sizes.sum())) != 0:
        step = 1 if diff > 0 else -1
        free = np.flatnonzero(sizes < max_len if step > 0 else sizes > min_len)
        take = rng.choice(free, size=min(abs(diff), len(free)), replace=False)
        sizes[take] += step
    return sizes


def _chunk(seed, ci, X, w, k_count, k_dense):
    """Fill the rows ``X`` (a block of the matrix, written in place) and
    return their relevance signal."""
    rng = np.random.default_rng([seed, ci])
    rng.random(out=X, dtype=np.float32)
    counts = np.ascontiguousarray(X[:, :k_count])
    np.multiply(counts, counts, out=counts)
    counts *= 6.0
    np.exp(counts, out=counts)
    np.floor(counts, out=counts)
    counts -= 1.0
    X[:, :k_count] = counts
    sparse = np.ascontiguousarray(X[:, k_count + k_dense:])
    sparse -= 0.8
    np.maximum(sparse, 0.0, out=sparse)
    sparse *= 5.0
    np.multiply(sparse, sparse, out=sparse)
    X[:, k_count + k_dense:] = sparse
    signal = (np.log1p(counts, out=counts) @ w[:k_count]
              + X[:, k_count:] @ w[k_count:])
    signal += rng.standard_normal(len(X), dtype=np.float32) * (
        0.5 * signal.std())
    return signal


def grades(seed, sizes, signal, relevant_share=0.042, zero_queries=0.08):
    """Each query's documents cut into grades 0-4 by ``signal``."""
    rng = np.random.default_rng([seed, GRADES_KEY])
    nq, n = len(sizes), len(signal)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    share = relevant_share * rng.lognormal(-0.18, 0.6, nq)
    k = np.floor(share * sizes + rng.random(nq)).astype(np.int64)
    k = np.clip(k, 1, np.maximum(sizes // 2, 1))
    k[rng.random(nq) < zero_queries] = 0
    qid = np.repeat(np.arange(nq), sizes)
    order = np.lexsort((-signal, qid))      # by query, best document first
    rank = np.arange(n) - starts[qid]       # of the sorted position
    kq = k[qid]                             # qid is sorted already
    upper = (rank + 0.5) / np.maximum(kq, 1)
    g = np.where(rank < kq, 1 + sum(upper < c for c in GRADE_CUTS), 0)
    y = np.empty(n, np.float32)
    y[order] = g
    return y


def generate(seed, rows, features, queries, threads=12, min_len=10,
             max_len=1200, relevant_share=0.042, zero_queries=0.08):
    sizes = query_lengths(seed, rows, queries, min_len, max_len)
    w = np.random.RandomState(220220).randn(features).astype(np.float32)
    w[np.random.RandomState(2016).rand(features) < 0.8] = 0.0
    k_count = int(round(COUNT_SHARE * features))
    k_dense = int(round(DENSE_SHARE * features))
    X = np.empty((rows, features), np.float32)
    signal = np.empty(rows, np.float32)
    starts = list(range(0, rows, CHUNK_ROWS))

    def fill(ci):
        lo = starts[ci]
        hi = min(lo + CHUNK_ROWS, rows)
        signal[lo:hi] = _chunk(seed, ci, X[lo:hi], w, k_count, k_dense)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(len(starts))))
    y = grades(seed, sizes, signal, relevant_share, zero_queries)
    return X, y, {"group": sizes}
