"""Synthetic rows at the Criteo click log's own schema: 13 integer counts
(I1-I13) and 26 categorical columns (C1-C26) as non-negative integer codes.

The counts follow ``criteo_like``'s law (heavy-tailed, floor of a
log-normal-like draw).  Each categorical column draws a code from a
Zipf-like law over its published cardinality: ``code = floor((card + 1) **
u) - 1`` for uniform ``u``, so code ``r`` of ``0 .. card - 1`` has
probability ``log((r + 2) / (r + 1)) / log(card + 1)`` and the code IS the
frequency rank (the usual ordinal encoding of an id column).  All codes
are below 2**24, so an f32 matrix carries them exactly.  A few percent of
the cells of some columns of each kind are missing (NaN), completely at
random, as the log has them.

The click label (25% positives) depends on the counts and on per-category
effects of low- (C9, C20, C6, C17), mid- (C14, C23, C5, C1) and
high-cardinality (C3, C4, C16) columns: a fixed table of effects over a
column's first ``EFFECT_CODES`` ranks, one common effect for the rarer
codes.  Chunked and seeded like ``criteo_like``: every ``seed`` gives other
rows of the same law, whatever the number of threads.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 20
COUNT_COLUMNS = 13
# C1-C26 of the Kaggle Display Advertising Challenge set, as commonly
# reported (configs/criteo-cat.json, "assumed")
CARDINALITIES = (1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3,
                 93145, 5683, 8351593, 3194, 27, 14992, 5461306, 10, 5652,
                 2173, 4, 7046547, 18, 15, 286181, 105, 142572)
FEATURES = COUNT_COLUMNS + len(CARDINALITIES)
CATEGORICAL = list(range(COUNT_COLUMNS, FEATURES))
# matrix column -> share of its cells that are missing
MISSING = {0: 0.05, 2: 0.02, 9: 0.03,                 # I1, I3, I10
           COUNT_COLUMNS + 2: 0.03,                   # C3
           COUNT_COLUMNS + 8: 0.02,                   # C9
           COUNT_COLUMNS + 13: 0.04,                  # C14
           COUNT_COLUMNS + 23: 0.03}                  # C24
# 0-based index into C1-C26 -> weight of the column's effects in the label
EFFECT_WEIGHT = {8: 1.0, 19: 0.8, 5: 0.7, 16: 0.6,   # C9 C20 C6 C17
                 13: 0.9, 22: 0.6, 4: 0.8, 0: 0.9,    # C14 C23 C5 C1
                 2: 1.0, 3: 0.7, 15: 0.8}             # C3 C4 C16
EFFECT_CODES = 4096


def _law(features):
    if features != FEATURES:
        raise ValueError(f"the click log has {FEATURES} columns, "
                         f"not {features}")
    rs = np.random.RandomState(24601)
    w = rs.randn(COUNT_COLUMNS).astype(np.float32) * 0.35
    w[rs.rand(COUNT_COLUMNS) < 0.4] = 0.0
    effects = {}
    for c, weight in EFFECT_WEIGHT.items():
        codes = min(CARDINALITIES[c], EFFECT_CODES)
        effects[COUNT_COLUMNS + c] = (rs.randn(codes) * weight).astype(
            np.float32)
    log_card = np.log(np.asarray(CARDINALITIES, np.float64) + 1.0).astype(
        np.float32)
    top = np.asarray(CARDINALITIES, np.float32) - 1.0
    return w, effects, log_card, top


def _chunk(seed, ci, rows, law):
    w, effects, log_card, top = law
    rng = np.random.default_rng([seed, ci])
    X = rng.random((rows, FEATURES), dtype=np.float32)
    k = COUNT_COLUMNS
    X[:, :k] = np.floor(np.exp(6.0 * X[:, :k] * X[:, :k])) - 1.0
    X[:, k:] = np.minimum(np.floor(np.exp(X[:, k:] * log_card)) - 1.0, top)
    signal = np.log1p(X[:, :k]) @ w
    for col, table in effects.items():
        code = np.minimum(X[:, col], len(table) - 1).astype(np.int32)
        signal += table[code]
    signal += rng.standard_normal(rows, dtype=np.float32) * 0.3 * signal.std()
    for col, share in MISSING.items():
        X[rng.random(rows, dtype=np.float32) < share, col] = np.nan
    return X, signal


def generate(seed, rows, features, threads=12, positive_share=0.25):
    law = _law(features)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, CHUNK_ROWS))

    def fill(ci, thresh):
        lo = starts[ci]
        hi = min(lo + CHUNK_ROWS, rows)
        Xc, signal = _chunk(seed, ci, hi - lo, law)
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
    return X, y, {"categorical_feature": list(CATEGORICAL)}
