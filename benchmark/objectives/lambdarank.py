"""The reference's own LambdaRank-NDCG objective (LightGBM's ``lambdarank``),
float64 NumPy from the published equations (Burges 2010, "From RankNet to
LambdaRank to LambdaMART"; LightGBM ``src/objective/rank_objective.hpp``,
``LambdarankNDCG::GetGradientsForOneQuery``); it imports nothing of the
program.  ``aux["group"]`` holds the query lengths, in row order.

For every query: documents ranked by score, best first, equal scores in row
order (a stable sort); discount of rank r (from 0) = 1 / log2(2 + r); gain
of a document = ``label_gain[label]``; inverse max DCG = 1 / the DCG of the
labels sorted best first, cut at ``lambdarank_truncation_level`` (0 for a
query without a relevant document).  The pairs are LightGBM's: ranks i < j
with i below the truncation level and unequal labels; the document with the
larger label is ``high``.  For each,

    delta = (gain_high - gain_low) |disc_i - disc_j| inverse_max_dcg
    delta /= 0.01 + |s_high - s_low|       under lambdarank_norm, where the
                                           query's best and worst score differ
    rho = 1 / (1 + exp(sigmoid (s_high - s_low)))
    lambda_high -= sigmoid delta rho;  lambda_low += sigmoid delta rho
    hessian of both += sigmoid^2 delta rho (1 - rho)

and under ``lambdarank_norm`` a query's lambdas and hessians are scaled by
log2(1 + S) / S, S the sum of 2 sigmoid delta rho over its pairs (where
S > 0).  ``loss`` is 1 - the mean over queries of NDCG@10, a query without
a relevant document counting 1.0 as in LightGBM's metric.

Departures from that source, each of no reach here: rho is computed, where
LightGBM reads it from a table of 2^20 entries over the clipped range;
LightGBM adds each pair in float32 into the two documents, here the sums
are float64; a score of -inf (LightGBM's ``kMinScore``, a document taken
out of the ranking) has no special case.  Nothing is padded, truncated
beyond the truncation level the parameters state, or approximated: queries
of one length are evaluated together, [queries, ranks below the truncation
level, length], on a few threads.

The parameters are the ones the benchmark's configurations state for this
objective (``configs/*.json`` beside this directory whose ``params`` name
``lambdarank``): the harness hands an objective the dataset fields only, so
the file reads them itself, and refuses to guess where two configurations
state different ones.  ``configure`` sets them for a test.
"""
import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

DEFAULTS = {"sigmoid": 1.0, "lambdarank_norm": True,
            "lambdarank_truncation_level": 20,
            "label_gain": [float((1 << i) - 1) for i in range(31)]}
NDCG_AT = 10
THREADS = 12
BATCH_DOCUMENTS = 1 << 17


def _stated():
    found = {}
    for path in sorted((Path(__file__).resolve().parents[1]
                        / "configs").glob("*.json")):
        params = json.loads(path.read_text()).get("params", {})
        if params.get("objective") == "lambdarank":
            found[path.name] = {k: params.get(k, v)
                                for k, v in DEFAULTS.items()}
    stated = list(found.values())
    if any(s != stated[0] for s in stated[1:]):
        raise ValueError(f"configurations state different lambdarank "
                         f"parameters: {found}")
    return dict(stated[0]) if stated else dict(DEFAULTS)


PARAMS = _stated()


def configure(**params):
    """Other parameters than the configurations' (tests)."""
    unknown = set(params) - set(DEFAULTS)
    if unknown:
        raise KeyError(f"not a lambdarank parameter: {sorted(unknown)}")
    PARAMS.update(params)


def _queries(aux, n):
    if not aux or aux.get("group") is None:
        raise ValueError("objectives/lambdarank.py needs aux['group']")
    if aux.get("weight") is not None:
        raise NotImplementedError("objectives/lambdarank.py follows "
                                  "unweighted rows only")
    sizes = np.asarray(aux["group"], np.int64)
    if sizes.sum() != n:
        raise ValueError(f"group sizes sum to {sizes.sum()}, rows are {n}")
    return sizes, np.concatenate([[0], np.cumsum(sizes)[:-1]])


def _by_length(sizes, starts):
    """[(length, row index matrix [queries of that length, length])], a
    length's queries in pieces of at most ``BATCH_DOCUMENTS`` documents."""
    order = np.argsort(sizes, kind="stable")
    cuts = np.flatnonzero(np.diff(sizes[order])) + 1
    out = []
    for q in np.split(order, cuts):
        L = int(sizes[q[0]])
        for part in np.array_split(q, -(-len(q) * L // BATCH_DOCUMENTS)):
            out.append((L, starts[part][:, None] + np.arange(L)))
    return out


def _over_lengths(fn, batches):
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(fn, batches))


def _discount(length):
    return 1.0 / np.log2(2.0 + np.arange(length))


def _max_dcg(gain, at):
    """DCG of each row of ``gain`` [b, L] sorted best first, cut at ``at``."""
    best = -np.sort(-gain, axis=1)[:, :at]
    return best @ _discount(best.shape[1])


def gradients(score, y, aux=None):
    score = np.asarray(score, np.float64)
    n = len(score)
    sizes, starts = _queries(aux, n)
    sig = float(PARAMS["sigmoid"])
    norm = bool(PARAMS["lambdarank_norm"])
    level = int(PARAMS["lambdarank_truncation_level"])
    label = np.asarray(y).astype(np.int64)
    gain_of = np.asarray(PARAMS["label_gain"], np.float64)[label]
    lam = np.zeros(n)
    hes = np.zeros(n)

    def batch(args):
        L, idx = args
        top = min(level, L - 1)         # ranks i < cnt - 1 and below the level
        if top <= 0:
            return
        order = np.argsort(-score[idx], axis=1, kind="stable")
        idx = np.take_along_axis(idx, order, axis=1)    # rows by rank
        s, lab, gain = score[idx], label[idx], gain_of[idx]
        max_dcg = _max_dcg(gain, level)
        inv = np.where(max_dcg > 0, 1.0 / np.maximum(max_dcg, 1e-300), 0.0)
        disc = _discount(L)
        i, j = np.arange(top)[None, :, None], np.arange(L)[None, None, :]
        pair = (j > i) & (lab[:, :top, None] != lab[:, None, :])
        # +1 where rank i holds the larger label, -1 where rank j does
        side = np.where(lab[:, :top, None] > lab[:, None, :], 1.0, -1.0)
        ds = (s[:, :top, None] - s[:, None, :]) * side      # high - low
        delta = ((gain[:, :top, None] - gain[:, None, :]) * side
                 * np.abs(disc[:top, None] - disc[None, :])
                 * inv[:, None, None])
        if norm:
            ranged = (s[:, 0] != s[:, -1])[:, None, None]
            delta = np.where(ranged, delta / (0.01 + np.abs(ds)), delta)
        with np.errstate(over="ignore"):
            rho = 1.0 / (1.0 + np.exp(sig * ds))
        p_lam = np.where(pair, sig * delta * rho, 0.0)
        p_hes = np.where(pair, sig * sig * delta * rho * (1.0 - rho), 0.0)
        toward = p_lam * side           # what rank j gains, rank i loses
        g = toward.sum(axis=1)
        g[:, :top] -= toward.sum(axis=2)
        h = p_hes.sum(axis=1)
        h[:, :top] += p_hes.sum(axis=2)
        if norm:
            total = 2.0 * p_lam.sum(axis=(1, 2))
            factor = np.where(total > 0, np.log2(1.0 + total)
                              / np.maximum(total, 1e-300), 1.0)[:, None]
            g, h = g * factor, h * factor
        lam[idx] = g
        hes[idx] = h

    _over_lengths(batch, _by_length(sizes, starts))
    return lam, hes


def loss(score, y, aux=None):
    score = np.asarray(score, np.float64)
    sizes, starts = _queries(aux, len(score))
    gain_of = np.asarray(PARAMS["label_gain"],
                         np.float64)[np.asarray(y).astype(np.int64)]

    def batch(args):
        L, idx = args
        order = np.argsort(-score[idx], axis=1, kind="stable")
        at = min(NDCG_AT, L)
        gain = gain_of[idx]
        dcg = np.take_along_axis(gain, order[:, :at], axis=1) @ _discount(at)
        best = _max_dcg(gain, NDCG_AT)
        return np.where(best > 0, dcg / np.maximum(best, 1e-300), 1.0).sum()

    return 1.0 - float(sum(_over_lengths(batch, _by_length(sizes, starts)))
                       / len(sizes))


def init_score(y, aux=None):
    """LambdaRank has no boost from average: the scores start at 0."""
    return 0.0
