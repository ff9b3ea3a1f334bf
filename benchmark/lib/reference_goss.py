"""The plain reference for training cells under gradient-based one-side
sampling (GOSS: Ke et al. 2017, Algorithm 2; LightGBM ``boosting=goss``):
float64 NumPy, importing nothing of the program.  It takes the pieces of
``reference_gbdt`` as they are (routing, per-leaf sums, the gain of a split
from its children, the split check) and adds two things:

- *following a tree from the row weights it was grown on*.  A sampled
  round multiplies each row's gradient and hessian by its weight (1 for the
  top rows, ``(n - top_k) / other_k`` for the sampled rest, 0 for the
  others), so a leaf's G and H are the sums of ``w * g`` and ``w * h`` over
  its rows and its count is its rows of nonzero weight.  Every row, of
  weight 0 too, is routed and takes its leaf's value.  The weights are the
  program's own (``Booster.boosting.last_row_weights``); whether they are
  the right sample is the second thing;
- *checking the sample* from the program's own train score before the
  round, with the reference's own gradients (``sample_numbers``): the top
  set against the reference's own k-th largest |g * h|, the rest's count
  and multiplier, and whether the rest is a uniform draw.

What the sample is owed (goss.hpp, ``GOSSStrategy::Helper``): ``top_k =
max(1, floor(top_rate * n))``, ``other_k = floor(other_rate * n)``, every
row whose |g * h| is at least the k-th largest kept at weight 1 (ties all
kept), exactly ``other_k`` rows of the rest at weight ``(n - top_k) /
other_k``, the rest drawn with equal chances.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .reference_gbdt import (_slice_hists, best_splits, bin_edges, leaf_sums,
                             route, split_gains)

BAND = 1e-4          # |g*h| this close to the threshold (relative) is a tie
BLOCKS = 64          # contiguous row blocks of the draw's uniformity check


def sizes(n, top_rate, other_rate):
    """(top_k, other_k, multiplier) of a sampled round over ``n`` rows."""
    top_k = max(1, int(top_rate * n))
    other_k = int(other_rate * n)
    return top_k, other_k, (n - top_k) / max(other_k, 1)


def sample_numbers(before, weights, y, objective, top_rate, other_rate,
                   aux=None):
    """The sample of one round against the reference's own reading of it.

    ``before`` is the program's train score the round started from,
    ``weights`` the weights the program grew the round's tree on (both
    float64, one a row).  Rows of weight 1 are the program's top set (the
    multiplier is not 1 at the configured rates)."""
    n = len(y)
    top_k, other_k, amp = sizes(n, top_rate, other_rate)
    g, h = objective.gradients(before, y, aux)
    s = np.abs(g * h)
    thr = float(np.partition(s, n - top_k)[n - top_k])
    top = weights == 1.0
    sampled = (weights != 0.0) & ~top
    out = {
        "goss_top_missing": float(np.sum(~top & (s > thr * (1 + BAND)))),
        "goss_top_extra": float(np.sum(top & (s < thr * (1 - BAND)))),
        "goss_rest_count_gap": float(abs(int(sampled.sum()) - other_k)),
        "goss_weight_gap": float(np.max(np.minimum(
            np.minimum(np.abs(weights), np.abs(weights - 1.0)),
            np.abs(weights - amp) / amp))),
    }
    out["goss_rest_bias_z"] = rest_bias_z(s, ~top, sampled)
    return out


def rest_bias_z(s, rest, sampled):
    """Whether the sampled rows are a uniform draw from the rest, in units
    of the draw's own standard deviation: the larger of the z-score of the
    sampled rows' mean ``s`` against the whole rest's, and the largest over
    ``BLOCKS`` contiguous row blocks of the z-score of the block's sampled
    count (a draw without replacement: hypergeometric)."""
    N, m = int(rest.sum()), int(sampled.sum())
    if m == 0 or m >= N:
        return 0.0
    fpc = (N - m) / (N - 1)
    sr = s[rest]
    z = abs(s[sampled].mean() - sr.mean()) / np.sqrt(sr.var() / m * fpc)
    cuts = np.linspace(0, len(s), BLOCKS + 1).astype(np.int64)
    for a, b in zip(cuts[:-1], cuts[1:]):
        p = rest[a:b].sum() / N
        sd = np.sqrt(m * p * (1 - p) * fpc)
        if sd > 0:
            z = max(z, abs(sampled[a:b].sum() - m * p) / sd)
    return float(z)


def rounding_z(step, scales, num_bins):
    """The largest gap of a leaf's gradient and hessian sums, program
    against reference, in units of what stochastic rounding to
    ``num_bins`` levels may add: each of a leaf's C rows of nonzero weight
    is rounded to a neighbouring level of the round's scale ``s`` (the
    largest weighted |g| or |h| over the levels of the published gradient
    discretizer, ``bins / 2 - 1`` and ``bins - 1``), unbiased, with a
    variance of at most s^2 / 4, so the sum strays by at most 0.5 s
    sqrt(C) in standard deviation.  ``step`` is one tree's per-leaf
    arrays as ``correct.train_numbers`` hands them to ``detail``;
    ``scales`` that tree's (largest |w g|, largest |w h|).  -> (z of G,
    z of H)."""
    live = step["count_ref"] > 0
    root = 0.5 * np.sqrt(step["count_ref"][live])
    levels = (max(num_bins // 2 - 1, 1), max(num_bins - 1, 1))
    out = []
    for side, top, level in zip("GH", scales, levels):
        gap = np.abs(step[side + "_prog"] - step[side + "_ref"])[live]
        out.append(float(np.max(gap / (root * top / level)))
                   if len(gap) else 0.0)
    return tuple(out)


class GossReference:
    """``follow`` as ``reference_gbdt.follow``, where the tree at position
    ``t`` in ``weights`` was grown on those row weights, and the split
    check runs on the trees at the positions in ``check_at``.  ``scales``
    gets each followed tree's largest |w g| and |w h|."""

    def __init__(self, weights=None, check_at=(0,)):
        self.weights = weights or {}
        self.check_at = set(check_at)
        self.scales = []

    def follow(self, X, y, trees, learning_rate, lambda_l2=0.0, blocks=12,
               starts=None, check_nodes=(), min_hess=0.0, min_rows=0,
               check_rows=1 << 20, *, objective, aux=None):
        """``reference_gbdt.follow``'s contract and results."""
        y = np.asarray(y, np.float64)
        n = len(y)
        starts = starts or {}
        stride = max(1, n // check_rows)
        bias = objective.init_score(y, aux)
        score = np.full(n, bias, np.float64)
        cuts = np.linspace(0, n, blocks + 1).astype(np.int64)
        spans = [(int(a), int(b)) for a, b in zip(cuts[:-1], cuts[1:])
                 if b > a]
        edges = None
        with ThreadPoolExecutor(max_workers=len(spans)) as pool:
            Xts = list(pool.map(
                lambda span: np.ascontiguousarray(X[span[0]:span[1]].T),
                spans))
            for t, tree in enumerate(trees):
                if t in starts:
                    score[:] = starts[t]
                g, h = objective.gradients(score, y, aux)
                w = self.weights.get(t)
                if w is not None:
                    g, h = g * w, h * w
                self.scales.append((np.max(np.abs(g)), np.max(np.abs(h))))
                nl = len(tree["leaf_value"])
                want = [k for k in check_nodes
                        if t in self.check_at
                        and k < len(tree["split_feature"])]

                def sums(args, w=w, g=g, h=h, want=want):
                    (lo, hi), Xt = args
                    keep = dict.fromkeys(want) if want else None
                    leaves = route(Xt, tree, keep)
                    grown = leaves
                    if w is not None:
                        # the leaf's count: its rows of nonzero weight
                        wp = w[lo:hi]
                        grown = {leaf: rows[wp[rows] != 0]
                                 for leaf, rows in leaves.items()}
                        if keep:
                            keep = {k: v[wp[v] != 0] for k, v in keep.items()}
                    return (leaves, leaf_sums(grown, nl, g[lo:hi], h[lo:hi]),
                            keep)

                parts = list(pool.map(sums, zip(spans, Xts)))
                G = sum(p[1][0] for p in parts)
                H = sum(p[1][1] for p in parts)
                C = sum(p[1][2] for p in parts)
                value = -G / (H + lambda_l2) * learning_rate

                splits = {}
                if want:
                    edges = bin_edges(X) if edges is None else edges
                    splits = best_splits(list(pool.map(
                        lambda a, g=g, h=h: _slice_hists(
                            a[1], g[a[0][0]:a[0][1]], h[a[0][0]:a[0][1]],
                            a[2][2], tree, edges, stride),
                        zip(spans, Xts, parts))), tree, lambda_l2, min_hess,
                        min_rows, stride)

                def update(args, value=value):
                    (lo, hi), (leaves, _, _) = args
                    part = score[lo:hi]
                    for leaf, rows in leaves.items():
                        part[rows] += value[leaf]

                list(pool.map(update, zip(spans, parts)))
                yield {
                    "G": G, "H": H, "count": C, "value": value,
                    "bias": bias if t == 0 and 0 not in starts else 0.0,
                    "gain": split_gains(tree, G, H, C, lambda_l2),
                    "score": score, "loss": objective.loss(score, y, aux),
                    "splits": splits,
                }
