"""The plain reference for the training cells: GBDT in float64 NumPy,
written from LightGBM's published equations, importing nothing of the
program and taking no table of it.  The objective is a module of its own
(``objectives/<objective>.py``: ``init_score(y, aux)``, ``gradients(score,
y, aux)``, ``loss(score, y, aux)``), found by the name in the
configuration's parameters and handed in; ``aux`` is the dict of dataset
fields the generator made beside the label.

It does not grow trees of its own: a leaf-wise grower breaks near-ties on
the last bits of a histogram sum, so two correct growers disagree from the
first tie on.  Instead it *follows* the trees the timed path produced: for
each followed tree it routes every raw row by the tree's real-valued
thresholds, computes its own gradients from its own score, sums them per
leaf, and derives what the tree's numbers have to be if binning, gradients,
histograms, gain, routing, leaf values and the score update were right:

- rows per leaf (exact),
- hessian and gradient sum per leaf, hence each leaf's value,
- the gain of every split from the two children's sums,
- the score of every row after the tree, and the loss,
- for the first splits of the first followed tree, whether the split the
  program chose is the best there is (``best_splits``): the best gain over
  every feature and every threshold of the reference's own bin edges, from
  its own gradients, beside the gain of the chosen split on the same rows.

A tree is a dict of arrays named as in LightGBM's model text
(``split_feature``, ``threshold``, ``left_child``, ``right_child``,
``split_gain``, ``leaf_value``, ``leaf_weight``, ``leaf_count``).
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def route(Xt, tree, keep_nodes=None):
    """Rows of each leaf: ``{leaf: sorted row indices}``.  ``Xt`` is the raw
    matrix feature-major [F, n] float32; the decision is LightGBM's
    ``value <= threshold`` goes left, on the value widened to float64.
    ``keep_nodes``, a dict, is given the rows of the internal nodes it has
    as keys."""
    n = Xt.shape[1]
    leaves = {}
    if len(tree["split_feature"]) == 0:
        return {0: np.arange(n, dtype=np.int64)}
    stack = [(0, np.arange(n, dtype=np.int64))]
    while stack:
        node, idx = stack.pop()
        if keep_nodes is not None and node in keep_nodes:
            keep_nodes[node] = idx
        f = int(tree["split_feature"][node])
        left = Xt[f][idx].astype(np.float64) <= float(tree["threshold"][node])
        for child, rows in ((int(tree["left_child"][node]), idx[left]),
                            (int(tree["right_child"][node]), idx[~left])):
            if child < 0:
                leaves[~child] = rows
            else:
                stack.append((child, rows))
    return leaves


def raw_scores(X, trees, blocks=12):
    """Raw score of every row of ``X`` [n, F] float32 under ``trees``: each
    row routed through every tree by its real-valued thresholds (``route``),
    the leaves' values added up in float64.  The values are the host
    model's, so the first tree's carry the boost-from-average bias."""
    n = len(X)
    score = np.zeros(n, np.float64)
    cuts = np.linspace(0, n, blocks + 1).astype(np.int64)
    spans = [(int(a), int(b)) for a, b in zip(cuts[:-1], cuts[1:]) if b > a]

    def part(span):
        lo, hi = span
        Xt = np.ascontiguousarray(X[lo:hi].T)
        out = score[lo:hi]
        for tree in trees:
            for leaf, rows in route(Xt, tree).items():
                out[rows] += tree["leaf_value"][leaf]

    with ThreadPoolExecutor(max_workers=max(len(spans), 1)) as pool:
        list(pool.map(part, spans))
    return score


def auc(score, y):
    """Area under the ROC curve from the rank sum, tied scores sharing
    their mean rank (what LightGBM's ``auc`` computes, unweighted)."""
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="stable")
    s = score[order]
    pos = np.asarray(y)[order] > 0
    first = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    last = np.r_[first[1:], len(s)]
    mean_rank = 0.5 * (first + last + 1)            # 1-based
    ranks = np.repeat(mean_rank, last - first)
    n_pos = float(pos.sum())
    n_neg = float(len(s) - n_pos)
    if n_pos == 0 or n_neg == 0:
        return 1.0
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def leaf_sums(leaves, num_leaves, g, h):
    G = np.zeros(num_leaves)
    H = np.zeros(num_leaves)
    C = np.zeros(num_leaves, np.int64)
    for leaf, rows in leaves.items():
        G[leaf] = g[rows].sum()
        H[leaf] = h[rows].sum()
        C[leaf] = len(rows)
    return G, H, C


def _child_sums(child, leaf, node):
    """(G, H, C) of a child: a leaf (``~child``) or an internal node."""
    src, i = (leaf, ~child) if child < 0 else (node, child)
    return src[0][i], src[1][i], src[2][i]


def node_sums(tree, G, H, C):
    """Sums of every internal node from its children's (post-order)."""
    ns = len(tree["split_feature"])
    node = (np.zeros(ns), np.zeros(ns), np.zeros(ns, np.int64))
    done = np.zeros(ns, bool)
    # children of node k are leaves or nodes numbered above k (leaf-wise
    # growth numbers splits in the order they were made)
    for k in range(ns - 1, -1, -1):
        l, r = int(tree["left_child"][k]), int(tree["right_child"][k])
        assert (l < 0 or done[l]) and (r < 0 or done[r]), "node order"
        for a, b_l, b_r in zip(node, _child_sums(l, (G, H, C), node),
                               _child_sums(r, (G, H, C), node)):
            a[k] = b_l + b_r
        done[k] = True
    return node


def split_gains(tree, G, H, C, lam):
    """gain = GL^2/(HL+lam) + GR^2/(HR+lam) - GP^2/(HP+lam) per split."""
    node = node_sums(tree, G, H, C)
    ns = len(tree["split_feature"])
    gains = np.zeros(ns)
    for k in range(ns):
        gl, hl, _ = _child_sums(int(tree["left_child"][k]), (G, H, C), node)
        gr, hr, _ = _child_sums(int(tree["right_child"][k]), (G, H, C), node)
        gains[k] = (gl * gl / (hl + lam) + gr * gr / (hr + lam)
                    - node[0][k] ** 2 / (node[1][k] + lam))
    return gains


def bin_edges(X, bins=255, sample=200_000):
    """The reference's own candidate thresholds: per feature the distinct
    ``bins``-quantiles of the first ``sample`` rows (the rows are i.i.d.)."""
    head = X[:sample].astype(np.float64)
    qs = np.linspace(0.0, 1.0, bins + 1)[1:-1]
    return [np.unique(np.quantile(head[:, f], qs))
            for f in range(X.shape[1])]


def _gain_curve(G, H, C, lam, min_hess, min_rows):
    """Best gain over the thresholds of one feature from its histogram
    (cumulative sums; a side lighter than ``min_hess`` or with fewer rows
    than ``min_rows`` is no candidate)."""
    GL, HL, CL = np.cumsum(G)[:-1], np.cumsum(H)[:-1], np.cumsum(C)[:-1]
    GP, HP = G.sum(), H.sum()
    GR, HR = GP - GL, HP - HL
    ok = ((HL >= min_hess) & (HR >= min_hess)
          & (CL >= min_rows) & (C.sum() - CL >= min_rows))
    if not ok.any():
        return -np.inf
    GL, HL, GR, HR = GL[ok], HL[ok], GR[ok], HR[ok]
    return float(np.max(GL * GL / (HL + lam) + GR * GR / (HR + lam)
                        - GP * GP / (HP + lam)))


def _slice_hists(Xt, g, h, keep, tree, edges, stride):
    """One row slice's part of ``best_splits``: per kept node, the (G, H,
    rows) histogram of every feature over the reference's edges and the sums of
    the two sides of the chosen split, on every ``stride``-th row."""
    out = {}
    for node, idx in keep.items():
        idx = idx[::stride]
        gs, hs = g[idx], h[idx]
        hist = []
        for f, e in enumerate(edges):
            code = np.searchsorted(e, Xt[f][idx], side="left")
            hist.append(np.stack([np.bincount(code, gs, len(e) + 1),
                                  np.bincount(code, hs, len(e) + 1),
                                  np.bincount(code, None, len(e) + 1)]))
        left = (Xt[int(tree["split_feature"][node])][idx].astype(np.float64)
                <= float(tree["threshold"][node]))
        sides = np.array([[gs[left].sum(), hs[left].sum()],
                          [gs[~left].sum(), hs[~left].sum()]])
        out[node] = (hist, sides)
    return out


def best_splits(slices, tree, lam, min_hess, min_rows, stride):
    """-> {node: (chosen gain, best gain, best gain off the chosen
    feature)} from the slices' histograms added up in slice order."""
    out = {}
    for node in slices[0]:
        hist = [sum(s[node][0][f] for s in slices)
                for f in range(len(slices[0][node][0]))]
        (gl, hl), (gr, hr) = sum(s[node][1] for s in slices)
        got = (gl * gl / (hl + lam) + gr * gr / (hr + lam)
               - (gl + gr) ** 2 / (hl + hr + lam))
        per_feature = np.array([_gain_curve(*hf, lam, min_hess / stride,
                                            min_rows / stride)
                                for hf in hist])
        f0 = int(tree["split_feature"][node])
        out[node] = (float(got), float(per_feature.max()),
                     float(np.delete(per_feature, f0).max()))
    return out


def follow(X, y, trees, learning_rate, lambda_l2=0.0, blocks=12,
           starts=None, check_nodes=(), min_hess=0.0, min_rows=0,
           check_rows=1 << 20, *, objective, aux=None):
    """Follow ``trees`` in order.  Yields, per tree, a dict with the
    reference's per-leaf ``G``, ``H``, ``count``, ``value`` (the leaf's
    output without the first tree's bias), ``gain`` per split, and after
    the update ``score`` (a view: copy it to keep it) and ``loss``.
    ``X`` is the raw matrix [n, F] float32.  Rows are routed in ``blocks``
    slices on as many threads (NumPy drops the lock), the per-leaf sums
    added up in slice order.  ``starts`` maps a position in ``trees`` to the
    score that tree was grown on (the program's own, for a tree from the
    middle of a run); the others follow on from the tree before them, the
    first from the label mean.  ``check_nodes`` are the
    internal nodes of the first tree whose split choice is checked
    (``best_splits``, under ``splits`` of its dict) on every k-th row, k
    such that about ``check_rows`` rows are read."""
    y = np.asarray(y, np.float64)
    n = len(y)
    starts = starts or {}
    stride = max(1, n // check_rows)
    bias = objective.init_score(y, aux)
    score = np.full(n, bias, np.float64)
    cuts = np.linspace(0, n, blocks + 1).astype(np.int64)
    spans = [(int(a), int(b)) for a, b in zip(cuts[:-1], cuts[1:]) if b > a]
    with ThreadPoolExecutor(max_workers=len(spans)) as pool:
        # feature-major copies of the slices, so a node reads one column
        Xts = list(pool.map(
            lambda span: np.ascontiguousarray(X[span[0]:span[1]].T), spans))
        for t, tree in enumerate(trees):
            if t in starts:
                score[:] = starts[t]
            g, h = objective.gradients(score, y, aux)
            nl = len(tree["leaf_value"])
            want = [k for k in check_nodes
                    if t == 0 and k < len(tree["split_feature"])]

            def sums(args):
                (lo, hi), Xt = args
                keep = dict.fromkeys(want) if want else None
                leaves = route(Xt, tree, keep)
                return (leaves, leaf_sums(leaves, nl, g[lo:hi], h[lo:hi]),
                        keep)

            parts = list(pool.map(sums, zip(spans, Xts)))
            G = sum(p[1][0] for p in parts)
            H = sum(p[1][1] for p in parts)
            C = sum(p[1][2] for p in parts)
            value = -G / (H + lambda_l2) * learning_rate

            splits = {}
            if want:
                edges = bin_edges(X)
                splits = best_splits(list(pool.map(
                    lambda a: _slice_hists(a[1], g[a[0][0]:a[0][1]],
                                           h[a[0][0]:a[0][1]], a[2][2],
                                           tree, edges, stride),
                    zip(spans, Xts, parts))), tree, lambda_l2, min_hess,
                    min_rows, stride)

            def update(args):
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
