"""The plain reference for training cells whose data has categorical
columns: float64 NumPy from LightGBM's published rules, importing nothing
of the program.  It takes the numeric pieces of ``reference_gbdt`` as they
are (per-leaf sums, node sums, the gain curve of a numeric column) and
follows trees as that file does; what it adds is what a categorical column
changes (docs/Features.rst "Optimal Split for Categorical Features",
``FeatureHistogram::FindBestThresholdCategoricalInner``, ``Tree::Decision``):

- *routing*.  A tree is a dict of arrays named as in the model text; beside
  ``reference_gbdt``'s it holds ``decision_type``, ``cat_boundaries`` and
  ``cat_threshold``.  At a node whose ``decision_type`` has the categorical
  bit a row goes left iff its value ``v`` is finite, ``v >= 0`` and
  ``int(v)`` is in the node's set: ``threshold`` is the set's index ``i``,
  the set the words ``cat_threshold[cat_boundaries[i]:cat_boundaries[i +
  1]]`` read as a bitset over raw codes.  Everything else goes right.  At a
  numeric node ``v <= threshold`` goes left; a missing value (NaN under
  missing type NaN, bits 2-3 of ``decision_type``) goes by the node's
  default direction (bit 1), and NaN counts as 0 under the other types.
- *gain of a categorical split*: ``GL^2/(HL+l2') + GR^2/(HR+l2') -
  GP^2/(HP+l2')`` with ``l2' = lambda_l2 + cat_l2`` in many-vs-many mode and
  ``l2' = lambda_l2`` in one-hot mode; leaf values stay ``-G/(H+lambda_l2) x
  learning_rate``.
- *whether the scan chose well* (``best_splits``): per column the best gain
  there is from the reference's own sums over the node's rows; a numeric
  column by its gain curve over the reference's own quantile edges (missing
  values tried on either side), a categorical column from its per-code sums
  (g, h, rows), ``one_hot`` or ``many_vs_many``.  The chosen split's gain
  is taken on the same rows, whatever set the program chose, so a program
  that finds a *better* set than the reference lists reads a gap below zero.

Which codes of a column are candidates follows the published binning rule
(``BinMapper::FindBin``, categorical arm) on the first rows: codes by
descending count, at most ``max_bin`` of them, none rarer than
``min_data_in_bin`` after the first two; the rest, negative codes and
missing values are "no category" and always go right.  A column is in
one-hot mode when its bins (kept codes, and one more for missing values
where every code was kept) are at most ``max_cat_to_onehot``.

Where the program departs from the published algorithm (all of it carried
by ``split_choice_gap`` and ``gain_gap``, none hidden): its many-vs-many arm
drops codes with fewer rows than ``min_data_per_group // 4`` of the node
where the published loop drops those under ``cat_smooth``, takes a prefix
of up to ``max_cat_threshold`` codes whatever the number kept and lists a
candidate at every prefix where the published loop asks for
``min_data_per_group`` rows since the last one (``ops/split.py``); its
one-hot arm adds ``cat_l2`` to ``lambda_l2`` where the published one does
not; and a bin of the program past the kept codes collects the rare codes,
which may be a candidate there.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .reference_gbdt import _child_sums, _gain_curve, leaf_sums, node_sums

CATEGORICAL_BIT = 1
DEFAULT_LEFT_BIT = 2
MISSING_NAN = 2
BIN_SAMPLE = 200_000
CAT_DEFAULTS = {"max_cat_threshold": 32, "cat_l2": 10.0, "cat_smooth": 10.0,
                "max_cat_to_onehot": 4, "min_data_per_group": 100,
                "max_bin": 255, "min_data_in_bin": 3}


def in_set(words, v):
    """Whether each value of ``v`` (float, raw codes) is in the bitset
    ``words``: finite, non-negative, and its bit set."""
    ok = np.isfinite(v) & (v >= 0)
    code = np.where(ok, v, 0).astype(np.int64)
    ok &= code < 32 * len(words)
    if not ok.any():
        return ok
    word = np.asarray(words, np.uint32)[np.where(ok, code >> 5, 0)]
    return ok & (((word >> (code & 31).astype(np.uint32)) & 1) == 1)


def node_set(tree, node):
    i = int(tree["threshold"][node])
    lo, hi = tree["cat_boundaries"][i], tree["cat_boundaries"][i + 1]
    return np.asarray(tree["cat_threshold"][lo:hi], np.uint32)


def is_categorical(tree, node):
    return bool(int(tree["decision_type"][node]) & CATEGORICAL_BIT)


def goes_left(v, tree, node):
    """The decision of ``node`` for the raw values ``v`` of its column."""
    dt = int(tree["decision_type"][node])
    if dt & CATEGORICAL_BIT:
        return in_set(node_set(tree, node), v)
    v = v.astype(np.float64)
    nan = np.isnan(v)
    below = np.where(nan, 0.0, v) <= float(tree["threshold"][node])
    if (dt >> 2) & 3 == MISSING_NAN:
        return np.where(nan, bool(dt & DEFAULT_LEFT_BIT), below)
    return below


def route(Xt, tree, keep_nodes=None):
    """Rows of each leaf, ``{leaf: sorted row indices}``, as
    ``reference_gbdt.route`` gives them; ``Xt`` is feature-major."""
    n = Xt.shape[1]
    if len(tree["split_feature"]) == 0:
        return {0: np.arange(n, dtype=np.int64)}
    leaves = {}
    stack = [(0, np.arange(n, dtype=np.int64))]
    while stack:
        node, idx = stack.pop()
        if keep_nodes is not None and node in keep_nodes:
            keep_nodes[node] = idx
        left = goes_left(Xt[int(tree["split_feature"][node])][idx], tree, node)
        for child, rows in ((int(tree["left_child"][node]), idx[left]),
                            (int(tree["right_child"][node]), idx[~left])):
            if child < 0:
                leaves[~child] = rows
            else:
                stack.append((child, rows))
    return leaves


def kept_codes(column, max_bin, min_data_in_bin):
    """-> (codes the binning rule keeps, by descending count; whether the
    column takes one more bin for missing values)."""
    ok = np.isfinite(column) & (column >= 0)
    codes, counts = np.unique(column[ok].astype(np.int64), return_counts=True)
    order = np.argsort(-counts, kind="stable")
    codes, counts = codes[order], counts[order]
    keep = len(codes)
    rare = np.flatnonzero(counts[2:] < min_data_in_bin)
    if len(rare):
        keep = 2 + int(rare[0])
    all_kept = keep == len(codes) and keep <= max_bin
    return codes[:min(keep, max_bin)], bool(all_kept and not ok.all())


def many_vs_many(G, H, C, totals, p, scale=1.0):
    """Best gain over the published many-vs-many candidates of one column,
    from its kept codes' sums in a node (``totals`` = the node's G, H, rows);
    -inf where there is none.  ``scale`` is the share of the node's rows the
    sums were taken on: every limit in rows or hessian is taken by it."""
    GP, HP, CP = totals
    lam = p["lambda_l2"] + p["cat_l2"]
    smooth = p["cat_smooth"] * scale
    group = p["min_data_per_group"] * scale
    min_rows, min_hess = p["min_data_in_leaf"] * scale, p["min_hess"] * scale
    use = np.flatnonzero(C >= smooth)
    use = use[np.argsort(G[use] / (H[use] + smooth), kind="stable")]
    most = min(int(p["max_cat_threshold"]), (len(use) + 1) // 2)
    best = -np.inf
    for order in (use[:most], use[::-1][:most]):
        GL, HL, CL = np.cumsum(G[order]), np.cumsum(H[order]), \
            np.cumsum(C[order])
        ok = (CL >= min_rows) & (HL >= min_hess)
        # the loop ends at the first prefix, sound on the left, whose right
        # side is too small
        ends = ok & ((CP - CL < max(min_rows, group))
                     | (HP - HL < min_hess))
        stop = int(np.argmax(ends)) if ends.any() else len(order)
        since = 0.0
        for i in range(stop):
            since += C[order[i]]
            if not ok[i] or since < group:
                continue
            since = 0.0
            best = max(best, GL[i] ** 2 / (HL[i] + lam)
                       + (GP - GL[i]) ** 2 / (HP - HL[i] + lam)
                       - GP ** 2 / (HP + lam))
    return float(best)


def one_hot(G, H, C, totals, p, scale=1.0):
    """Best gain of one kept code against the rest."""
    GP, HP, CP = totals
    lam = p["lambda_l2"]
    min_rows, min_hess = p["min_data_in_leaf"] * scale, p["min_hess"] * scale
    ok = ((C >= min_rows) & (CP - C >= min_rows)
          & (H >= min_hess) & (HP - H >= min_hess))
    if not ok.any():
        return -np.inf
    G, H = G[ok], H[ok]
    return float(np.max(G * G / (H + lam) + (GP - G) ** 2 / (HP - H + lam)
                        - GP ** 2 / (HP + lam)))


class CatReference:
    """``follow`` as ``reference_gbdt.follow``, for a data set whose
    ``categorical`` columns hold codes; ``params`` are the configuration's
    (the published defaults where it states none)."""

    def __init__(self, params, categorical):
        self.categorical = sorted(int(c) for c in categorical)
        self.p = {k: type(v)(params.get(k, v))
                  for k, v in CAT_DEFAULTS.items()}

    # ---- the column tables of the split check ---------------------------
    def tables(self, X):
        """Per column, from the first rows: a numeric column's quantile
        edges, or a categorical column's kept codes and its mode."""
        head = X[:BIN_SAMPLE]
        qs = np.linspace(0.0, 1.0, 256)[1:-1]
        out = []
        for f in range(X.shape[1]):
            col = head[:, f].astype(np.float64)
            if f in self.categorical:
                codes, nan_bin = kept_codes(col, self.p["max_bin"],
                                            self.p["min_data_in_bin"])
                lut = np.full(int(codes.max()) + 2 if len(codes) else 1,
                              len(codes), np.int64)
                lut[codes] = np.arange(len(codes))
                onehot = len(codes) + nan_bin <= self.p["max_cat_to_onehot"]
                out.append(("cat", lut, len(codes), onehot))
            else:
                out.append(("num", np.unique(np.nanquantile(col, qs))))
        return out

    @staticmethod
    def _hist(table, v, gs, hs):
        """(G, H, rows) per bucket of one column: a numeric column's edges
        and one more bucket for NaN; a categorical column's kept codes and
        one more for everything else."""
        if table[0] == "cat":
            lut = table[1]
            ok = np.isfinite(v) & (v >= 0)
            code = np.where(ok, v, len(lut) - 1).astype(np.int64)
            b = lut[np.minimum(code, len(lut) - 1)]
            nb = table[2] + 1
        else:
            edges = table[1]
            nan = np.isnan(v)
            b = np.where(nan, len(edges) + 1,
                         np.searchsorted(edges, np.where(nan, 0.0, v),
                                         side="left"))
            nb = len(edges) + 2
        return np.stack([np.bincount(b, gs, nb), np.bincount(b, hs, nb),
                         np.bincount(b, None, nb)])

    def _slice_hists(self, Xt, g, h, keep, tree, tables, stride):
        out = {}
        for node, idx in keep.items():
            idx = idx[::stride]
            gs, hs = g[idx], h[idx]
            hist = [self._hist(t, Xt[f][idx], gs, hs)
                    for f, t in enumerate(tables)]
            left = goes_left(Xt[int(tree["split_feature"][node])][idx],
                             tree, node)
            sides = np.array([[gs[left].sum(), hs[left].sum()],
                              [gs[~left].sum(), hs[~left].sum()]])
            out[node] = (hist, sides)
        return out

    def _column_best(self, table, hist, totals, p, scale):
        if table[0] == "cat":
            G, H, C = hist[:, :table[2]]
            fn = one_hot if table[3] else many_vs_many
            return fn(G, H, C, totals, p, scale)
        # a numeric column: missing values last (to the right) or first
        body, nan = hist[:, :-1], hist[:, -1:]
        args = (p["lambda_l2"], p["min_hess"] * scale,
                p["min_data_in_leaf"] * scale)
        return max(_gain_curve(*np.concatenate([body, nan], 1), *args),
                   _gain_curve(*np.concatenate([nan, body], 1), *args))

    def best_splits(self, slices, tree, tables, p, stride):
        """-> {node: (chosen gain, best gain, best gain off the chosen
        column)} from the slices' histograms added up in slice order."""
        out = {}
        for node in slices[0]:
            hist = [sum(s[node][0][f] for s in slices)
                    for f in range(len(tables))]
            (gl, hl), (gr, hr) = sum(s[node][1] for s in slices)
            f0 = int(tree["split_feature"][node])
            lam = p["lambda_l2"]
            if is_categorical(tree, node) and not tables[f0][3]:
                lam += p["cat_l2"]
            got = (gl * gl / (hl + lam) + gr * gr / (hr + lam)
                   - (gl + gr) ** 2 / (hl + hr + lam))
            totals = (gl + gr, hl + hr, float(hist[0][2].sum()))
            per = np.array([self._column_best(t, hf, totals, p, 1.0 / stride)
                            for t, hf in zip(tables, hist)])
            out[node] = (float(got), float(per.max()),
                         float(np.delete(per, f0).max()))
        return out

    def split_gains(self, tree, G, H, C, lam, onehot):
        """The gain of every split from its children's sums, ``l2'`` by the
        node's kind and its column's mode."""
        node = node_sums(tree, G, H, C)
        gains = np.zeros(len(tree["split_feature"]))
        for k in range(len(gains)):
            l2 = lam
            if is_categorical(tree, k) \
                    and int(tree["split_feature"][k]) not in onehot:
                l2 += self.p["cat_l2"]
            gl, hl, _ = _child_sums(int(tree["left_child"][k]), (G, H, C),
                                    node)
            gr, hr, _ = _child_sums(int(tree["right_child"][k]), (G, H, C),
                                    node)
            gains[k] = (gl * gl / (hl + l2) + gr * gr / (hr + l2)
                        - node[0][k] ** 2 / (node[1][k] + l2))
        return gains

    def follow(self, X, y, trees, learning_rate, lambda_l2=0.0, blocks=12,
               starts=None, check_nodes=(), min_hess=0.0, min_rows=0,
               check_rows=1 << 21, *, objective, aux=None):
        """``reference_gbdt.follow``'s contract and results."""
        y = np.asarray(y, np.float64)
        n = len(y)
        starts = starts or {}
        stride = max(1, n // check_rows)
        p = dict(self.p, lambda_l2=lambda_l2, min_hess=min_hess,
                 min_data_in_leaf=min_rows)
        bias = objective.init_score(y, aux)
        score = np.full(n, bias, np.float64)
        cuts = np.linspace(0, n, blocks + 1).astype(np.int64)
        spans = [(int(a), int(b)) for a, b in zip(cuts[:-1], cuts[1:])
                 if b > a]
        tables = self.tables(X)
        onehot = {f for f, t in enumerate(tables) if t[0] == "cat" and t[3]}
        with ThreadPoolExecutor(max_workers=len(spans)) as pool:
            Xts = list(pool.map(
                lambda span: np.ascontiguousarray(X[span[0]:span[1]].T),
                spans))
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
                G = sum(q[1][0] for q in parts)
                H = sum(q[1][1] for q in parts)
                C = sum(q[1][2] for q in parts)
                value = -G / (H + lambda_l2) * learning_rate

                splits = {}
                if want:
                    splits = self.best_splits(list(pool.map(
                        lambda a: self._slice_hists(
                            a[1], g[a[0][0]:a[0][1]], h[a[0][0]:a[0][1]],
                            a[2][2], tree, tables, stride),
                        zip(spans, Xts, parts))), tree, tables, p, stride)

                def update(args):
                    (lo, hi), (leaves, _, _) = args
                    part = score[lo:hi]
                    for leaf, rows in leaves.items():
                        part[rows] += value[leaf]

                list(pool.map(update, zip(spans, parts)))
                yield {
                    "G": G, "H": H, "count": C, "value": value,
                    "bias": bias if t == 0 and 0 not in starts else 0.0,
                    "gain": self.split_gains(tree, G, H, C, lambda_l2,
                                             onehot),
                    "score": score, "loss": objective.loss(score, y, aux),
                    "splits": splits,
                }
