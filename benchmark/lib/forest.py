"""A seeded random forest as LightGBM model text, its plain reference
scorer, and a parser of the same text (the benchmark's own, used by tests).

The scoring cell needs a 500-tree forest of the published shape without
paying 500 rounds of training in every run's set-up.  Trees are grown
leaf-wise at random: the next leaf to split is drawn uniformly, its feature
uniformly, its threshold a float64 in the feature's range (not an f32
value, as a real model's bin bounds are not), leaf values normal.  The
text is what ``Booster.save_model`` writes, so ``Booster(model_str=...)``
loads it through the program's normal path.
"""
import numpy as np


def random_tree(rng, num_leaves, features, leaf_scale):
    ns = num_leaves - 1
    split_feature = np.zeros(ns, np.int32)
    threshold = np.zeros(ns, np.float64)
    left = np.zeros(ns, np.int32)
    right = np.zeros(ns, np.int32)
    # leaf -> (parent node, side); splitting leaf L at step k makes node k,
    # keeps L on the left and opens leaf k+1 on the right (LightGBM's
    # numbering)
    where = {0: None}
    lo = {0: np.zeros(features)}
    hi = {0: np.ones(features)}
    for k in range(ns):
        leaf = int(rng.randint(0, k + 1))
        f = int(rng.randint(0, features))
        a, b = lo[leaf][f], hi[leaf][f]
        thr = float(a + (b - a) * (0.2 + 0.6 * rng.rand()))
        split_feature[k], threshold[k] = f, thr
        left[k], right[k] = ~leaf, ~(k + 1)
        parent = where[leaf]
        if parent is not None:
            node, side = parent
            (left if side == 0 else right)[node] = k
        where[leaf] = (k, 0)
        where[k + 1] = (k, 1)
        lo[k + 1], hi[k + 1] = lo[leaf].copy(), hi[leaf].copy()
        hi[leaf] = hi[leaf].copy()
        hi[leaf][f] = thr
        lo[k + 1][f] = thr
    return {
        "split_feature": split_feature, "threshold": threshold,
        "left_child": left, "right_child": right,
        "leaf_value": rng.randn(num_leaves) * leaf_scale,
    }


def random_forest(seed, trees, num_leaves, features, leaf_scale=0.02):
    rng = np.random.RandomState((seed * 31 + 7) % (1 << 32))
    return [random_tree(rng, num_leaves, features, leaf_scale)
            for _ in range(trees)]


def _fmt(values, kind="g"):
    if kind == "r":
        return " ".join(repr(float(v)) for v in values)
    return " ".join(str(int(v)) for v in values)


def to_model_text(forest, features, objective="binary sigmoid:1"):
    blocks = []
    for i, t in enumerate(forest):
        nl = len(t["leaf_value"])
        ns = nl - 1
        blocks.append("\n".join([
            f"Tree={i}",
            f"num_leaves={nl}",
            "num_cat=0",
            "split_feature=" + _fmt(t["split_feature"]),
            "split_gain=" + _fmt(np.ones(ns)),
            "threshold=" + _fmt(t["threshold"], "r"),
            "decision_type=" + _fmt(np.full(ns, 2)),
            "left_child=" + _fmt(t["left_child"]),
            "right_child=" + _fmt(t["right_child"]),
            "leaf_value=" + _fmt(t["leaf_value"], "r"),
            "leaf_weight=" + _fmt(np.ones(nl)),
            "leaf_count=" + _fmt(np.ones(nl)),
            "internal_value=" + _fmt(np.zeros(ns)),
            "internal_weight=" + _fmt(np.ones(ns)),
            "internal_count=" + _fmt(np.ones(ns)),
            "shrinkage=1",
            "", ""]))
    header = "\n".join([
        "tree", "version=v3", "num_class=1", "num_tree_per_iteration=1",
        "label_index=0", f"max_feature_idx={features - 1}",
        f"objective={objective}",
        "feature_names=" + " ".join(f"Column_{i}" for i in range(features)),
        "feature_infos=" + " ".join("[0:1]" for _ in range(features)),
        "tree_sizes=" + " ".join(str(len(b)) for b in blocks),
        "", ""])
    return (header + "".join(blocks) + "end of trees\n\n"
            "parameters:\nend of parameters\n\npandas_categorical:null\n")


_INT = ("split_feature", "left_child", "right_child", "decision_type",
        "leaf_count", "internal_count")
_FLOAT = ("threshold", "leaf_value", "leaf_weight", "split_gain",
          "internal_value", "internal_weight")


def parse_model_text(text):
    """Trees of a LightGBM model text as dicts of arrays."""
    forest = []
    for block in text.split("Tree=")[1:]:
        tree = {}
        for line in block.splitlines():
            key, _, val = line.partition("=")
            if key in _INT:
                tree[key] = np.array(val.split(), np.float64).astype(np.int64)
            elif key in _FLOAT:
                tree[key] = np.array(val.split(), np.float64)
        tree.setdefault("split_feature", np.zeros(0, np.int64))
        forest.append(tree)
    return forest


def score_rows(forest, X, dtype=np.float64):
    """Raw score of each row of ``X`` [rows, F]: the plain traversal, one
    tree at a time, all rows abreast.  ``dtype`` is the precision the
    comparison ``value <= threshold`` is made in (float64 is the stated
    one; the control narrows it)."""
    X = np.asarray(X)
    Xc = X.astype(dtype)
    rows = np.arange(len(X))
    out = np.zeros(len(X), np.float64)
    for t in forest:
        thr = t["threshold"].astype(dtype)
        node = np.zeros(len(X), np.int64)
        live = np.ones(len(X), bool) if len(thr) else np.zeros(len(X), bool)
        while live.any():
            cur = node[live]
            go_left = Xc[rows[live], t["split_feature"][cur]] <= thr[cur]
            node[live] = np.where(go_left, t["left_child"][cur],
                                  t["right_child"][cur])
            live = node >= 0
        out += t["leaf_value"][~node] if len(thr) else t["leaf_value"][0]
    return out
