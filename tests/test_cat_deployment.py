"""Native categorical features as the ``criteo-cat`` deployment needs them:
the system against the benchmark's plain categorical reference
(``benchmark/lib/reference_gbdt_cat.py``, float64 NumPy, imports nothing of
the program), that reference against a literal transcription of the
published many-vs-many loop and against ``tree.py``'s own decisions after a
model-text round trip, and category binning on the device against the host
mapper at the click log's cardinalities."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import lightgbm_tpu as lgb  # noqa: E402
from benchmark.lib import reference_gbdt_cat as ref_cat  # noqa: E402
from benchmark.lib.traffic import TREE_FIELDS  # noqa: E402
from benchmark.objectives import binary  # noqa: E402
from lightgbm_tpu.ops import ingest as ING  # noqa: E402
from test_cat_router import cat_data  # noqa: E402

CAT_L2, LR = 10.0, 0.1
PARAMS = {"objective": "binary", "num_leaves": 24, "max_bin": 63,
          "learning_rate": LR, "min_data_in_leaf": 5,
          "min_sum_hessian_in_leaf": 1.0, "min_data_per_group": 20,
          "cat_smooth": 5.0, "cat_l2": CAT_L2, "max_cat_threshold": 32,
          "max_cat_to_onehot": 4, "verbosity": -1}


def host_tree(model):
    return {k: np.array(getattr(model, k)) for k in
            TREE_FIELDS + ("decision_type", "cat_boundaries",
                           "cat_threshold")}


@pytest.fixture(scope="module")
def followed():
    """Three f32 rounds through ``Booster.update()`` and the reference's
    reading of their trees."""
    X, y, cats = cat_data(seed=5, n=8000)
    bst = lgb.Booster(PARAMS, lgb.Dataset(X, label=y, params=PARAMS,
                                          categorical_feature=cats))
    for _ in range(3):
        bst.update()
    trees = [host_tree(m) for m in bst.models[:3]]
    reference = ref_cat.CatReference(PARAMS, cats)
    steps = []
    for r in reference.follow(X, y, trees, LR, blocks=3,
                              check_nodes=(0, 1, 2), min_hess=1.0,
                              min_rows=5, objective=binary,
                              aux={"categorical_feature": cats}):
        steps.append(dict(r, score=r["score"].copy()))
    one_hot = {f for f, t in enumerate(reference.tables(X))
               if t[0] == "cat" and t[3]}
    return X, y, bst, trees, steps, one_hot


def test_every_row_reaches_the_references_leaf(followed):
    X, _, bst, trees, steps, _ = followed
    leaves = bst.predict(X, pred_leaf=True)
    for t, (tree, r) in enumerate(zip(trees, steps)):
        assert np.array_equal(tree["leaf_count"].astype(np.int64), r["count"])
        routed = ref_cat.route(np.ascontiguousarray(X.T), tree)
        mine = np.empty(len(X), np.int64)
        for leaf, rows in routed.items():
            mine[rows] = leaf
        assert np.array_equal(leaves[:, t], mine)


def test_leaf_sums_and_values_are_the_references(followed):
    _, _, _, trees, steps, _ = followed
    for tree, r in zip(trees, steps):
        # f32 histograms, a sibling by subtraction, against float64 sums
        np.testing.assert_allclose(tree["leaf_weight"], r["H"], rtol=1e-3)
        np.testing.assert_allclose(tree["leaf_value"] - r["bias"],
                                   r["value"], rtol=5e-3, atol=1e-6)


def test_gains_carry_cat_l2(followed):
    """A numeric split's gain under ``lambda_l2``, a many-vs-many split's
    under ``lambda_l2 + cat_l2``, as published.  The program's one-hot arm
    adds ``cat_l2`` too where the published one does not: its gains are
    held to that, so the departure stays as small as it is known to be."""
    _, _, _, trees, steps, one_hot = followed
    kinds = set()
    for tree, r in zip(trees, steps):
        onehot_node = np.array([
            ref_cat.is_categorical(tree, k) and int(f) in one_hot
            for k, f in enumerate(tree["split_feature"])])
        cat_node = (tree["decision_type"] & 1).astype(bool)
        kinds |= {("onehot" if o else "many" if c else "numeric")
                  for o, c in zip(onehot_node, cat_node)}
        np.testing.assert_allclose(tree["split_gain"][~onehot_node],
                                   r["gain"][~onehot_node], rtol=1e-2)
        G, H, C = r["G"], r["H"], r["count"]
        node = ref_cat.node_sums(tree, G, H, C)
        for k in np.flatnonzero(onehot_node):
            gl, hl, _ = ref_cat._child_sums(int(tree["left_child"][k]),
                                            (G, H, C), node)
            gr, hr, _ = ref_cat._child_sums(int(tree["right_child"][k]),
                                            (G, H, C), node)
            with_cat_l2 = (gl * gl / (hl + CAT_L2) + gr * gr / (hr + CAT_L2)
                           - (gl + gr) ** 2 / (hl + hr + CAT_L2))
            assert tree["split_gain"][k] == pytest.approx(with_cat_l2,
                                                          rel=1e-2)
            assert tree["split_gain"][k] < r["gain"][k]
    assert kinds == {"onehot", "many", "numeric"}


def test_the_scan_chose_among_the_best(followed):
    """The first three splits of the first tree: the chosen split's gain on
    the reference's own sums against the best the reference lists.  The
    reference scans 255 quantile edges where the program has 63 bins, and
    its many-vs-many candidates are the published ones (the module's
    docstring lists where the program's differ)."""
    _, _, _, _, steps, _ = followed
    splits = steps[0]["splits"]
    assert sorted(splits) == [0, 1, 2]
    for got, best, runner in splits.values():
        assert (best - got) / best < 0.05
        assert runner < best


def test_score_follows(followed):
    _, y, bst, _, steps, _ = followed
    score = np.asarray(bst.boosting.train_score)[0, :len(y)]
    np.testing.assert_allclose(score, steps[-1]["score"], atol=2e-4)


# ---- the reference against the published loop -----------------------------

def literal_many_vs_many(G, H, C, totals, p):
    """``FindBestThresholdCategoricalInner``'s many-vs-many arm, bin by
    bin as published."""
    GP, HP, CP = totals
    l2 = p["lambda_l2"] + p["cat_l2"]
    used = [i for i in range(len(C)) if C[i] >= p["cat_smooth"]]
    used.sort(key=lambda i: G[i] / (H[i] + p["cat_smooth"]))
    max_num_cat = min(p["max_cat_threshold"], (len(used) + 1) // 2)
    best = -np.inf
    for direction, start in ((1, 0), (-1, len(used) - 1)):
        pos = start
        cnt_cur_group = 0
        gl = hl = cl = 0.0
        for i in range(len(used)):
            if i >= max_num_cat:
                break
            t = used[pos]
            pos += direction
            gl += G[t]
            hl += H[t]
            cl += C[t]
            cnt_cur_group += C[t]
            if cl < p["min_data_in_leaf"] or hl < p["min_hess"]:
                continue
            right = CP - cl
            if right < p["min_data_in_leaf"] \
                    or right < p["min_data_per_group"]:
                break
            if HP - hl < p["min_hess"]:
                break
            if cnt_cur_group < p["min_data_per_group"]:
                continue
            cnt_cur_group = 0
            gain = (gl * gl / (hl + l2) + (GP - gl) ** 2 / (HP - hl + l2)
                    - GP * GP / (HP + l2))
            best = max(best, gain)
    return best


@pytest.mark.parametrize("case", range(8))
def test_many_vs_many_is_the_published_loop(case):
    rng = np.random.default_rng(100 + case)
    codes = int(rng.integers(3, 64))
    C = np.floor(rng.pareto(1.0, codes) * 30).astype(np.float64)
    H = C * rng.uniform(0.1, 0.25, codes)
    G = rng.normal(size=codes) * np.sqrt(C + 1) + 0.05 * C
    other = float(rng.integers(0, 400))
    totals = (G.sum() + 0.1 * other, H.sum() + 0.2 * other, C.sum() + other)
    p = {"lambda_l2": float(case % 2), "cat_l2": 10.0,
         "cat_smooth": float(rng.choice([1.0, 10.0, 40.0])),
         "min_data_per_group": int(rng.choice([10, 100, 400])),
         "min_data_in_leaf": int(rng.choice([1, 20, 200])),
         "min_hess": float(rng.choice([1e-3, 5.0, 40.0])),
         "max_cat_threshold": int(rng.choice([4, 32]))}
    got = ref_cat.many_vs_many(G, H, C, totals, p)
    want = literal_many_vs_many(G, H, C, totals, p)
    assert got == pytest.approx(want, rel=1e-12) or got == want == -np.inf


def test_sets_read_as_tree_py_reads_them_after_a_round_trip(followed):
    """A model saved as text and loaded again: at every node the reference's
    decision on raw values equals ``HostTree._decide``'s, for kept, rare,
    never-seen, negative, fractional and missing codes alike."""
    X, _, bst, _, _, _ = followed
    loaded = lgb.Booster(model_str=bst.model_to_string())
    rng = np.random.default_rng(9)
    values = np.concatenate([
        X[:400].ravel(), np.arange(-3, 80, dtype=np.float32),
        rng.integers(0, 6000, 300).astype(np.float32),
        np.float32([np.nan, 1e9, 2.0 ** 31, -1e30, 2.5, 16777215.0])])
    categorical = 0
    for model in loaded.models:
        tree = host_tree(model)
        for node in range(len(tree["split_feature"])):
            want = np.asarray(model._decide(values.astype(np.float64), node),
                              bool)
            got = ref_cat.goes_left(values, tree, node)
            assert np.array_equal(got, want), (node, tree["decision_type"])
            categorical += int(ref_cat.is_categorical(tree, node))
    assert categorical >= 3


# ---- category binning at the click log's cardinalities --------------------

def id_columns(rows=30000, seed=2):
    """Zipf-like codes over 10,131,227, 8,351,593 and 24 categories and a
    numeric column; NaN, a negative code and the largest codes among them."""
    rng = np.random.default_rng(seed)
    cards = (10131227, 8351593, 24)
    cols = [np.floor((c + 1) ** rng.random(rows)) - 1 for c in cards]
    X = np.column_stack(cols + [rng.random(rows) * 50]).astype(np.float32)
    X[:3, 0] = [10131226.0, 10131225.0, -7.0]
    X[3:5, 1] = [8351592.0, 16777215.0]
    X[rng.random(rows) < 0.03, 0] = np.nan
    y = (rng.random(rows) < 0.25).astype(np.float32)
    return X, y


def test_category_binning_on_the_device_is_the_host_mappers(monkeypatch):
    X, y = id_columns()

    def build():
        ds = lgb.Dataset(X.copy(), label=y, categorical_feature=[0, 1, 2],
                         params={"verbosity": -1, "max_bin": 63})
        return ds.construct()
    host = build()
    monkeypatch.setenv("LGBM_TPU_INGEST_KERNEL", "kernel")
    dev = build()
    assert ING.ingest_last().get("path") == "kernel"
    assert dev.binned.dtype == host.binned.dtype == np.uint8
    assert np.array_equal(dev.binned, host.binned)
    # max_bin binds the id columns; the 24-code column keeps every code
    bins = [host.bin_mappers[f].num_bin for f in range(3)]
    assert bins[0] == bins[1] == 63 and bins[2] == 24
    # block by block against the mapper itself, salted rows included
    tables = ING.build_ingest_tables(host)
    assert tables.cats.shape == (3, 63)
    binner = ING.DeviceBinner(tables, tile_rows=256)
    probe = np.concatenate([X[:600], ING.salt_rows(X.shape[1], X)])
    got = np.asarray(binner(probe))
    for f in range(3):
        want = host.bin_mappers[f].value_to_bin(probe[:, f].astype(np.float64))
        col = got[:, host.feat_group[f]].astype(np.int64)
        assert np.array_equal(col - host.feat_start[f] + 1, want) \
            or np.array_equal(col, want)


def test_edges_record_carries_the_categorical_seconds():
    from lightgbm_tpu.obs.flight import global_flight
    X, y = id_columns(rows=5000)
    lgb.Dataset(X, label=y, categorical_feature=[0, 1, 2],
                params={"verbosity": -1, "max_bin": 63}).construct()
    rec = [e for e in global_flight.ring_events()
           if e.get("name") == "ingest.edges"][-1]
    assert rec["args"]["categorical"] == 3
    assert 0 < rec["args"]["cat_s"] <= rec["dur"] / 1e6


def test_trees_count_their_categorical_splits(followed):
    """``tree_to_host`` counts what the forest stands on: the counters move
    by the splits, the categorical splits and the codes in their sets of
    every tree the host takes."""
    from lightgbm_tpu.obs.metrics import global_registry
    X, y, cats = cat_data(seed=6, n=3000)

    def counters():
        c = global_registry.to_dict().get("counters", {})
        return np.array([c.get(k, 0) for k in (
            "tree_splits_total", "tree_splits_categorical_total",
            "tree_cat_set_codes_total")])
    before = counters()
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y, categorical_feature=cats),
                    num_boost_round=2)
    moved = counters() - before
    splits = sum(len(m.split_feature) for m in bst.models)
    cat = sum(int(np.sum(m.decision_type & 1)) for m in bst.models)
    codes = sum(int(bin(int(w)).count("1")) for m in bst.models
                for w in m.cat_threshold)
    assert list(moved) == [splits, cat, codes] and 0 < cat < splits
