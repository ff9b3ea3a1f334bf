"""The path form of the binned traversal (``grower.route_leaf_index_binned``:
whole feature rows, node decisions, one matmul against the tree's root
paths) against the walk over tree levels, bit for bit: called directly on
hand-built and grown trees, and through ``lgb.train`` with a validation
set, where the trace-time predicate ``leaf_router_engages`` is what picks
the program and the registry's two counters say which one ran."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import grower
from lightgbm_tpu.binning import MissingType
from lightgbm_tpu.boosting import gbdt
from lightgbm_tpu.dataset import FeatureMeta
from lightgbm_tpu.grower import (GrowerConfig, TreeArrays, grow_tree,
                                 predict_leaf_index_binned,
                                 route_leaf_index_binned)
from lightgbm_tpu.obs.metrics import global_registry
from lightgbm_tpu.ops.split import SplitHyperparams

import example_data


def _meta(num_bin, missing=None, default_bin=None):
    F = len(num_bin)
    return FeatureMeta(
        num_bin=np.asarray(num_bin, np.int32),
        missing_type=np.asarray(missing if missing is not None
                                else np.zeros(F), np.int32),
        default_bin=np.asarray(default_bin if default_bin is not None
                               else np.zeros(F), np.int32),
        most_freq_bin=np.zeros(F, np.int32),
        is_categorical=np.zeros(F, bool),
        max_num_bin=int(max(num_bin)),
    ).resolved()


def _hand_tree(rng, L, splits, meta, pick, junk=False):
    """``splits`` leaf-wise splits in the reference's numbering (node s is
    the s-th split, the left child keeps the leaf's index, the right one
    gets the next); ``pick(rng, num_leaves)`` names the leaf to split.
    With ``junk`` the nodes no split reached hold random pointers."""
    n_int = max(L - 1, 1)
    F = len(meta.num_bin)
    t = {k: np.zeros(n_int, np.int32)
         for k in ("split_feature", "threshold_bin", "left_child",
                   "right_child")}
    if junk:
        t["left_child"] = rng.randint(-L, n_int, n_int).astype(np.int32)
        t["right_child"] = rng.randint(-L, n_int, n_int).astype(np.int32)
        t["split_feature"] = rng.randint(0, F, n_int).astype(np.int32)
    default_left = rng.rand(n_int) < 0.5
    parent = np.full(L, -1)
    for s in range(splits):
        leaf, new = pick(rng, s + 1), s + 1
        p = parent[leaf]
        if p >= 0:
            side = "left_child" if t["left_child"][p] == ~leaf \
                else "right_child"
            t[side][p] = s
        f = rng.randint(F)
        t["split_feature"][s] = f
        t["threshold_bin"][s] = rng.randint(0, meta.num_bin[f])
        t["left_child"][s], t["right_child"][s] = ~leaf, ~new
        parent[leaf] = parent[new] = s
    return TreeArrays.empty(L)._replace(
        default_left=jnp.asarray(default_left),
        num_leaves=jnp.asarray(splits + 1, jnp.int32),
        **{k: jnp.asarray(v) for k, v in t.items()})


def _any_leaf(rng, num_leaves):
    return rng.randint(num_leaves)


def _rows(rng, meta, n, group_bins=None):
    hi = np.asarray(group_bins if group_bins is not None else meta.num_bin)
    rows = np.stack([rng.randint(0, h, n) for h in hi])
    return rows.astype(np.uint8 if hi.max() <= 256 else np.uint16)


def _stump(rng):
    meta = _meta([16] * 5)
    return (_hand_tree(rng, 15, 0, meta, _any_leaf, junk=True),
            _rows(rng, meta, 300), meta)


def _stopped_early(rng):
    """7 of 31 leaves: dead nodes and leaves hold junk and match nothing."""
    meta = _meta([32] * 6)
    return (_hand_tree(rng, 31, 6, meta, _any_leaf, junk=True),
            _rows(rng, meta, 500), meta)


def _left_chain(rng):
    """Depth L-1: leaf 0 is split every time, so its path holds every node."""
    meta = _meta([64] * 4)
    return (_hand_tree(rng, 31, 30, meta, lambda rng, nl: 0),
            _rows(rng, meta, 700), meta)


def _full_random(rng):
    """Every node live; one column uses all 256 values a uint8 holds."""
    meta = _meta([63] * 8 + [256])
    return (_hand_tree(rng, 255, 254, meta, _any_leaf),
            _rows(rng, meta, 2000), meta)


def _missing_types(rng):
    """NAN (missing = the last bin), ZERO (missing = ``default_bin``) and
    NONE features, thresholds at and around the missing bins, both
    ``default_left`` values (drawn per node)."""
    num_bin = [8, 8, 8, 5, 5, 5]
    meta = _meta(num_bin,
                 missing=[MissingType.NAN, MissingType.ZERO, MissingType.NONE]
                 * 2, default_bin=[0, 3, 2, 0, 0, 4])
    tree = _hand_tree(rng, 63, 62, meta, _any_leaf)
    return tree, _rows(rng, meta, 1500), meta


def _bundled(rng):
    """EFB: nine features in four columns.  Feature ``f``'s bins ``b >= 1``
    live at merged bin ``feat_start[f] + b - 1`` of its column; every other
    merged bin decodes to ``f``'s bin 0."""
    meta = dataclasses.replace(
        _meta([6, 4, 9, 5, 7, 3, 8, 16, 12],
              missing=[0, 0, MissingType.NAN, 0, MissingType.ZERO, 0, 0,
                       MissingType.NAN, 0],
              default_bin=[0, 0, 0, 0, 2, 0, 0, 0, 0]),
        feat_group=np.array([0, 0, 0, 1, 1, 1, 1, 2, 3], np.int32),
        feat_start=np.array([1, 6, 9, 1, 5, 11, 13, 1, 1], np.int32),
        num_groups=4, max_group_bin=20)
    tree = _hand_tree(rng, 63, 62, meta, _any_leaf)
    return tree, _rows(rng, meta, 1500, group_bins=[17, 20, 16, 12]), meta


def _wide_bins(rng):
    """More than 256 bins a column: the matrix is uint16 and the node
    columns are taken, not multiplied out."""
    meta = _meta([300, 700, 16], missing=[MissingType.NAN, 0, 0])
    tree = _hand_tree(rng, 31, 30, meta, _any_leaf)
    return tree, _rows(rng, meta, 800), meta


def _permuted_columns(rng):
    """The static ``meta`` of ``_tree_pred_train_jit``: ``feat_group``
    points into a matrix whose columns are permuted and padded."""
    meta = _meta([16] * 6)
    meta = dataclasses.replace(
        meta, feat_group=np.array([5, 2, 7, 0, 3, 6], np.int32), num_groups=8)
    tree = _hand_tree(rng, 31, 30, meta, _any_leaf)
    return tree, _rows(rng, meta, 600, group_bins=[16] * 8), meta


def _grown(rng):
    """A 255-leaf tree the grower grew on seeded rows."""
    n, F, B = 6000, 12, 63
    meta = _meta([B] * F)
    binned = _rows(rng, meta, n)
    grad = (rng.randn(n) + np.sin(binned[0] / 7.0) - (binned[3] > 40)
            + 0.5 * (binned[5] % 5)).astype(np.float32)
    cfg = GrowerConfig(num_leaves=255, hp=SplitHyperparams(min_data_in_leaf=2),
                       num_bins=B, hist_method="scatter")
    tree, leaf_id = grow_tree(jnp.asarray(binned), jnp.asarray(grad),
                              jnp.ones(n, jnp.float32),
                              jnp.ones(n, jnp.float32), meta, cfg)
    assert int(tree.num_leaves) == 255
    np.testing.assert_array_equal(
        np.asarray(route_leaf_index_binned(tree, jnp.asarray(binned), meta)),
        np.asarray(leaf_id))
    return tree, binned, meta


CASES = {"stump": _stump, "stopped_at_7_of_31": _stopped_early,
         "left_chain": _left_chain, "random_255": _full_random,
         "missing_types": _missing_types, "efb_bundles": _bundled,
         "permuted_static_meta": _permuted_columns, "grown_255": _grown,
         "uint16_bins": _wide_bins}


@pytest.mark.parametrize("runtime_meta", [False, True],
                         ids=["static_meta", "meta_arrays"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_path_form_equals_the_walk(case, runtime_meta):
    tree, binned, meta = CASES[case](np.random.RandomState(len(case)))
    binned = jnp.asarray(binned)
    args = ((None, meta.as_runtime_arrays()) if runtime_meta else (meta,))
    walk = np.asarray(predict_leaf_index_binned(tree, binned, *args))
    assert walk.min() >= 0 and walk.max() < int(tree.num_leaves)
    # one block, whole blocks, and a row count that is no multiple of the
    # block (the last block overlaps the one before)
    for block in (grower.ROUTE_BLOCK_ROWS, 100, 128):
        got = route_leaf_index_binned(tree, binned, *args, block=block)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), walk)
    # the entry point takes the path form when its caller says so
    np.testing.assert_array_equal(
        np.asarray(jax.jit(
            lambda t, b: predict_leaf_index_binned(t, b, *args, routed=True))(
                tree, binned)), walk)


def test_missing_rows_follow_default_left():
    """One split on a NAN feature: rows in the missing bin go where
    ``default_left`` says, whatever the threshold."""
    meta = _meta([8], missing=[MissingType.NAN])
    binned = jnp.asarray(np.arange(8, dtype=np.uint8)[None, :])
    for dl in (False, True):
        tree = TreeArrays.empty(3)._replace(
            threshold_bin=jnp.array([2, 0], jnp.int32),
            default_left=jnp.array([dl, False]),
            left_child=jnp.array([~0, 0], jnp.int32),
            right_child=jnp.array([~1, 0], jnp.int32),
            num_leaves=jnp.asarray(2, jnp.int32))
        want = [0, 0, 0, 1, 1, 1, 1, 0 if dl else 1]
        for fn in (predict_leaf_index_binned, route_leaf_index_binned):
            assert np.asarray(fn(tree, binned, meta)).tolist() == want


def test_predicate_follows_the_metadata_and_the_backend(monkeypatch):
    numeric = _meta([8, 8])
    with_cat = dataclasses.replace(
        numeric, is_categorical=np.array([False, True]))
    assert not grower.leaf_router_engages(numeric)      # CPU: the walk
    monkeypatch.setattr(grower, "on_accelerator", lambda: True)
    assert grower.leaf_router_engages(numeric)
    assert not grower.leaf_router_engages(with_cat)


def _counters():
    c = global_registry.to_dict().get("counters", {})
    return (c.get("valid_update_trees_routed_total", 0),
            c.get("valid_update_trees_walked_total", 0))


def _train_with_valid(params, train, test, rounds, **dataset_kw):
    ds = lgb.Dataset(train.X, label=train.y, **dataset_kw)
    valid = lgb.Dataset(test.X, label=test.y, reference=ds, **dataset_kw)
    evals = {}
    lgb.train(dict(params, verbosity=-1), ds, num_boost_round=rounds,
              valid_sets=[valid], valid_names=["valid"], evals_result=evals,
              verbose_eval=False)
    return evals["valid"]


PUBLIC = {
    "binary": (example_data.binary, 1, {
        "objective": "binary", "metric": ["auc", "binary_logloss"],
        "num_leaves": 31, "min_data_in_leaf": 5}),
    "multiclass": (example_data.multiclass, 5, {
        "objective": "multiclass", "num_class": 5, "metric": "multi_logloss",
        "num_leaves": 15, "min_data_in_leaf": 5}),
    "multiclass_k3": (None, 3, {
        "objective": "multiclass", "num_class": 3, "metric": "multi_logloss",
        "num_leaves": 15, "min_data_in_leaf": 5}),
    "rf": (example_data.binary, 1, {
        "objective": "binary", "boosting": "rf", "metric": "binary_logloss",
        "bagging_freq": 1, "bagging_fraction": 0.7, "feature_fraction": 0.8,
        "num_leaves": 31, "min_data_in_leaf": 5}),
    "per_iteration": (example_data.binary, 1, {
        "objective": "binary", "metric": "binary_logloss", "num_leaves": 31,
        "min_data_in_leaf": 5}),
}


def _three_classes():
    train, test = example_data.multiclass()
    return (train._replace(y=np.minimum(train.y, 2)),
            test._replace(y=np.minimum(test.y, 2)))


@pytest.mark.parametrize("case", sorted(PUBLIC))
def test_lgb_train_evaluates_the_same_with_the_path_form(case, monkeypatch):
    """``evals_result`` of ``lgb.train(..., valid_sets=...)`` with the
    predicate forced true equals the walk's exactly, and the counters book
    every tree x class under the program that ran."""
    data, K, params = PUBLIC[case]
    train, test = data() if data else _three_classes()
    if case == "per_iteration":
        monkeypatch.setenv("LGBM_TPU_CHUNK", "0")
    rounds = 6
    traced = []
    path_form = grower.route_leaf_index_binned
    monkeypatch.setattr(
        grower, "route_leaf_index_binned",
        lambda *a, **kw: traced.append(1) or path_form(*a, **kw))
    r0, w0 = _counters()
    walked = _train_with_valid(params, train, test, rounds)
    r1, w1 = _counters()
    assert (r1 - r0, w1 - w0) == (0, rounds * K) and not traced
    monkeypatch.setattr(gbdt, "leaf_router_engages",
                        lambda meta: not meta.is_categorical.any())
    routed = _train_with_valid(params, train, test, rounds)
    r2, w2 = _counters()
    assert (r2 - r1, w2 - w1) == (rounds * K, 0) and traced
    assert routed.keys() == walked.keys()
    for name in walked:
        assert len(walked[name]) == rounds
        np.testing.assert_allclose(routed[name], walked[name], rtol=0, atol=0)


def test_a_categorical_feature_keeps_the_walk(monkeypatch):
    """Forced onto the accelerator's side of the predicate, a data set
    with a categorical feature still walks, and the counter says so."""
    monkeypatch.setattr(grower, "on_accelerator", lambda: True)
    rng = np.random.RandomState(7)
    n = 1500
    X = rng.randn(n, 5)
    X[:, 2] = rng.randint(0, 6, n)
    y = ((X[:, 2] % 2 == 0) ^ (X[:, 0] > 0.2)).astype(float)
    split = example_data.Split
    r0, w0 = _counters()
    out = _train_with_valid(
        {"objective": "binary", "metric": "binary_logloss", "num_leaves": 15,
         "min_data_in_leaf": 5}, split(X[:1200], y[:1200]),
        split(X[1200:], y[1200:]), 4, categorical_feature=[2])
    r1, w1 = _counters()
    assert (r1 - r0, w1 - w0) == (0, 4)
    assert out["binary_logloss"][-1] < out["binary_logloss"][0]


def test_eval_routed_share_reads_the_counters(monkeypatch):
    """The benchmark's reader: 100 x routed / (routed + walked), ``None``
    where the program made neither counter."""
    from benchmark.lib import lookup
    from lightgbm_tpu.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "global_registry", reg)
    manifest = lookup.load_manifest()
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "eval_routed_share")
    assert entry == {
        "name": "eval_routed_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "eval",
        "moves": "train_s_per_tree", "workloads": ["criteo-quant.monitored"]}
    read = lookup.load_module(
        lookup.find(manifest, "metrics/eval_routed_share.py")).read
    assert read({}) is None
    reg.counter("valid_update_trees_walked_total").inc(3)
    assert read({}) == 0.0
    reg.counter("valid_update_trees_routed_total").inc(9)
    assert read({}) == 75.0
