"""Batched-frontier grower (grower_rounds.py) vs serial grower equality.

The rounds grower must produce STRUCTURALLY IDENTICAL trees to the serial
best-first grower — same splits, same node/leaf numbering — for every gain
pattern (its exactness check falls back to single steps when a round would
deviate).  Float fields (gains, sums, leaf values) agree only to float32
accumulation order: the two growers sum histogram bins in different orders,
the same class of difference as the reference's CPU vs GPU histograms.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import FeatureMeta
from lightgbm_tpu.grower import GrowerConfig, grow_tree
from lightgbm_tpu.grower_rounds import grow_tree_rounds
from lightgbm_tpu.ops.split import SplitHyperparams


def _meta(B, F):
    return FeatureMeta(
        num_bin=np.full(F, B, np.int32),
        missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        most_freq_bin=np.zeros(F, np.int32),
        is_categorical=np.zeros(F, bool),
        max_num_bin=B,
    )


def _assert_trees_equal(t1, t2):
    nl = int(t1.num_leaves)
    assert nl == int(t2.num_leaves)
    nn = max(nl - 1, 1)
    for name in ("split_feature", "threshold_bin", "default_left",
                 "is_categorical", "left_child", "right_child"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, name))[:nn],
            np.asarray(getattr(t2, name))[:nn], err_msg=name)
    for name in ("split_gain", "internal_value", "internal_count"):
        np.testing.assert_allclose(
            np.asarray(getattr(t1, name))[:nn],
            np.asarray(getattr(t2, name))[:nn], rtol=3e-5, err_msg=name)
    for name in ("leaf_value", "leaf_weight", "leaf_count"):
        np.testing.assert_allclose(
            np.asarray(getattr(t1, name))[:nl],
            np.asarray(getattr(t2, name))[:nl], rtol=3e-5, atol=1e-7,
            err_msg=name)


def _grow_both(binned, grad, hess, mask, meta, cfg, mc=None):
    t_s, lid_s = grow_tree(jnp.asarray(binned.T), jnp.asarray(grad),
                           jnp.asarray(hess), jnp.asarray(mask), meta, cfg,
                           monotone_constraints=mc)
    t_r, lid_r = grow_tree_rounds(jnp.asarray(binned.T), jnp.asarray(grad),
                                  jnp.asarray(hess), jnp.asarray(mask),
                                  meta, cfg, monotone_constraints=mc)
    _assert_trees_equal(t_s, t_r)
    np.testing.assert_array_equal(np.asarray(lid_s), np.asarray(lid_r))


@pytest.fixture
def problem():
    rng = np.random.RandomState(7)
    n, F, B = 4096, 10, 32
    binned = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    grad = (rng.randn(n) + 0.7 * (binned[:, 1] > 16)
            - 0.4 * (binned[:, 3] < 5)).astype(np.float32)
    hess = np.ones(n, np.float32)
    return binned, grad, hess, B, F


@pytest.mark.parametrize("leaves", [2, 7, 31, 64])
def test_rounds_equals_serial(problem, leaves):
    binned, grad, hess, B, F = problem
    cfg = GrowerConfig(num_leaves=leaves, num_bins=B, hp=SplitHyperparams(),
                       hist_method="scatter")
    _grow_both(binned, grad, hess, np.ones(len(grad), np.float32),
               _meta(B, F), cfg)


def test_rounds_equals_serial_bagging_and_depth(problem):
    binned, grad, hess, B, F = problem
    rng = np.random.RandomState(3)
    mask = (rng.rand(len(grad)) < 0.7).astype(np.float32) * 2.0
    cfg = GrowerConfig(num_leaves=31, max_depth=4, num_bins=B,
                       hp=SplitHyperparams(min_data_in_leaf=40),
                       hist_method="scatter")
    _grow_both(binned, grad, hess, mask, _meta(B, F), cfg)


def test_rounds_equals_serial_monotone(problem):
    binned, grad, hess, B, F = problem
    mc = np.zeros(F, np.int32)
    mc[1] = 1
    mc[3] = -1
    cfg = GrowerConfig(num_leaves=31, num_bins=B, hp=SplitHyperparams(),
                       hist_method="scatter")
    _grow_both(binned, grad, hess, np.ones(len(grad), np.float32),
               _meta(B, F), cfg, mc=jnp.asarray(mc))


def test_rounds_equals_serial_adversarial_xor():
    """XOR-style data: a child's split gain EXCEEDS its parent's, forcing
    the rounds grower through its exactness fallback path."""
    rng = np.random.RandomState(0)
    n, F, B = 4096, 6, 16
    binned = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    a = binned[:, 0] >= 8
    b = binned[:, 1] >= 8
    grad = (np.where(a ^ b, 1.0, -1.0) + 0.01 * rng.randn(n)
            ).astype(np.float32)
    hess = np.ones(n, np.float32)
    for leaves in (4, 9, 31):
        cfg = GrowerConfig(num_leaves=leaves, num_bins=B,
                           hp=SplitHyperparams(), hist_method="scatter")
        _grow_both(binned, grad, hess, np.ones(n, np.float32),
                   _meta(B, F), cfg)


def test_rounds_equals_serial_extra_trees_and_bynode(problem):
    """Node RNG keys derive from node identity in both growers, so the
    randomized modes stay structurally identical too."""
    import jax
    binned, grad, hess, B, F = problem
    cfg = GrowerConfig(num_leaves=31, num_bins=B,
                       hp=SplitHyperparams(extra_trees=True),
                       bynode_feature_cnt=5, hist_method="scatter")
    mask = np.ones(len(grad), np.float32)
    meta = _meta(B, F)
    key = jax.random.PRNGKey(42)
    t_s, lid_s = grow_tree(jnp.asarray(binned.T), jnp.asarray(grad),
                           jnp.asarray(hess), jnp.asarray(mask), meta, cfg,
                           rng_key=key)
    t_r, lid_r = grow_tree_rounds(jnp.asarray(binned.T), jnp.asarray(grad),
                                  jnp.asarray(hess), jnp.asarray(mask),
                                  meta, cfg, rng_key=key)
    _assert_trees_equal(t_s, t_r)
    np.testing.assert_array_equal(np.asarray(lid_s), np.asarray(lid_r))


def test_rounds_data_parallel_matches_single(problem):
    """Rounds grower under shard_map row sharding == single-device rounds."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    binned, grad, hess, B, F = problem
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=15, num_bins=B,
                       hp=SplitHyperparams(min_data_in_leaf=10),
                       hist_method="scatter")
    mask = np.ones(len(grad), np.float32)
    ref_tree, ref_leaf = grow_tree_rounds(
        jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(mask), meta, cfg)

    assert jax.device_count() >= 8
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    sharded = jax.shard_map(
        lambda b, g, h, m: grow_tree_rounds(b, g, h, m, meta, cfg,
                                            axis_name="d"),
        mesh=mesh, in_specs=(P(None, "d"), P("d"), P("d"), P("d")),
        out_specs=(P(), P("d")), check_vma=False)
    tree, leaf_id = jax.jit(sharded)(
        np.ascontiguousarray(binned.T), grad, hess, mask)

    nl = int(ref_tree.num_leaves)
    assert int(tree.num_leaves) == nl
    np.testing.assert_array_equal(np.asarray(tree.split_feature[:nl - 1]),
                                  np.asarray(ref_tree.split_feature[:nl - 1]))
    np.testing.assert_array_equal(np.asarray(tree.threshold_bin[:nl - 1]),
                                  np.asarray(ref_tree.threshold_bin[:nl - 1]))
    np.testing.assert_allclose(np.asarray(tree.leaf_value[:nl]),
                               np.asarray(ref_tree.leaf_value[:nl]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(leaf_id),
                                  np.asarray(ref_leaf))


def test_fast_mode_trains_equivalent_quality():
    """tpu_tree_growth=fast (no exactness fallback) may pick a different
    final-level split set, but trained quality must match exact growth."""
    rng = np.random.RandomState(2)
    n = 6000
    X = rng.rand(n, 10).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2] - X[:, 3] + 0.2 * rng.randn(n)) > 0.2
         ).astype(np.float32)
    Xt, yt = X[:4500], y[:4500]
    Xv, yv = X[4500:], y[4500:]
    loss = {}
    for mode in ("rounds", "fast"):
        params = {"objective": "binary", "num_leaves": 31, "max_bin": 32,
                  "metric": "binary_logloss", "verbosity": -1,
                  "tpu_tree_growth": mode}
        ds = lgb.Dataset(Xt, label=yt)
        evals = {}
        import lightgbm_tpu.callback as cb
        bst = lgb.train(params, ds, num_boost_round=10,
                        valid_sets=[ds.create_valid(Xv, label=yv)],
                        valid_names=["v"],
                        callbacks=[lgb.record_evaluation(evals)])
        assert bst.models[0].num_leaves == 31
        loss[mode] = evals["v"]["binary_logloss"][-1]
    assert abs(loss["fast"] - loss["rounds"]) < 0.01, loss


def test_rounds_engine_matches_serial_model():
    """End-to-end through the engine (incl. EFB bundling and multiple
    boosting iterations): same structures, predictions within float
    accumulation tolerance."""
    rng = np.random.RandomState(11)
    n = 3000
    X = rng.rand(n, 12).astype(np.float32)
    X[:, 5] = (X[:, 5] > 0.6).astype(np.float32)     # sparse-ish for EFB
    X[:, 7] = 0.0
    y = ((X[:, 0] + X[:, 1] * X[:, 2] - X[:, 5] + 0.2 * rng.randn(n)) > 0.5
         ).astype(np.float32)
    dumps, preds = {}, {}
    for mode in ("serial", "rounds"):
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "max_bin": 32, "verbosity": -1,
                  "tpu_tree_growth": mode}
        ds = lgb.Dataset(X, label=y)
        booster = lgb.train(params, ds, num_boost_round=8)
        dumps[mode] = booster.dump_model()
        preds[mode] = booster.predict(X)

    def structures(d):
        out = []
        def walk(node):
            if "split_feature" in node:
                out.append((node["split_feature"], node["threshold"],
                            node["default_left"]))
                walk(node["left_child"]); walk(node["right_child"])
        for t in d["tree_info"]:
            walk(t["tree_structure"])
        return out

    assert structures(dumps["serial"]) == structures(dumps["rounds"])
    np.testing.assert_allclose(preds["serial"], preds["rounds"],
                               rtol=2e-4, atol=2e-6)


def test_rounds_goss_matches_serial():
    """GOSS amplified weights flow through the rounds grower's weighted
    smaller-child selection identically to serial growth."""
    rng = np.random.RandomState(4)
    n = 5000
    X = rng.rand(n, 8).astype(np.float32)
    y = ((X[:, 0] * X[:, 1] + 0.1 * rng.randn(n)) > 0.25).astype(np.float32)
    preds = {}
    for mode in ("serial", "rounds"):
        params = {"objective": "binary", "boosting": "goss",
                  "top_rate": 0.3, "other_rate": 0.2, "num_leaves": 15,
                  "max_bin": 32, "verbosity": -1, "tpu_tree_growth": mode,
                  "learning_rate": 0.2}
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=12)
        preds[mode] = bst.predict(X)
    np.testing.assert_allclose(preds["serial"], preds["rounds"],
                               rtol=2e-4, atol=2e-6)


def test_rounds_equals_serial_categorical():
    """Categorical splits (one-hot + sorted many-vs-many bitsets) through
    the batched partition's per-row bitset path."""
    rng = np.random.RandomState(9)
    n = 5000
    Xnum = rng.rand(n, 4).astype(np.float32)
    cat1 = rng.randint(0, 12, n)
    cat2 = rng.randint(0, 5, n)
    X = np.column_stack([Xnum, cat1, cat2]).astype(np.float32)
    eff = np.array([0.9, -0.4, 0.1, 0.6, -0.8, 0.2, 0.5, -0.3, 0.0, 0.7,
                    -0.6, 0.4])
    y = ((X[:, 0] + eff[cat1] + 0.3 * (cat2 == 2) + 0.15 * rng.randn(n))
         > 0.5).astype(np.float32)
    dumps, preds = {}, {}
    for mode in ("serial", "rounds"):
        params = {"objective": "binary", "num_leaves": 15, "max_bin": 32,
                  "verbosity": -1, "tpu_tree_growth": mode,
                  "categorical_feature": [4, 5],
                  "min_data_per_group": 10, "cat_smooth": 5.0}
        bst = lgb.train(params, lgb.Dataset(
            X, label=y, categorical_feature=[4, 5]), num_boost_round=6)
        dumps[mode] = bst.dump_model()
        preds[mode] = bst.predict(X)

    def structures(d):
        out = []
        def walk(nd):
            if "split_feature" in nd:
                out.append((nd["split_feature"], nd.get("threshold"),
                            nd.get("decision_type")))
                walk(nd["left_child"]); walk(nd["right_child"])
        for t in d["tree_info"]:
            walk(t["tree_structure"])
        return out

    assert structures(dumps["serial"]) == structures(dumps["rounds"])
    np.testing.assert_allclose(preds["serial"], preds["rounds"],
                               rtol=2e-4, atol=2e-6)


def test_rounds_equals_serial_sorted_seghist(problem, monkeypatch):
    """The sorted-arena segment histogram (the TPU path) must leave the
    rounds grower structurally identical to the serial grower; forced on
    CPU via the LGBM_TPU_SEGHIST testing hook."""
    monkeypatch.setenv("LGBM_TPU_SEGHIST", "sorted")
    binned, grad, hess, B, F = problem
    mask = np.ones(len(grad), np.float32)
    meta = _meta(B, F)
    for leaves in (7, 31, 64):
        cfg = GrowerConfig(num_leaves=leaves, num_bins=B,
                           hp=SplitHyperparams(), hist_method="scatter")
        t_s, lid_s = grow_tree(jnp.asarray(binned.T), jnp.asarray(grad),
                               jnp.asarray(hess), jnp.asarray(mask),
                               meta, cfg)
        t_r, lid_r = grow_tree_rounds(jnp.asarray(binned.T), jnp.asarray(grad),
                                      jnp.asarray(hess), jnp.asarray(mask),
                                      meta, cfg)
        # structure must be identical; floats only to accumulation order
        # (the sorted arena reduces via block partials — one more stage of
        # f32 reordering than the scatter path, hence the looser rtol)
        nl = int(t_s.num_leaves)
        assert nl == int(t_r.num_leaves)
        nn = max(nl - 1, 1)
        for name in ("split_feature", "threshold_bin", "default_left",
                     "left_child", "right_child"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t_s, name))[:nn],
                np.asarray(getattr(t_r, name))[:nn], err_msg=name)
        np.testing.assert_array_equal(np.asarray(lid_s), np.asarray(lid_r))
        for name in ("leaf_value", "split_gain"):
            np.testing.assert_allclose(
                np.asarray(getattr(t_s, name))[:nn],
                np.asarray(getattr(t_r, name))[:nn], rtol=2e-4, atol=1e-5,
                err_msg=name)


def test_rounds_data_parallel_sorted_dispatch(problem, monkeypatch):
    """The TPU seghist dispatch (slot-expanded pass / sorted arena, forced
    via LGBM_TPU_SEGHIST=sorted) must agree with single-device growth when
    psum'd under shard_map row sharding — the headline TPU configuration."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setenv("LGBM_TPU_SEGHIST", "sorted")
    binned, grad, hess, B, F = problem
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=15, num_bins=B,
                       hp=SplitHyperparams(min_data_in_leaf=10),
                       hist_method="matmul_f32")
    mask = np.ones(len(grad), np.float32)
    ref_tree, ref_leaf = grow_tree_rounds(
        jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(mask), meta, cfg)

    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    sharded = jax.shard_map(
        lambda b, g, h, m: grow_tree_rounds(b, g, h, m, meta, cfg,
                                            axis_name="d"),
        mesh=mesh, in_specs=(P(None, "d"), P("d"), P("d"), P("d")),
        out_specs=(P(), P("d")), check_vma=False)
    tree, leaf_id = jax.jit(sharded)(
        np.ascontiguousarray(binned.T), grad, hess, mask)

    nl = int(ref_tree.num_leaves)
    assert int(tree.num_leaves) == nl
    np.testing.assert_array_equal(np.asarray(tree.split_feature[:nl - 1]),
                                  np.asarray(ref_tree.split_feature[:nl - 1]))
    np.testing.assert_allclose(np.asarray(tree.leaf_value[:nl]),
                               np.asarray(ref_tree.leaf_value[:nl]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(leaf_id), np.asarray(ref_leaf))


def _router_case(case, problem):
    """(grow, lanes_of) for one case of the router-against-scan test:
    ``grow()`` grows one tree and returns (tree, leaf ids, stats)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from lightgbm_tpu.binning import MissingType
    binned, grad, hess, B, F = problem
    mask = np.ones(len(grad), np.float32)
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=31, num_bins=B,
                       hp=SplitHyperparams(min_data_in_leaf=10),
                       hist_method="matmul_f32")
    kw = {}
    if case == "narrow":
        # a cap of 16: every round at the narrowest rung's lanes
        cfg = cfg._replace(num_leaves=17)
    elif case == "missing":
        # every missing type, with default bins off the first: NaN rows in
        # the last bin, zero rows at the default bin
        mt = np.array([int(MissingType.NAN), int(MissingType.ZERO),
                       int(MissingType.NONE)] * 4, np.int32)[:F]
        db = np.arange(F, dtype=np.int32) % 7 + 3
        meta = FeatureMeta(
            num_bin=np.full(F, B, np.int32), missing_type=mt,
            default_bin=db, most_freq_bin=np.zeros(F, np.int32),
            is_categorical=np.zeros(F, bool), max_num_bin=B)
        binned = binned.copy()
        binned[::5, 1] = B - 1
        binned[1::6, 4] = db[4]
        grad = (grad + 0.9 * (binned[:, 1] == B - 1)
                - 0.8 * (binned[:, 4] == db[4])).astype(np.float32)
    elif case == "rungs":
        # the fused arm at 255 leaves on int8 gradients: rounds at 16, 64
        # and the cap, the route in the pass's branch at its width
        from lightgbm_tpu.ops import histogram as H
        cfg = GrowerConfig(num_leaves=255, num_bins=B, quant=True,
                           quant_bins=8, hist_method="fused",
                           hp=SplitHyperparams(min_data_in_leaf=2))
        kw["quant_vals"] = H.quantize_gradients(
            jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), 8,
            jax.random.PRNGKey(3))
    elif case == "efb":
        # sparse exclusive columns bundled into shared groups: the route
        # decodes a row's bin by its feature's group and start
        rng = np.random.RandomState(0)
        n = len(grad)
        groups = rng.randint(0, 8, size=n)
        X = np.zeros((n, 8), np.float32)
        X[np.arange(n), groups] = rng.randint(1, 9, n)
        X = np.concatenate([X, rng.rand(n, 4).astype(np.float32)], axis=1)
        ds = lgb.Dataset(X, label=np.zeros(n), free_raw_data=False,
                         params={"max_bin": 31, "verbosity": -1})
        ds.construct()
        meta = ds.feature_meta()
        assert meta.resolved().has_bundles, "test premise: EFB fires"
        binned = np.asarray(ds.host_binned())
        grad = (0.6 * (groups % 3 == 1) - X[:, 2] + 0.8 * (X[:, 9] > 0.4)
                + 0.2 * rng.randn(n)).astype(np.float32)
        cfg = cfg._replace(num_bins=int(meta.max_num_bin))
    args = (jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta, cfg)
    if case == "data_parallel":
        mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
        # a new program a call: the form is fixed when it is traced
        return lambda: jax.jit(jax.shard_map(
            lambda b, g, h, m: grow_tree_rounds(
                b, g, h, m, meta, cfg._replace(num_machines=4),
                axis_name="d", with_stats=True),
            mesh=mesh, in_specs=(P(None, "d"), P("d"), P("d"), P("d")),
            out_specs=(P(), P("d"), P()), check_vma=False))(*args[:4])
    return lambda: grow_tree_rounds(*args, with_stats=True, **kw)


@pytest.mark.parametrize("case", ["cap", "narrow", "missing", "rungs",
                                  "efb", "data_parallel"])
def test_router_matmul_matches_scan(problem, monkeypatch, case):
    """The router form (rows decided a block at a time against the round's
    lanes, ``route_lanes``) must produce the identical tree and rows to
    the candidate scan it replaces: at the cap's lanes, at 16, with every
    missing type and default bins, through the fused arm's rungs (16 / 64
    / cap, the lanes following the pass's width), on EFB bundles and on
    four data-parallel shards.  Blocks of 1,000 rows, so every tree
    decides its rows in several and rewrites a last one."""
    from lightgbm_tpu import grower_rounds as GR
    monkeypatch.setattr(GR, "ROUTE_BLOCK", 1000)
    grow = _router_case(case, problem)
    monkeypatch.setenv("LGBM_TPU_SEGHIST", "sorted")
    monkeypatch.setenv("LGBM_TPU_ROUTER", "0")
    t_scan, lid_scan, st_scan = grow()
    monkeypatch.setenv("LGBM_TPU_ROUTER", "1")
    t_rt, lid_rt, st_rt = grow()
    _assert_trees_equal(t_scan, t_rt)
    for name in t_scan._fields:
        np.testing.assert_array_equal(np.asarray(getattr(t_scan, name)),
                                      np.asarray(getattr(t_rt, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(lid_scan), np.asarray(lid_rt))
    rounds, offered, applied, slots, clipped, lanes, rows = (
        int(v) for v in st_rt)
    np.testing.assert_array_equal(np.asarray(st_scan)[:5],
                                  np.asarray(st_rt)[:5])
    assert int(st_scan[5]) == offered            # the scan: live lanes
    # every pass read every row: the root's and one a round
    assert rows == int(st_scan[6]) == (rounds + 1) * len(lid_rt)
    if case == "rungs":
        # the router's lanes are the pass's widths, the root's aside
        assert lanes == slots - 16 and lanes < 128 * rounds
        assert rounds > 8
    else:                                        # staged: the cap
        assert lanes == (16 if case == "narrow" else 30) * rounds


def _route_round(seed, n, F, bins, k, W):
    """A random numeric carry as a round sees it: rows in 63 leaves, every
    leaf with a cached split, the round's candidates in a random order,
    ``k`` of the ``W`` lanes live."""
    from lightgbm_tpu.grower import _LeafBest
    rng = np.random.default_rng(seed)
    L = 63
    layout = tuple(jnp.asarray(a, jnp.int32) for a in (
        np.full(F, bins - 1), rng.integers(0, 3, F),
        rng.integers(0, bins - 1, F), np.arange(F), np.ones(F)))
    best = _LeafBest.empty(L)._replace(
        feature=jnp.asarray(rng.integers(0, F, L), jnp.int32),
        threshold=jnp.asarray(rng.integers(0, bins - 1, L), jnp.int32),
        default_left=jnp.asarray(rng.random(L) < 0.5),
        left_count=jnp.asarray(rng.integers(1, 9, L), jnp.float32),
        right_count=jnp.asarray(rng.integers(1, 9, L), jnp.float32))
    idl = jnp.asarray(rng.permutation(L)[:W], jnp.int32)
    return (jnp.asarray(rng.integers(0, bins, (F, n)).astype(np.uint8)),
            jnp.asarray(rng.integers(0, L, n), jnp.int32), idl,
            jnp.int32(k), best, layout)


@pytest.mark.parametrize("live", ["inside_a_block", "on_a_block_edge",
                                  "every_row"])
@pytest.mark.parametrize("form", ["lanes", "scan"])
def test_route_stops_at_the_live_rows(monkeypatch, form, live):
    """``route_lanes`` and ``route_scan`` told the buffer's live rows
    (``live_rows``) give every row before the bound what the unbounded call
    gives, and put every row at or past it in no lane and no slot: the
    bound inside a block of the router, on a block's edge, and at the last
    row."""
    import lightgbm_tpu.grower_rounds as GR
    monkeypatch.setattr(GR, "ROUTE_BLOCK", 700)
    n, bins, W, kcap = 5000, 32, 16, 16
    args = _route_round(11, n, 7, bins, 13, W)
    bound = {"inside_a_block": 2 * 700 + 311, "on_a_block_edge": 3 * 700,
             "every_row": n}[live]

    def route(**kw):
        if form == "lanes":
            out = GR.route_lanes(*args[:4], W, args[4], kcap, args[5], bins,
                                 0, **kw)
        else:
            out = GR.route_scan(*args[:4], args[4], kcap, args[5], False,
                                **kw)
        return [np.asarray(a) for a in out]
    crank, gl, slot = route()
    crank_b, gl_b, slot_b = route(live_rows=jnp.int32(bound))
    on = np.arange(n) < bound
    assert np.array_equal(crank_b[on], crank[on])
    assert np.array_equal(slot_b[on], slot[on])
    held = on & (crank < kcap)
    assert np.array_equal(gl_b[held], gl[held])
    assert (crank_b[~on] == kcap).all() and (slot_b[~on] == kcap).all()
    # the carry exercises what it should: rows in lanes and out of them
    assert 0 < held.sum() < on.sum()
    assert (crank[~on] < kcap).any() or bound == n


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_chip", "axis_name"])
def test_rounds_width_election_equals_serial_quantized(sharded):
    """The fused arm's accumulate pass runs at the narrowest compiled slot
    width that holds the round's candidates (the root at the narrowest),
    on one chip and on the sharded seam (where the padded arena is what is
    reduced).  Integer sums are associative, so at 255 leaves, through
    rounds of every width, the tree is the serial oracle's bit for bit."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from lightgbm_tpu.ops import fused as FU
    from lightgbm_tpu.ops import histogram as H

    rng = np.random.RandomState(21)
    n, F, B = 8192, 6, 32
    binned = rng.randint(0, B - 1, (n, F)).astype(np.uint8)
    y = (np.sin(binned[:, 0] * 0.3) + 0.2 * binned[:, 1]
         + 0.1 * binned[:, 2] * np.cos(binned[:, 3] * 0.2)
         + rng.randn(n) * 0.3)
    grad, hess = (-y).astype(np.float32), np.ones(n, np.float32)
    mask = np.ones(n, np.float32)
    meta = _meta(B, F)
    gq, hq, gs, hs = H.quantize_gradients(
        jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), 8,
        jax.random.PRNGKey(3))
    cfg = GrowerConfig(num_leaves=255, num_bins=B, quant=True, quant_bins=8,
                       hp=SplitHyperparams(min_data_in_leaf=2),
                       hist_method="scatter")
    bt = jnp.asarray(binned.T)
    t_s, lid_s = grow_tree(bt, jnp.asarray(grad), jnp.asarray(hess),
                           jnp.asarray(mask), meta, cfg,
                           quant_vals=(gq, hq, gs, hs))
    fused = cfg._replace(hist_method="fused")
    assert FU.NARROW_SLOT_WIDTHS == (16, 64)
    if sharded:
        mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
        grow = jax.jit(jax.shard_map(
            lambda b, g, h, m, q, r, a, c: grow_tree_rounds(
                b, g, h, m, meta, fused._replace(num_machines=4),
                axis_name="d", quant_vals=(q, r, a, c), with_stats=True),
            mesh=mesh,
            in_specs=(P(None, "d"), P("d"), P("d"), P("d"), P("d"), P("d"),
                      P(), P()),
            out_specs=(P(), P("d"), P()), check_vma=False))
        t_r, lid_r, stats = grow(bt, grad, hess, mask, gq, hq, gs, hs)
    else:
        t_r, lid_r, stats = grow_tree_rounds(
            bt, jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask),
            meta, fused, quant_vals=(gq, hq, gs, hs), with_stats=True)
    assert int(t_r.num_leaves) == 255
    for name in t_s._fields:
        np.testing.assert_array_equal(np.asarray(getattr(t_s, name)),
                                      np.asarray(getattr(t_r, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(lid_s), np.asarray(lid_r))
    rounds, offered, _, slots, clipped, lanes, rows = (int(v) for v in stats)
    assert lanes == offered             # the scan: the live lanes alone
    assert rows == (rounds + 1) * len(lid_r)    # every pass, every row
    # the root at 16, then every round at the rung its offer named: with
    # the offer following the commits, under 64 slots a round on average
    assert offered <= slots - 16 < 64 * rounds
    assert 0 <= clipped <= rounds


def _quantized_255(binned, grad, B, F):
    """(serial oracle's tree and rows, the fused rounds grower's arguments)
    for one 255-leaf tree on 8-level int8 gradients."""
    import jax

    from lightgbm_tpu.ops import histogram as H
    n = len(grad)
    hess, mask = np.ones(n, np.float32), np.ones(n, np.float32)
    meta = _meta(B, F)
    qv = H.quantize_gradients(jnp.asarray(grad), jnp.asarray(hess),
                              jnp.asarray(mask), 8, jax.random.PRNGKey(3))
    cfg = GrowerConfig(num_leaves=255, num_bins=B, quant=True, quant_bins=8,
                       hp=SplitHyperparams(min_data_in_leaf=2),
                       hist_method="scatter")
    args = (jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta)
    return (grow_tree(*args, cfg, quant_vals=qv),
            args + (cfg._replace(hist_method="fused"),), {"quant_vals": qv})


@pytest.mark.parametrize("gains", ["noise", "levels"])
def test_offer_equals_serial_quantized(gains):
    """The offer on int8 gradients against the serial oracle, bit for bit.

    ``noise``: gradients that are noise alone (the shape ``istella-rank``
    has under 4-level gradients): every leaf's best gain sits on the noise
    floor, a child outranks its parent's neighbours as often as not, and a
    round commits a couple of the candidates it builds.  The offer stays at
    the narrowest rung, so every pass is a narrow one, and no round is
    clipped that the cap would not have ended as early.

    ``levels``: gains that fall by level until the 8-level rounding drowns
    them: the first rounds commit all they offer and the offer climbs, the
    late ones commit a few and it comes down again, a clipped round on the
    way."""
    from lightgbm_tpu.ops import fused as FU
    rng = np.random.RandomState(5)
    n, B = 8192, 32
    if gains == "noise":
        F = 6
        binned = rng.randint(0, B - 1, (n, F)).astype(np.uint8)
        grad = rng.randn(n)
    else:
        F = 8
        binned = rng.randint(0, 2, (n, F)).astype(np.uint8)
        grad = -(binned * 0.5 ** np.arange(F)).sum(1)
        grad = grad - grad.mean()
    (t_s, lid_s), args, kw = _quantized_255(
        binned, grad.astype(np.float32), B, F)
    t_r, lid_r, stats = grow_tree_rounds(*args, with_stats=True, **kw)
    assert int(t_r.num_leaves) == int(t_s.num_leaves) > 200
    for name in t_s._fields:
        np.testing.assert_array_equal(np.asarray(getattr(t_s, name)),
                                      np.asarray(getattr(t_r, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(lid_s), np.asarray(lid_r))
    rounds, offered, applied, slots, clipped, lanes, rows = (
        int(v) for v in stats)
    assert rows == (rounds + 1) * len(lid_r)
    assert applied == int(t_r.num_leaves) - 1
    assert lanes == offered
    narrow, cap = FU.NARROW_SLOT_WIDTHS[0], 128
    wider = slots - narrow * (rounds + 1)       # what passes over 16 added
    if gains == "noise":
        assert rounds > 60                      # ~2 commits a round
        assert offered <= narrow * rounds
        # well under the cap's cost: nearly every pass at the narrowest
        assert wider < narrow * rounds / 4
        assert 0 <= clipped <= rounds // 10
    else:
        assert wider > 0 and 1 <= clipped <= rounds // 4
    assert slots - narrow < cap * rounds / 4


def test_offer_climbs_when_the_rounds_fill_the_rung(monkeypatch):
    """Gains that fall by level (each feature worth half the one before):
    every candidate a round offers commits, the frontier doubles, and the
    offer has to climb with it: 16 -> 64 when 16 of 16 commit, 64 -> the cap
    when 64 of 64 do.  No round is clipped and the loop takes the trips it
    takes with every pass at the cap: 1, 2, 4, ..., 64, 127 splits."""
    from lightgbm_tpu.ops import fused as FU
    rng = np.random.RandomState(5)
    n, F, B = 8192, 8, 32
    bits = rng.randint(0, 2, (n, F)).astype(np.uint8)
    grad = -(bits * 0.5 ** np.arange(F)).sum(1)
    grad = (grad - grad.mean()).astype(np.float32)
    cfg = GrowerConfig(num_leaves=255, num_bins=B, hist_method="fused",
                       hp=SplitHyperparams(min_data_in_leaf=2))
    args = (jnp.asarray(bits.T), jnp.asarray(grad),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32), _meta(B, F),
            cfg)
    assert FU.NARROW_SLOT_WIDTHS == (16, 64)
    t_of, _, st_of = grow_tree_rounds(*args, with_stats=True)
    monkeypatch.setattr(FU, "NARROW_SLOT_WIDTHS", ())      # every pass: cap
    t_fx, _, st_fx = grow_tree_rounds(*args, with_stats=True)
    # the same 255 leaves (a level's leaves gain within 1e-4 of each other,
    # and the f32 arena's summation order differs by width: their numbers
    # may differ, the rows they hold may not)
    assert int(t_of.num_leaves) == int(t_fx.num_leaves) == 255
    np.testing.assert_array_equal(np.sort(np.asarray(t_of.leaf_count)),
                                  np.sort(np.asarray(t_fx.leaf_count)))
    rounds, offered, applied, slots, clipped, lanes, rows = (
        int(v) for v in st_of)
    assert lanes == offered
    assert rows == int(st_fx[6]) == (rounds + 1) * n
    assert (rounds, offered, applied) == tuple(int(v) for v in st_fx[:3])
    assert (rounds, offered, applied, clipped) == (8, 254, 254, 0)
    # root, 1, 2, 4, 8, 16 at 16 slots; 32 and 64 at 64; 127 at the cap
    assert slots == 6 * 16 + 2 * 64 + 128
    assert int(st_fx[3]) == 128 * (rounds + 1) and int(st_fx[4]) == 0


RUNGS = (16, 64, 128)


@pytest.mark.parametrize("before,m,nxt", [
    (0, 1, 16),         # the tree's first rounds: the frontier binds
    (2, 3, 16),         # noisy gains: a few of 16 commit
    (3, 12, 16),        # the narrow rung holds it with a quarter to spare
    (3, 13, 64),        # at the rung's edge: the wider one
    (5, 16, 64),        # the whole offer committed: up a rung
    (16, 5, 64),        # one quiet round does not bring it down
    (1, 29, 64),        # late in a tree: 1, 29, 1, 43 ...
    (29, 1, 64),
    (5, 1, 16),         # two quiet rounds do
    (40, 48, 64),
    (40, 49, 128),
    (30, 64, 128),      # 64 of 64: up again
    (128, 30, 128),
    (30, 5, 64),        # and down a rung at a time as the memory drains
    (127, 127, 128),    # nothing over the cap
])
def test_next_offer(before, m, nxt):
    from lightgbm_tpu.grower_rounds import next_offer
    rungs = jnp.asarray(RUNGS, jnp.int32)
    assert int(next_offer(rungs, jnp.int32(m), jnp.int32(before))) == nxt
    # the rule is symmetric in the two rounds
    assert int(next_offer(rungs, jnp.int32(before), jnp.int32(m))) == nxt


@pytest.mark.parametrize("kcap", [1, 12, 16, 17, 20, 62, 64, 65, 128, 254])
def test_a_wholly_committed_offer_goes_up(kcap):
    """At every round cap: a round that committed all of an offer under
    the cap is followed by a wider one, and the cap by the cap."""
    from lightgbm_tpu.grower_rounds import next_offer
    from lightgbm_tpu.ops.fused import slot_widths
    widths = slot_widths(kcap)
    assert widths[-1] == kcap and list(widths) == sorted(set(widths))
    rungs = jnp.asarray(widths, jnp.int32)
    for lower, upper in zip(widths, widths[1:]):
        assert int(next_offer(rungs, jnp.int32(lower), jnp.int32(0))) \
            >= upper
    assert int(next_offer(rungs, jnp.int32(kcap), jnp.int32(kcap))) == kcap
    assert int(next_offer(rungs, jnp.int32(0), jnp.int32(0))) == widths[0]
