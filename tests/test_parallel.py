"""Distributed learner tests on the virtual 8-device CPU mesh.

Validates DataParallel/FeatureParallel semantics: sharded growth must
produce the SAME tree as single-device growth (the reference can only test
this with multi-machine sockets; here it's one process, 8 XLA devices).
"""

import example_data
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.dataset import FeatureMeta
from lightgbm_tpu.grower import GrowerConfig, grow_tree
from lightgbm_tpu.ops.split import SplitHyperparams
from lightgbm_tpu.parallel.learners import (DATA_AXIS, FEATURE_AXIS,
                                            create_parallel_grower, make_mesh,
                                            shard_dataset)


def _meta(B, F):
    return FeatureMeta(
        num_bin=np.full(F, B, np.int32),
        missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        most_freq_bin=np.zeros(F, np.int32),
        is_categorical=np.zeros(F, bool),
        max_num_bin=B,
    )


@pytest.fixture(scope="module")
def problem():
    rng = np.random.RandomState(0)
    n, F, B = 1024, 8, 16
    binned = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    grad = (rng.randn(n) + 0.5 * (binned[:, 1] > 8)).astype(np.float32)
    hess = np.ones(n, np.float32)
    return binned, grad, hess, B, F


def _single_device_tree(problem, cfg, meta):
    binned, grad, hess, B, F = problem
    tree, leaf_id = grow_tree(jnp.asarray(binned.T), jnp.asarray(grad),
                              jnp.asarray(hess),
                              jnp.ones(len(grad), jnp.float32), meta, cfg)
    return tree, np.asarray(leaf_id)


def test_data_parallel_matches_serial(problem):
    binned, grad, hess, B, F = problem
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=15, hp=SplitHyperparams(min_data_in_leaf=10),
                       num_bins=B, hist_method="scatter")
    ref_tree, ref_leaf = _single_device_tree(problem, cfg, meta)

    assert jax.device_count() >= 8, "conftest must provide 8 CPU devices"
    mesh = make_mesh(8, (DATA_AXIS,))
    grower = create_parallel_grower("data", mesh, meta, cfg)
    (b, g, h, m), n_pad = shard_dataset(
        mesh, binned, grad, hess, np.ones(len(grad), np.float32))
    tree, leaf_id = grower(b, g, h, m)

    assert int(tree.num_leaves) == int(ref_tree.num_leaves)
    nl = int(tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree.split_feature[:nl - 1]),
                                  np.asarray(ref_tree.split_feature[:nl - 1]))
    np.testing.assert_array_equal(np.asarray(tree.threshold_bin[:nl - 1]),
                                  np.asarray(ref_tree.threshold_bin[:nl - 1]))
    np.testing.assert_allclose(np.asarray(tree.leaf_value[:nl]),
                               np.asarray(ref_tree.leaf_value[:nl]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(leaf_id)[:len(ref_leaf)], ref_leaf)


def test_feature_parallel_matches_serial(problem):
    binned, grad, hess, B, F = problem
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=15, hp=SplitHyperparams(min_data_in_leaf=10),
                       num_bins=B, hist_method="scatter")
    ref_tree, ref_leaf = _single_device_tree(problem, cfg, meta)

    mesh = make_mesh(8, (FEATURE_AXIS,))
    grower = create_parallel_grower("feature", mesh, meta, cfg)
    tree, leaf_id = grower(jnp.asarray(binned.T), jnp.asarray(grad),
                           jnp.asarray(hess),
                           jnp.ones(len(grad), jnp.float32))
    assert int(tree.num_leaves) == int(ref_tree.num_leaves)
    nl = int(tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree.split_feature[:nl - 1]),
                                  np.asarray(ref_tree.split_feature[:nl - 1]))
    np.testing.assert_allclose(np.asarray(tree.leaf_value[:nl]),
                               np.asarray(ref_tree.leaf_value[:nl]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(leaf_id), ref_leaf)


def test_2d_mesh_matches_serial(problem):
    binned, grad, hess, B, F = problem
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=7, hp=SplitHyperparams(min_data_in_leaf=10),
                       num_bins=B, hist_method="scatter")
    ref_tree, _ = _single_device_tree(problem, cfg, meta)

    mesh = make_mesh(8, (DATA_AXIS, FEATURE_AXIS), shape=(4, 2))
    grower = create_parallel_grower("data_feature", mesh, meta, cfg)
    from jax.sharding import NamedSharding, PartitionSpec as P
    b = jax.device_put(np.ascontiguousarray(binned.T),
                       NamedSharding(mesh, P(FEATURE_AXIS, DATA_AXIS)))
    g = jax.device_put(grad, NamedSharding(mesh, P(DATA_AXIS)))
    h = jax.device_put(hess, NamedSharding(mesh, P(DATA_AXIS)))
    m = jax.device_put(np.ones(len(grad), np.float32),
                       NamedSharding(mesh, P(DATA_AXIS)))
    tree, _ = grower(b, g, h, m)
    assert int(tree.num_leaves) == int(ref_tree.num_leaves)
    nl = int(tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree.split_feature[:nl - 1]),
                                  np.asarray(ref_tree.split_feature[:nl - 1]))


# ---------------------------------------------------------------------------
# e2e: tree_learner=data|feature wired through GBDT/engine.train
# (reference dispatch: GBDT::Init -> CreateTreeLearner, gbdt.cpp:79)

def _binary_xy():
    train = example_data.binary()[0]
    return train.X, train.y


def test_engine_data_parallel_end_to_end():
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "metric": "auc", "verbosity": -1,
            "num_leaves": 15, "min_data_in_leaf": 20}
    ev_s, ev_d = {}, {}

    def run(tl, ev):
        params = dict(base, tree_learner=tl)
        train = lgb.Dataset(X, label=y)
        return lgb.train(params, train, num_boost_round=10,
                         valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                         evals_result=ev, verbose_eval=False)

    bst_s = run("serial", ev_s)
    bst_d = run("data", ev_d)
    assert bst_d.boosting._mesh is not None, "tree_learner=data must shard"
    assert bst_d.boosting._n_pad % 8 == 0
    # identical tree structure (gains are well separated on this data; the
    # only fp difference is psum order inside histogram bins)
    for ms, md in zip(bst_s.boosting.models, bst_d.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, md.split_feature)
        np.testing.assert_array_equal(ms.threshold_in_bin, md.threshold_in_bin)
    np.testing.assert_allclose(bst_s.predict(X), bst_d.predict(X),
                               rtol=1e-4, atol=1e-5)
    assert abs(ev_s["valid_0"]["auc"][-1] - ev_d["valid_0"]["auc"][-1]) < 1e-3


def test_engine_feature_parallel_end_to_end():
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "metric": "auc", "verbosity": -1,
            "num_leaves": 15, "min_data_in_leaf": 20,
            "enable_bundle": False}

    def run(tl):
        params = dict(base, tree_learner=tl)
        return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)

    bst_s = run("serial")
    bst_f = run("feature")
    assert bst_f.boosting._mesh is not None
    for ms, mf in zip(bst_s.boosting.models, bst_f.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, mf.split_feature)
        np.testing.assert_array_equal(ms.threshold_in_bin, mf.threshold_in_bin)
    np.testing.assert_allclose(bst_s.predict(X), bst_f.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_engine_feature_parallel_with_efb_matches_serial():
    """Feature sharding composes with EFB by partitioning whole BUNDLES
    (reference partitions features after bundling,
    feature_parallel_tree_learner.cpp:33-52): sparse one-hot-ish columns
    bundle into shared group columns, groups are packed shard-major, and
    the result must match serial training exactly."""
    rng = np.random.RandomState(0)
    n = 500
    groups = rng.randint(0, 8, size=n)
    X = np.zeros((n, 8), np.float32)
    X[np.arange(n), groups] = rng.rand(n) + 0.5
    X = np.concatenate([X, rng.rand(n, 4).astype(np.float32)], axis=1)
    y = ((groups % 2) ^ (X[:, 8] > 0.5)).astype(np.float32)
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    assert ds.feature_meta().resolved().has_bundles, "test premise: EFB fires"
    base = {"objective": "binary", "verbosity": -1, "min_data_in_leaf": 5,
            "num_leaves": 15}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=6)
    bst_f = lgb.train(dict(base, tree_learner="feature"),
                      lgb.Dataset(X, label=y), num_boost_round=6)
    assert bst_f.boosting._mesh is not None
    assert bst_f.boosting._feat_perm is not None, "EFB shard layout in use"
    for ms, mf in zip(bst_s.boosting.models, bst_f.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, mf.split_feature)
        np.testing.assert_array_equal(ms.threshold_in_bin, mf.threshold_in_bin)
        np.testing.assert_allclose(ms.leaf_value, mf.leaf_value,
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(bst_s.predict(X), bst_f.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_engine_data_parallel_bagging_goss_l1():
    """Distributed modes compose with bagging masks, GOSS and L1 renewal."""
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    cases = [
        {"objective": "binary", "bagging_freq": 1, "bagging_fraction": 0.7},
        {"objective": "binary", "boosting": "goss"},
        {"objective": "regression_l1"},
    ]
    for extra in cases:
        params = dict({"metric": "None", "verbosity": -1, "num_leaves": 7,
                       "min_data_in_leaf": 20, "tree_learner": "data"}, **extra)
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
        p = bst.predict(X)
        assert np.isfinite(p).all()
        assert bst.boosting.num_trees() == 5


# ---------------------------------------------------------------------------
# voting-parallel (PV-Tree, reference voting_parallel_tree_learner.cpp)

def test_engine_voting_parallel_matches_serial_at_full_topk():
    # top_k >= num_features: the election keeps every feature, so voting
    # must agree with serial exactly (module histogram psum fp order)
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20}

    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=8)
    bst_v = lgb.train(dict(base, tree_learner="voting", top_k=X.shape[1]),
                      lgb.Dataset(X, label=y), num_boost_round=8)
    assert bst_v.boosting._mesh is not None
    assert bst_v.boosting.grower_cfg.voting_top_k == X.shape[1]
    for ms, mv in zip(bst_s.boosting.models, bst_v.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, mv.split_feature)
        np.testing.assert_array_equal(ms.threshold_in_bin, mv.threshold_in_bin)
    np.testing.assert_allclose(bst_s.predict(X), bst_v.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_engine_voting_parallel_small_topk_trains():
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    evals = {}
    train = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "metric": "auc", "verbosity": -1,
                     "num_leaves": 15, "min_data_in_leaf": 20,
                     "tree_learner": "voting", "top_k": 5},
                    train, num_boost_round=10,
                    valid_sets=[lgb.Dataset(X, label=y, reference=train)],
                    evals_result=evals, verbose_eval=False)
    # approximate mode must still learn (reference PV-Tree claim);
    # on example_data.binary()'s train split serial at this config
    # measures 0.8452, voting top_k=5 0.8457
    assert evals["valid_0"]["auc"][-1] > 0.83


def _allreduce_f32_elems(hlo_text):
    """Sum of f32 element counts over all all-reduce ops in an HLO dump."""
    import re
    total = 0
    for m in re.finditer(r"f32\[([0-9,]*)\][^=]*all-reduce", hlo_text):
        dims = m.group(1)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def test_voting_parallel_reduces_histogram_traffic(problem):
    """The vote exchanges [top_k, B, 3] histograms instead of [F, B, 3]."""
    import functools
    binned, grad, hess, B, F = problem
    meta = _meta(B, F)
    mesh = make_mesh(8, (DATA_AXIS,))

    def lower(cfg):
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(None, DATA_AXIS),)
            + (jax.sharding.PartitionSpec(DATA_AXIS),) * 3,
            out_specs=(jax.sharding.PartitionSpec(),
                       jax.sharding.PartitionSpec(DATA_AXIS)),
            check_vma=False)
        def step(b, g, h, m):
            return grow_tree(b, g, h, m, meta, cfg, axis_name=DATA_AXIS)
        (b,), _ = shard_dataset(mesh, binned)
        args, _ = shard_dataset(mesh, binned, grad, hess,
                                np.ones(len(grad), np.float32))
        return jax.jit(step).lower(*args).compile().as_text()

    hp = SplitHyperparams(min_data_in_leaf=10)
    data_cfg = GrowerConfig(num_leaves=7, hp=hp, num_bins=B,
                            hist_method="scatter")
    vote_cfg = GrowerConfig(num_leaves=7, hp=hp, num_bins=B,
                            hist_method="scatter", voting_top_k=2,
                            num_machines=8)
    data_traffic = _allreduce_f32_elems(lower(data_cfg))
    vote_traffic = _allreduce_f32_elems(lower(vote_cfg))
    assert vote_traffic < data_traffic, (vote_traffic, data_traffic)


def test_engine_feature_parallel_monotone_matches_serial():
    # regression guard: bound propagation must index constraints by GLOBAL
    # feature id even when the scan slices them per feature shard
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20, "enable_bundle": False,
            "monotone_constraints": [1, -1] * 14}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=6)
    bst_f = lgb.train(dict(base, tree_learner="feature"),
                      lgb.Dataset(X, label=y), num_boost_round=6)
    for ms, mf in zip(bst_s.boosting.models, bst_f.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, mf.split_feature)
        np.testing.assert_allclose(ms.leaf_value, mf.leaf_value,
                                   rtol=1e-4, atol=1e-6)


def _ranking_xy(n_queries=60, seed=7):
    """Synthetic LTR data: queries of varying size with graded labels."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(5, 40, n_queries)
    Xs, ys, group = [], [], []
    for s in sizes:
        Xq = rng.rand(s, 6)
        rel = (2.0 * Xq[:, 0] + Xq[:, 1] + 0.3 * rng.randn(s))
        yq = np.clip(np.digitize(rel, [0.8, 1.5, 2.2]), 0, 3)
        Xs.append(Xq)
        ys.append(yq)
        group.append(s)
    return (np.concatenate(Xs), np.concatenate(ys).astype(np.float64),
            np.asarray(group, np.int64))


@pytest.mark.parametrize("objective", ["lambdarank", "rank_xendcg"])
def test_engine_data_parallel_ranking_matches_serial(objective):
    """Distributed ranking via query-aligned row sharding: whole queries
    per shard, per-query lambdas shard-local by construction (reference:
    Metadata::CheckOrPartition partitions at query boundaries,
    src/io/metadata.cpp:141)."""
    import lightgbm_tpu as lgb
    X, y, group = _ranking_xy()
    base = {"objective": objective, "metric": "ndcg", "ndcg_eval_at": [5],
            "verbosity": -1, "num_leaves": 15, "min_data_in_leaf": 10,
            "objective_seed": 11}
    ev_s, ev_d = {}, {}

    def run(tl, ev):
        params = dict(base, tree_learner=tl)
        train = lgb.Dataset(X, label=y, group=group)
        valid = lgb.Dataset(X, label=y, group=group, reference=train)
        return lgb.train(params, train, num_boost_round=8,
                         valid_sets=[valid], evals_result=ev,
                         verbose_eval=False)

    bst_s = run("serial", ev_s)
    bst_d = run("data", ev_d)
    assert bst_d.boosting._mesh is not None, "tree_learner=data must shard"
    assert bst_d.boosting._row_perm is not None, "query-aligned layout"
    # no query may straddle a shard boundary
    perm = bst_d.boosting._row_perm
    n = len(y)
    n_shard = len(perm) // 8
    qb = np.concatenate([[0], np.cumsum(group)])
    starts = {int(s): i for i, s in enumerate(qb[:-1])}
    for d in range(8):
        chunk = perm[d * n_shard:(d + 1) * n_shard]
        rows = chunk[chunk < n]
        # rows of one shard = union of complete queries
        covered = 0
        while covered < len(rows):
            q = starts[int(rows[covered])]
            covered += int(qb[q + 1] - qb[q])
        assert covered == len(rows)
    for ms, md in zip(bst_s.boosting.models, bst_d.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, md.split_feature)
        np.testing.assert_array_equal(ms.threshold_in_bin, md.threshold_in_bin)
    np.testing.assert_allclose(bst_s.predict(X), bst_d.predict(X),
                               rtol=1e-4, atol=1e-5)
    assert abs(ev_s["valid_0"]["ndcg@5"][-1]
               - ev_d["valid_0"]["ndcg@5"][-1]) < 1e-3


def test_network_machine_list_mapping():
    """Reference machine-list configs map onto jax.distributed wiring
    (parallel/network.py; reference linkers_socket.cpp:23-76)."""
    import socket
    from lightgbm_tpu.parallel.network import (init_network,
                                               parse_machine_list,
                                               resolve_rank)
    ml = parse_machine_list("10.0.0.1:12400,10.0.0.2:12401")
    assert ml == [("10.0.0.1", 12400), ("10.0.0.2", 12401)]
    host = socket.gethostname()
    ml2 = parse_machine_list(f"10.0.0.1:12400,{host}:12401")
    assert resolve_rank(ml2) == 1
    out = init_network(machines=f"10.0.0.1:12400,{host}:12401",
                       num_machines=2, dry_run=True)
    assert out == ("10.0.0.1:12400", 2, 1)
    # multi-process-per-host: port disambiguates
    ml3 = parse_machine_list(f"{host}:12400,{host}:12401")
    assert resolve_rank(ml3, local_listen_port=12401) == 1
    import pytest
    with pytest.raises(ValueError):
        resolve_rank([("10.9.9.9", 1)])


# ---------------------------------------------------------------------------
# learner-combination matrix: CEGB and forced splits compose with the
# distributed learners (the reference wires both through SerialTreeLearner
# hooks shared by every learner, serial_tree_learner.cpp:65-68,411-521,
# 529-532; here the sharded growers must match serial exactly)

def _struct_match(a, b):
    assert len(a.boosting.models) == len(b.boosting.models)
    for ms, mf in zip(a.boosting.models, b.boosting.models):
        np.testing.assert_array_equal(ms.split_feature, mf.split_feature)
        np.testing.assert_array_equal(ms.threshold_in_bin,
                                      mf.threshold_in_bin)


def test_cegb_feature_parallel_matches_serial():
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20, "enable_bundle": False,
            "cegb_penalty_split": 0.002,
            "cegb_penalty_feature_coupled": [0.3] * X.shape[1]}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=6)
    bst_f = lgb.train(dict(base, tree_learner="feature"),
                      lgb.Dataset(X, label=y), num_boost_round=6)
    assert bst_f.boosting._mesh is not None
    # the penalties actually bit: the CEGB model must differ from plain
    plain = lgb.train({k: v for k, v in base.items()
                       if not k.startswith("cegb")},
                      lgb.Dataset(X, label=y), num_boost_round=6)
    assert not np.allclose(plain.predict(X), bst_s.predict(X)), \
        "test premise: CEGB penalties changed the model"
    _struct_match(bst_s, bst_f)
    np.testing.assert_allclose(bst_s.predict(X), bst_f.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_cegb_lazy_feature_parallel_matches_serial():
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20, "enable_bundle": False,
            "cegb_penalty_feature_lazy": [0.004] * X.shape[1]}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    bst_f = lgb.train(dict(base, tree_learner="feature"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    _struct_match(bst_s, bst_f)
    np.testing.assert_allclose(bst_s.predict(X), bst_f.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_cegb_feature_parallel_with_efb_matches_serial():
    """CEGB under the sharded-EFB layout: penalties/used-state ride in
    device-slot order (padded, permuted) and must still match serial."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    n = 500
    groups = rng.randint(0, 8, size=n)
    X = np.zeros((n, 8), np.float32)
    X[np.arange(n), groups] = rng.rand(n) + 0.5
    X = np.concatenate([X, rng.rand(n, 4).astype(np.float32)], axis=1)
    y = ((groups % 2) ^ (X[:, 8] > 0.5)).astype(np.float32)
    base = {"objective": "binary", "verbosity": -1, "min_data_in_leaf": 5,
            "num_leaves": 15,
            "cegb_penalty_feature_coupled": [0.2] * X.shape[1]}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    bst_f = lgb.train(dict(base, tree_learner="feature"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    assert bst_f.boosting._feat_perm is not None, "EFB shard layout in use"
    _struct_match(bst_s, bst_f)
    np.testing.assert_allclose(bst_s.predict(X), bst_f.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_cegb_data_parallel_matches_serial():
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20, "cegb_penalty_split": 0.002,
            "cegb_penalty_feature_lazy": [0.002] * X.shape[1]}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    bst_d = lgb.train(dict(base, tree_learner="data"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    _struct_match(bst_s, bst_d)
    np.testing.assert_allclose(bst_s.predict(X), bst_d.predict(X),
                               rtol=1e-4, atol=1e-5)


def _forced_json(tmp_path, spec):
    import json
    import os
    fn = os.path.join(str(tmp_path), "forced.json")
    with open(fn, "w") as f:
        json.dump(spec, f)
    return fn


def test_forced_splits_feature_parallel_matches_serial(tmp_path):
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    fn = _forced_json(tmp_path, {
        "feature": 3, "threshold": 0.5,
        "left": {"feature": 1, "threshold": 0.4}})
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20, "enable_bundle": False,
            "forcedsplits_filename": fn}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    bst_f = lgb.train(dict(base, tree_learner="feature"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    # forced structure honored: root split on feature 3
    for m in bst_s.boosting.models:
        assert int(m.split_feature[0]) == 3
    _struct_match(bst_s, bst_f)
    np.testing.assert_allclose(bst_s.predict(X), bst_f.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_forced_splits_voting_parallel_matches_serial(tmp_path):
    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    fn = _forced_json(tmp_path, {"feature": 2, "threshold": 0.6})
    base = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
            "min_data_in_leaf": 20, "forcedsplits_filename": fn}
    bst_s = lgb.train(dict(base, tree_learner="serial"),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    bst_v = lgb.train(dict(base, tree_learner="voting", top_k=X.shape[1]),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    assert bst_v.boosting.grower_cfg.voting_top_k == X.shape[1]
    for m in bst_v.boosting.models:
        assert int(m.split_feature[0]) == 2
    _struct_match(bst_s, bst_v)
    np.testing.assert_allclose(bst_s.predict(X), bst_v.predict(X),
                               rtol=1e-4, atol=1e-5)


def test_cegb_voting_raises_with_rationale():
    """CEGB x voting is a recorded design exclusion (exact CEGB needs the
    global per-feature candidates voting exists to avoid building)."""
    import pytest

    import lightgbm_tpu as lgb
    X, y = _binary_xy()
    with pytest.raises(NotImplementedError, match="tree_learner=data"):
        lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7,
                   "tree_learner": "voting", "top_k": 3,
                   "cegb_penalty_split": 0.01},
                  lgb.Dataset(X, label=y), num_boost_round=1)
