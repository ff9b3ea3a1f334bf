"""Traversal-kernel parity matrix + predict planner election.

The three traversal programs (while / fori / fused,
ops/predict_kernels.py) share ONE decision-step expression, so their
leaf indices must be BIT-identical across every precision, missing
type, categorical bitset, multiclass layout and ragged last tile — the
invariant the whole inference-kernel election rests on.  The serving
epilogue probe (device f32 leaf sum vs host f64 gather) promotes and
demotes per forest; both directions keep ``predict_raw_padded``
bit-equal to the host path.
"""

import copy
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.fleet.lowprec import quantize_forest
from lightgbm_tpu.ops import planner as P
from lightgbm_tpu.predict import DeviceForest, gather_leaf_sum

VARIANTS = ("while", "fori", "fused")
# not a multiple of any fused tile rung -> the last tile is ragged
EVAL_ROWS = 700
TILE = 128


def _train(X, y, num_class=1, categorical=None, rounds=8, leaves=7,
           **extra):
    params = {"objective": "binary", "verbosity": -1, "num_leaves": leaves,
              "min_data_in_leaf": 5}
    if num_class > 1:
        params.update(objective="multiclass", num_class=num_class)
    params.update(extra)
    ds = lgb.Dataset(X, label=y, categorical_feature=categorical or "auto")
    return lgb.train(params, ds, num_boost_round=rounds, verbose_eval=False)


def _forest(bst):
    return bst._forest(0, len(bst.models) // bst.num_tree_per_iteration)


def _salted(X):
    """Eval batch with the routing edge cases planted in known rows."""
    Xs = np.array(X[:EVAL_ROWS], np.float64)
    Xs[0, :] = 0.0
    Xs[1, :] = np.nan
    Xs[2, :] = -1e30
    Xs[3, :] = 1e30
    return Xs


@pytest.fixture(scope="module")
def models():
    """One booster per routing regime: categorical + NaN-missing,
    zero-as-missing, no-missing, multiclass."""
    rng = np.random.RandomState(7)
    n = 1500
    out = {}

    cat = rng.randint(0, 12, n).astype(np.float64)
    dense = rng.randn(n)
    dense[rng.rand(n) < 0.2] = np.nan
    X = np.column_stack([cat, dense, rng.randn(n)])
    y = (np.isin(cat, [1, 4, 9]) | (np.nan_to_num(dense) > 0.7)
         ).astype(float)
    out["cat_nan"] = (_train(X, y, categorical=[0]), X)

    Xz = rng.randn(n, 4)
    Xz[rng.rand(n, 4) < 0.3] = 0.0
    yz = (Xz[:, 0] + Xz[:, 2] > 0).astype(float)
    out["zero_missing"] = (_train(Xz, yz, zero_as_missing=True), Xz)

    Xc = rng.rand(n, 4) + 0.5          # strictly positive, nothing missing
    yc = (Xc[:, 0] * Xc[:, 1] > Xc[:, 2]).astype(float)
    out["none_missing"] = (_train(Xc, yc), Xc)

    Xm = rng.randn(n, 5)
    ym = rng.randint(0, 3, n).astype(float)
    out["multiclass"] = (_train(Xm, ym, num_class=3, rounds=5), Xm)
    return out


def _leaf_matrix(forest, Xs, precision):
    """Leaf indices per variant at one precision; dict variant->array."""
    f = quantize_forest(forest, precision) if precision != "f32" else forest
    import jax.numpy as jnp
    X32 = jnp.asarray(np.asarray(Xs, np.float32))
    out = {}
    for v in VARIANTS:
        dev = DeviceForest(f, precision=precision, variant=v,
                           tile_rows=TILE)
        out[v] = np.asarray(dev._leaves_jit(X32))
    return out


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize(
    "case", ["cat_nan", "zero_missing", "none_missing", "multiclass"])
def test_variant_parity_matrix(models, case, precision):
    bst, X = models[case]
    leaves = _leaf_matrix(_forest(bst), _salted(X), precision)
    for v in ("fori", "fused"):
        assert np.array_equal(leaves["while"], leaves[v]), (
            f"{case}/{precision}: {v} leaf indices diverge from while")


def test_fused_ragged_last_tile(models):
    """Rows that do not divide the tile exercise the pad-and-slice arm:
    every ragged width must match the while baseline bit-for-bit."""
    bst, X = models["cat_nan"]
    forest = _forest(bst)
    import jax.numpy as jnp
    from lightgbm_tpu.ops import predict_kernels as PK
    dev = DeviceForest(forest, variant="while", tile_rows=TILE)
    for rows in (1, TILE - 1, TILE, TILE + 1, 2 * TILE + 17):
        X32 = jnp.asarray(np.asarray(_salted(X)[:rows], np.float32))
        ref = np.asarray(PK.leaves_while(dev, X32))
        got = np.asarray(PK.fused_traverse(dev, X32, TILE))
        assert got.shape == ref.shape == (forest.num_trees, rows)
        assert np.array_equal(ref, got), f"ragged rows={rows} diverged"


def test_serving_parity_all_variants(models):
    """predict_raw_padded (the serving entry point) is bit-equal to
    Booster.predict(raw_score=True) whatever variant routes the rows."""
    bst, X = models["cat_nan"]
    forest = _forest(bst)
    ref = bst.predict(X[:EVAL_ROWS], raw_score=True)
    for v in VARIANTS:
        dev = DeviceForest(forest, variant=v, tile_rows=TILE)
        raw = dev.predict_raw_padded(X[:EVAL_ROWS])[0]
        assert np.array_equal(raw, ref), f"variant {v} broke serving parity"


def test_serving_parity_multiclass(models):
    bst, X = models["multiclass"]
    forest = _forest(bst)
    K = bst.num_tree_per_iteration
    ref = bst.predict(X[:EVAL_ROWS], raw_score=True).T      # [K, n]
    dev = DeviceForest(forest, variant="fori")
    raw = dev.predict_raw_padded(X[:EVAL_ROWS], num_class=K)
    assert np.array_equal(raw, ref)


# ----------------------------------------------------------------------
# epilogue probe: promotion, demotion, env pin
# ----------------------------------------------------------------------


def _with_leaves(forest, leaf_value):
    f = copy.copy(forest)
    f.leaf_value = np.asarray(leaf_value, np.float64)
    return f


def test_epilogue_promotes_integer_leaves(models):
    """Integer-valued leaves sum exactly in f32 -> the device epilogue
    passes the bit-exactness probe and predict_raw_padded's output is
    STILL bit-equal to the host f64 gather."""
    bst, X = models["none_missing"]
    forest = _forest(bst)
    f = _with_leaves(forest, np.round(forest.leaf_value * 50))
    dev = DeviceForest(f, variant="fori")
    assert dev._epilogue_verified(1)
    Xs = np.asarray(X[:333], np.float64)
    raw = dev.predict_raw_padded(Xs)
    import jax.numpy as jnp
    leaves = np.asarray(dev._leaves_jit(jnp.asarray(Xs, jnp.float32)))
    assert np.array_equal(raw, gather_leaf_sum(f, leaves, 1))


def test_epilogue_demotes_on_f32_rounding(models):
    """Leaf values spanning 1e8 vs 1.0 make f32 sums drop the low bits;
    the probe must demote to the host path — and the serving output must
    still be the f64 host gather bit-for-bit."""
    bst, X = models["none_missing"]
    forest = _forest(bst)
    lv = np.ones_like(forest.leaf_value)
    lv[0, :] = 1e8
    f = _with_leaves(forest, lv)
    dev = DeviceForest(f, variant="fori")
    assert not dev._epilogue_verified(1)
    Xs = np.asarray(X[:128], np.float64)
    raw = dev.predict_raw_padded(Xs)
    import jax.numpy as jnp
    leaves = np.asarray(dev._leaves_jit(jnp.asarray(Xs, jnp.float32)))
    assert np.array_equal(raw, gather_leaf_sum(f, leaves, 1))


def test_epilogue_env_pin(models, monkeypatch):
    """LGBM_TPU_PREDICT_EPILOGUE=0 pins the host path even for a forest
    the probe would promote."""
    bst, _ = models["none_missing"]
    forest = _forest(bst)
    f = _with_leaves(forest, np.round(forest.leaf_value * 50))
    monkeypatch.setenv("LGBM_TPU_PREDICT_EPILOGUE", "0")
    dev = DeviceForest(f, variant="fori")
    assert not dev._epilogue_verified(1)


# ----------------------------------------------------------------------
# planner election: env gates, byte models
# ----------------------------------------------------------------------


def test_kernel_env_override(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PREDICT_KERNEL", "while")
    plan = P.plan_predict(num_trees=8, nodes_dim=7, leaves_dim=8,
                          features=4, rows=1000)
    assert plan.variant == "while" and plan.elected_by == "env"
    monkeypatch.setenv("LGBM_TPU_PREDICT_KERNEL", "bogus")
    plan = P.plan_predict(num_trees=8, nodes_dim=7, leaves_dim=8,
                          features=4, rows=1000)
    assert plan.elected_by != "env"        # unknown names are ignored


def test_chunk_env_override(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PREDICT_CHUNK", "4096")
    assert P.elect_predict_chunk(8, 7, 8, 4) == 4096
    assert P.elect_csr_chunk(4) == 4096
    monkeypatch.delenv("LGBM_TPU_PREDICT_CHUNK")
    assert P.elect_predict_chunk(8, 7, 8, 4) >= P.MIN_BUCKET_ROWS


def test_chunk_respects_budget():
    """A starved budget pins the chunk at the ladder floor; a generous
    one climbs it (never past MAX_PREDICT_CHUNK_ROWS)."""
    small = P.elect_predict_chunk(64, 255, 256, 32, budget=1 << 20)
    big = P.elect_predict_chunk(64, 255, 256, 32, budget=1 << 40)
    assert small == P.MIN_BUCKET_ROWS
    assert small <= big <= P.MAX_PREDICT_CHUNK_ROWS


def test_fused_tile_ladder_fits_or_none():
    got = P.plan_predict_fused_tile(8, 7, 4, vmem_bytes=1 << 30)
    assert got is not None and got["tile_rows"] == P.FUSED_PREDICT_TILES[0]
    assert P.plan_predict_fused_tile(4000, 2047, 256, vmem_bytes=1 << 16) \
        is None


def test_deviceforest_chunk_shrinks_to_batch(models):
    """Small batches never pad out to the elected chunk ceiling."""
    bst, _ = models["none_missing"]
    dev = DeviceForest(_forest(bst), variant="fori")
    assert dev._call_chunk(10) <= P.bucket_rows(10)
    assert dev._call_chunk(10 ** 9) == dev.chunk_rows
