"""Categorical candidates on the rounds grower's router form.

The router form decides rows a block at a time against the round's lanes
(``grower_rounds.route_lanes``); a categorical candidate's set rides the
lane's bytes (``set_bytes_hold``).  The
candidate scan (one pass over the rows a candidate, ``row_goes_left`` with
the set as it is) stays as the oracle: the two forms must grow the same
trees, leaf ids and scores bit for bit.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.grower import row_goes_left
from lightgbm_tpu.grower_rounds import router_engages, set_bytes_hold
from lightgbm_tpu.ops.split import MAX_CAT_WORDS


def cat_data(seed=3, n=6000):
    """Numeric columns, a 3-code and a 4-code column (one-hot mode), a
    12-code and a 40-code column (many-vs-many), and a 5,000-code id column
    (binned to its most frequent codes); NaN in one column of each kind, and
    negative and never-sampled codes in the id column."""
    rng = np.random.default_rng(seed)
    num = rng.random((n, 3)).astype(np.float32)
    num[rng.random(n) < 0.05, 1] = np.nan
    c3 = rng.integers(0, 3, n)
    c4 = rng.integers(0, 4, n)
    c12 = rng.integers(0, 12, n)
    c40 = np.floor(41 ** rng.random(n)).astype(np.int64) - 1
    ids = np.floor(5001 ** rng.random(n)).astype(np.int64) - 1
    e12 = rng.normal(size=12)
    e40 = rng.normal(size=40)
    signal = (num[:, 0] + 0.8 * (c3 == 1) - 0.6 * (c4 == 2) + 0.7 * e12[c12]
              + 0.7 * e40[c40] + 0.5 * (ids < 3) + 0.2 * rng.normal(size=n))
    X = np.column_stack([num, c3, c4, c12, c40, ids]).astype(np.float32)
    X[rng.random(n) < 0.04, 5] = np.nan
    X[rng.random(n) < 0.01, 7] = -3.0
    y = (signal > np.quantile(signal, 0.6)).astype(np.float32)
    return X, y, [3, 4, 5, 6, 7]


MODES = {
    "f32": {},
    "int8": {"use_quantized_grad": True, "num_grad_quant_bins": 4},
    "int8_renew": {"use_quantized_grad": True, "num_grad_quant_bins": 4,
                   "quant_train_renew_leaf": True},
    "f32_wide_bins": {"max_bin": 255},
    # the fused arm: rounds at 16 lanes and at the cap (23 of 24 leaves),
    # the route in the branch of the pass's width; categorical lanes at
    # 63 and at 255 bins (two words of a set, and eight)
    "int8_fused": {"use_quantized_grad": True, "num_grad_quant_bins": 4,
                   "tpu_hist_method": "fused"},
    "int8_fused_wide_bins": {"use_quantized_grad": True,
                             "num_grad_quant_bins": 4,
                             "tpu_hist_method": "fused", "max_bin": 255},
}


def grow(monkeypatch, router, mode, rounds=3):
    monkeypatch.setenv("LGBM_TPU_SEGHIST", "sorted")
    monkeypatch.setenv("LGBM_TPU_ROUTER", "1" if router else "0")
    assert router_engages() is router
    X, y, cats = cat_data()
    params = {"objective": "binary", "num_leaves": 24, "max_bin": 63,
              "min_data_in_leaf": 5, "min_data_per_group": 20,
              "cat_smooth": 5.0, "tpu_tree_growth": "rounds",
              "verbosity": -1, **MODES[mode]}
    bst = lgb.train(params, lgb.Dataset(X, label=y, categorical_feature=cats),
                    num_boost_round=rounds)
    # rows the training sample never saw: other ids, negative codes, NaN
    probe = X.copy()
    probe[::3, 7] += 4000.0
    probe[1::5, 6] = np.nan
    return (bst.model_to_string(), np.asarray(bst.boosting.train_score),
            bst.predict(probe, pred_leaf=True), bst)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_router_form_grows_the_scans_trees(monkeypatch, mode):
    text_s, score_s, leaves_s, _ = grow(monkeypatch, False, mode)
    text_r, score_r, leaves_r, bst = grow(monkeypatch, True, mode)
    assert text_r == text_s
    assert np.array_equal(score_r, score_s)
    assert np.array_equal(leaves_r, leaves_s)
    kinds = [int(d) & 1 for m in bst.models for d in m.decision_type]
    assert 0 < sum(kinds) < len(kinds)          # both kinds of split taken
    one_hot = [m for m in bst.models for f, d in
               zip(m.split_feature, m.decision_type)
               if int(d) & 1 and f in (3, 4)]
    many = [m for m in bst.models for f, d in
            zip(m.split_feature, m.decision_type)
            if int(d) & 1 and f in (5, 6, 7)]
    assert one_hot and many


def test_router_rounds_are_counted(monkeypatch):
    from lightgbm_tpu.obs.metrics import global_registry

    def counters():
        c = global_registry.to_dict().get("counters", {})
        return (c.get("grower_rounds_routed_total", 0),
                c.get("grower_rounds_scanned_total", 0))
    r0, s0 = counters()
    grow(monkeypatch, True, "f32", rounds=2)
    r1, s1 = counters()
    grow(monkeypatch, False, "f32", rounds=2)
    r2, s2 = counters()
    assert r1 > r0 and s1 == s0
    assert r2 == r1 and s2 > s1


@pytest.mark.parametrize("bins", [64, 256])
def test_set_bytes_hold_is_the_bitset_test(bins):
    """Every bin against random sets: the bytes' test equals
    ``row_goes_left``'s on the words themselves."""
    rng = np.random.default_rng(bins)
    sets = rng.integers(0, 2 ** 32, (50, MAX_CAT_WORDS), dtype=np.uint64
                        ).astype(np.uint32)
    col = jnp.arange(bins, dtype=jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    for words in sets:
        want = row_goes_left(col, zero, False, True, jnp.asarray(words),
                             zero, zero, jnp.int32(bins))
        per_row = [jnp.full((bins,), (int(words[i // 4]) >> (8 * (i % 4)))
                            & 255, jnp.int32) for i in range(bins // 8)]
        got = set_bytes_hold(per_row, col)
        assert np.array_equal(np.asarray(got), np.asarray(want))


def _random_round(seed, n, F, bins, cat, W, k):
    """A random carry as a round sees it: rows in 255 leaves, every leaf
    with a cached split on a random feature (numeric, or categorical on
    the last half of the columns with ``cat``), every missing type with
    default bins, the round's candidates in a random order, ``k`` live."""
    from lightgbm_tpu.binning import MissingType
    from lightgbm_tpu.grower import _LeafBest
    rng = np.random.default_rng(seed)
    L = 255
    is_cat = (np.arange(F) >= F // 2) if cat else np.zeros(F, bool)
    num_bin = np.where(is_cat, bins, bins - 1).astype(np.int32)
    binned = rng.integers(0, bins, (F, n)).astype(
        np.uint8 if bins <= 256 else np.uint16)
    layout = tuple(jnp.asarray(a, jnp.int32) for a in (
        num_bin,
        rng.choice([int(MissingType.NONE), int(MissingType.ZERO),
                    int(MissingType.NAN)], F),
        rng.integers(0, bins - 1, F),
        np.arange(F), np.ones(F)))
    feature = rng.integers(0, F, L)
    best = _LeafBest.empty(L)._replace(
        feature=jnp.asarray(feature, jnp.int32),
        threshold=jnp.asarray(rng.integers(0, bins - 1, L), jnp.int32),
        default_left=jnp.asarray(rng.random(L) < 0.5),
        left_count=jnp.asarray(rng.integers(1, 9, L), jnp.float32),
        right_count=jnp.asarray(rng.integers(1, 9, L), jnp.float32),
        is_categorical=jnp.asarray(is_cat[feature]),
        cat_bitset=jnp.asarray(rng.integers(
            0, 2 ** 32, (L, MAX_CAT_WORDS), dtype=np.uint64
        ).astype(np.uint32)))
    idl = jnp.asarray(rng.permutation(L)[:128], jnp.int32)
    leaf_id = jnp.asarray(rng.integers(0, L, n), jnp.int32)
    return jnp.asarray(binned), leaf_id, idl, jnp.int32(k), best, layout


@pytest.mark.parametrize("cat", [False, True], ids=["numeric", "cat"])
@pytest.mark.parametrize("W,k", [(16, 16), (16, 5), (64, 40), (128, 97)])
def test_route_alone_is_the_scan(monkeypatch, W, k, cat):
    """``route_lanes`` on a random carry, alone: at W lanes of which k
    live (dead lanes where k < W), at the fused arm's three widths, in
    blocks that do not divide the rows, ``crank`` and
    ``slot`` equal the candidate scan's on every row, and ``gl`` (hence
    ``row_small``) on every row a live lane holds."""
    import lightgbm_tpu.grower_rounds as GR
    from lightgbm_tpu.ops.split import MAX_CAT_WORDS as MW
    monkeypatch.setattr(GR, "ROUTE_BLOCK", 777)
    bins = 64
    binned, leaf_id, idl, kk, best, layout = _random_round(
        W + k, 5000, 12, bins, cat, W, k)
    words = min(MW, -(-bins // 32)) if cat else 0
    crank_s, gl_s, slot_s = GR.route_scan(binned, leaf_id, idl, kk, best,
                                          128, layout, cat)
    crank_r, gl_r, slot_r = GR.route_lanes(binned, leaf_id, idl, kk, W,
                                           best, 128, layout, bins, words)
    crank_s, crank_r = np.asarray(crank_s), np.asarray(crank_r)
    assert np.array_equal(crank_r, crank_s)
    assert np.array_equal(np.asarray(slot_r), np.asarray(slot_s))
    live = crank_s < 128
    assert np.array_equal(np.asarray(gl_r)[live], np.asarray(gl_s)[live])
    small_s = np.asarray(slot_s)[live] < 128
    assert np.array_equal(np.asarray(slot_r)[live] < 128, small_s)
    # the carry exercises what it should: live and dead rows, both sides
    assert 0 < live.sum() < len(live)
    assert 0 < small_s.sum() < live.sum()
    assert set(np.unique(crank_s[live])) == set(range(k))
