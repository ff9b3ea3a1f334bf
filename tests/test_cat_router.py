"""Categorical candidates on the rounds grower's router form.

The router form hands every row its candidate's parameters by one table
matmul and decides elementwise; a categorical candidate's set rides that
table as 16-bit halves (``grower.bitset_halves`` / ``halves_hold``).  The
candidate scan (one pass over the rows a candidate, ``row_goes_left`` with
the set as it is) stays as the oracle: the two forms must grow the same
trees, leaf ids and scores bit for bit.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.grower import bitset_halves, halves_hold, row_goes_left
from lightgbm_tpu.grower_rounds import router_engages
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
def test_halves_hold_is_the_bitset_test(bins):
    """Every bin against random sets: the halves' test equals
    ``row_goes_left``'s on the words themselves."""
    rng = np.random.default_rng(bins)
    sets = rng.integers(0, 2 ** 32, (50, MAX_CAT_WORDS), dtype=np.uint64
                        ).astype(np.uint32)
    halves = 2 * (bins // 32)
    col = jnp.arange(bins, dtype=jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    for words in sets:
        want = row_goes_left(col, zero, False, True, jnp.asarray(words),
                             zero, zero, jnp.int32(bins))
        rows = bitset_halves(jnp.asarray(words)[None, :], halves)[0]
        per_row = jnp.broadcast_to(rows[:, None], (halves, bins))
        got = halves_hold(per_row, col)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.max(bitset_halves(jnp.asarray(sets), halves))) < 65536
