"""LambdaRank on the device against the benchmark's plain reference
(``benchmark/objectives/lambdarank.py``, float64 NumPy, imports nothing of
the program): the pair set, every factor, the query tables and the inverse
slot map of ``objective_rank.py``, and three quantized, renewed rounds
followed by ``reference_gbdt.follow``."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import reference_gbdt  # noqa: E402
from benchmark.objectives import lambdarank as ref  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.dataset import Metadata  # noqa: E402
from lightgbm_tpu.objective_rank import LambdarankNDCG, _windows  # noqa: E402

# one document; one grade only; lengths on both sides of 8, 16, 32, 64, 128
# and 256 (the buckets' padded sizes); two queries without a relevant one
SIZES = np.array([1, 7, 8, 9, 15, 16, 17, 33, 40, 64, 65, 130, 5, 3, 260, 12])
# f32 pair sums against float64: a document's lambda adds up to 20 x 260
# pairs of either sign in f32 (relative rounding 6e-8 a term, the sum's
# error grows like its square root), and the reference works in float64
# throughout; measured 4e-8 to 3e-7 of the largest lambda
RTOL = 1e-5


def _labels(rng, sizes):
    y = rng.choice(5, int(sizes.sum()), p=[.7, .1, .1, .05, .05])
    starts = np.concatenate([[0], np.cumsum(sizes)])
    y[starts[1]:starts[2]] = 2          # one grade only
    y[starts[12]:starts[14]] = 0        # no relevant document
    return y.astype(np.float32)


def _objective(y, sizes, **params):
    obj = LambdarankNDCG(Config.from_params(
        {"objective": "lambdarank", "verbosity": -1, **params}))
    md = Metadata()
    md.label = y
    md.set_group(sizes)
    obj.init(md, len(y))
    return obj


@pytest.fixture(autouse=True)
def _reference_parameters():
    stated = dict(ref.PARAMS)
    yield
    ref.configure(**stated)


SCORES = {
    "zero": lambda rng, n: np.zeros(n, np.float32),
    "random": lambda rng, n: rng.normal(size=n).astype(np.float32),
    # many equal scores: ranks decided by row order, as a stable sort does
    "tied": lambda rng, n: np.round(rng.normal(size=n), 1).astype(np.float32),
}


@pytest.mark.parametrize("scores", sorted(SCORES))
@pytest.mark.parametrize("level", [20, 3])
@pytest.mark.parametrize("norm", [True, False])
def test_gradients_match_the_reference(norm, level, scores):
    rng = np.random.default_rng(7)
    y = _labels(rng, SIZES)
    obj = _objective(y, SIZES, lambdarank_norm=norm,
                     lambdarank_truncation_level=level)
    ref.configure(lambdarank_norm=norm, lambdarank_truncation_level=level)
    s = SCORES[scores](rng, len(y))
    g, h = jax.jit(obj.get_gradients)(jnp.asarray(s), obj.device_tables)
    g_ref, h_ref = ref.gradients(s.astype(np.float64), y, {"group": SIZES})
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=0,
                               atol=RTOL * np.abs(g_ref).max())
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=0,
                               atol=RTOL * np.abs(h_ref).max())
    # a query's lambdas cancel; one document or one grade gives none
    starts = np.concatenate([[0], np.cumsum(SIZES)])
    for q in (0, 1, 12, 13):
        assert not np.asarray(h)[starts[q]:starts[q + 1]].any()


def test_a_label_outside_label_gain_is_refused():
    with pytest.raises(ValueError, match="label_gain"):
        _objective(np.array([0, 5, 1], np.float32), np.array([3]),
                   label_gain=[0, 1, 3])


def test_tables_enter_the_program_as_arguments():
    """The query tables are parameters of the jitted program and not
    constants of its HLO, and the booster's round program takes them so."""
    import re
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(3)
    y = _labels(rng, SIZES)
    n = len(y)
    obj = _objective(y, SIZES)
    score = jnp.zeros(n, jnp.float32)
    inverse_map = re.compile(r"stablehlo\.constant[^\n]*tensor<%dxi32>" % n)
    closed = jax.jit(
        lambda s: obj.get_gradients(s, obj.device_tables)).lower(
            score).as_text()
    handed = jax.jit(obj.get_gradients).lower(
        score, obj.device_tables).as_text()
    assert inverse_map.search(closed) and not inverse_map.search(handed)
    X = rng.random((n, 4)).astype(np.float32)
    bst = lgb.Booster({"objective": "lambdarank", "verbosity": -1,
                       "num_leaves": 7},
                      lgb.Dataset(X, label=y, group=SIZES))
    b = bst.boosting
    assert b._macro_ctx["obj_tables"] is b.objective.device_tables
    assert set(b.objective.device_tables) == {"buckets", "slot_of_row"}


def test_gradients_take_the_tables_on_one_path():
    """A ranking objective has no copy of the tables to fall back on (closed
    over they would be constants of the program again); every other
    objective takes the argument and ignores it."""
    from lightgbm_tpu.objectives import create_objective
    rng = np.random.default_rng(4)
    y = _labels(rng, SIZES)
    score = jnp.zeros(len(y), jnp.float32)
    with pytest.raises(TypeError):
        _objective(y, SIZES).get_gradients(score)
    plain = create_objective(Config.from_params(
        {"objective": "regression", "verbosity": -1}))
    md = Metadata()
    md.label = y
    plain.init(md, len(y))
    assert plain.device_tables is None
    for a, b in zip(plain.get_gradients(score),
                    plain.get_gradients(score, None)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_inverse_slot_map_is_the_scatter_it_replaces():
    """Every row reads its slot: bit for bit what scatter-adding the slots
    onto their rows gave (each row has one slot, so nothing is added up)."""
    rng = np.random.default_rng(11)
    y = _labels(rng, SIZES)
    n = len(y)
    obj = _objective(y, SIZES)
    t = obj.device_tables
    score = jnp.asarray(rng.normal(size=n).astype(np.float32))
    score_pad = jnp.concatenate([score, jnp.zeros(obj.max_bucket)])
    lam, rows = [], []
    for Q, tb in t["buckets"].items():
        s = _windows(score_pad, tb["start"], Q)
        valid = jnp.arange(Q) < tb["cnt"][:, None]
        # the windows are the rows' own scores wherever a slot is valid
        row = tb["start"][:, None] + jnp.arange(Q)
        np.testing.assert_array_equal(
            np.asarray(s)[np.asarray(valid)],
            np.asarray(score)[np.asarray(row)[np.asarray(valid)]])
        g, _ = obj._query_gradients(Q, s, tb)
        lam.append(g.reshape(-1))
        rows.append(jnp.where(valid, row, n).reshape(-1))
    lam, rows = jnp.concatenate(lam), jnp.concatenate(rows)
    scattered = jnp.zeros(n + 1, jnp.float32).at[rows].add(
        jnp.where(rows < n, lam, 0.0))[:n]
    gathered = jnp.take(lam, t["slot_of_row"])
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(scattered))
    g, _ = obj.get_gradients(score, t)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(gathered))


def test_rank_init_record_counts():
    from lightgbm_tpu.obs.flight import global_flight
    rng = np.random.default_rng(5)
    y = _labels(rng, SIZES)
    obj = _objective(y, SIZES, lambdarank_truncation_level=4)
    rec = [e for e in global_flight.ring_events()
           if e.get("name") == "rank.init"][-1]
    args = rec["args"]
    padded = np.maximum(8, 1 << np.ceil(np.log2(SIZES)).astype(int))
    assert args["rows"] == SIZES.sum() and args["queries"] == len(SIZES)
    assert args["slots"] == obj.num_slots == padded.sum()
    assert args["pair_slots"] == (np.minimum(4, padded) * padded).sum()
    # pairs of unequal labels among ranks i < j, i < 4, at their most
    starts = np.concatenate([[0], np.cumsum(SIZES)])
    most = 0
    for a, b in zip(starts[:-1], starts[1:]):
        lab = y[a:b]
        unequal = sum(lab[i] != lab[j] for i in range(len(lab))
                      for j in range(i + 1, len(lab)))
        admitted = sum(len(lab) - 1 - i for i in range(min(4, len(lab) - 1)))
        most += min(unequal, admitted)
    assert args["label_pairs"] == most
    assert rec["dur"] > 0


def _ranking_rows(rng, sizes, features=6):
    n = int(sizes.sum())
    X = rng.random((n, features)).astype(np.float32)
    rel = 2.0 * X[:, 0] + X[:, 1] + 0.3 * rng.normal(size=n)
    y = np.clip(np.digitize(rel, [1.6, 2.0, 2.4, 2.7]), 0, 4)
    return X, y.astype(np.float32)


def test_padded_rows_belong_to_no_query(monkeypatch):
    """With the shape-bucket ladder on (the chip's default) the row count
    is padded: the padded rows get zero gradient and hessian, the others
    the reference's."""
    monkeypatch.setenv("LGBM_TPU_SHAPE_BUCKETS", "1")
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(2)
    sizes = rng.integers(5, 70, 40)
    X, y = _ranking_rows(rng, sizes)
    ds = lgb.Dataset(X, label=y, group=sizes)
    bst = lgb.Booster({"objective": "lambdarank", "verbosity": -1,
                       "num_leaves": 7}, ds)
    b = bst.boosting
    n, n_pad = len(y), b._n_pad
    assert n_pad > n
    s = rng.normal(size=n_pad).astype(np.float32)
    g, h = b._gradients_fn(jnp.asarray(s)[None, :])
    g, h = np.asarray(g)[0], np.asarray(h)[0]
    assert g.shape == (n_pad,) and not g[n:].any() and not h[n:].any()
    g_ref, h_ref = ref.gradients(s[:n].astype(np.float64), y,
                                 {"group": sizes})
    np.testing.assert_allclose(g[:n], g_ref, rtol=0,
                               atol=RTOL * np.abs(g_ref).max())
    np.testing.assert_allclose(h[:n], h_ref, rtol=0,
                               atol=RTOL * np.abs(h_ref).max())


def test_quantized_renewed_rounds_follow_the_reference():
    """Three rounds of ``Booster.update()`` with 4-level gradients and
    renewed leaves through ``Dataset(group=)``: every leaf's value and
    weight are the reference's own sums of ITS gradients over the rows it
    routes there (renewal takes the 4-level noise out of them), and the
    train score follows."""
    import lightgbm_tpu as lgb
    from benchmark.lib.traffic import host_tree
    rng = np.random.default_rng(4)
    sizes = rng.integers(20, 120, 60)
    X, y = _ranking_rows(rng, sizes)
    params = {"objective": "lambdarank", "verbosity": -1, "num_leaves": 15,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 0.5, "max_bin": 63,
              "use_quantized_grad": True, "num_grad_quant_bins": 4,
              "quant_train_renew_leaf": True}
    ds = lgb.Dataset(X, label=y, group=sizes, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(3):
        bst.update()
    trees = [host_tree(m) for m in bst.models[:3]]
    score = np.asarray(bst.boosting.train_score)[0, :len(y)]
    steps = list(reference_gbdt.follow(
        X, y, trees, 0.1, objective=ref, aux={"group": sizes}, blocks=2))
    for tree, r in zip(trees, steps):
        assert len(tree["leaf_value"]) > 1
        scale = np.maximum(np.abs(r["value"]), np.median(np.abs(r["value"])))
        # f32 sums of a few hundred rows a leaf against float64
        assert np.max(np.abs(tree["leaf_value"] - r["value"]) / scale) < 1e-4
        # (leaf_count is no witness: the int8 path estimates a leaf's rows
        # from its hessian sum)
        np.testing.assert_allclose(tree["leaf_weight"], r["H"], rtol=1e-4)
    np.testing.assert_allclose(score, steps[-1]["score"], atol=1e-5)


def test_round_width_changes_no_tree():
    """The rounds grower commits the strict best-first prefix of what a
    round offers, so ``tpu_round_width`` decides how many passes a tree
    takes and not which tree it is: quantized, renewed lambdarank models
    at widths 4, 16 and 128 are the same trees.  The key is a cap (no
    configuration of the benchmark states it): under it the grower's offer
    follows its commits round by round, on the same ground."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(8)
    sizes = rng.integers(20, 120, 60)
    X, y = _ranking_rows(rng, sizes)
    texts = []
    for width in (4, 16, 128):
        params = {"objective": "lambdarank", "verbosity": -1,
                  "num_leaves": 31, "min_data_in_leaf": 1,
                  "min_sum_hessian_in_leaf": 0.5, "max_bin": 63,
                  "use_quantized_grad": True, "num_grad_quant_bins": 4,
                  "quant_train_renew_leaf": True,
                  "tpu_tree_growth": "rounds", "tpu_round_width": width}
        bst = lgb.train(params, lgb.Dataset(X, label=y, group=sizes,
                                            params=params),
                        num_boost_round=3, verbose_eval=False)
        # the trees, without the parameters the text ends with
        texts.append(bst.model_to_string().split("parameters:")[0])
        assert bst.boosting.grower_cfg.round_width == width
    assert texts[0] == texts[1] == texts[2]
