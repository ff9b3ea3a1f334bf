"""GOSS at the click log's deployment, on the CPU at a small size: the
selection (``boosting/goss.py``) against ``lax.top_k`` and the published
counts, the warm-up, the chunk program against the per-iteration path, the
counters on the ``grower.tree`` record, no sort over the rows in the round
program, and the program's GOSS trees against the benchmark's plain
weighted reference (``benchmark/lib/reference_goss.py``, float64 NumPy,
imports nothing of the program)."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import lightgbm_tpu as lgb  # noqa: E402
from benchmark.lib import reference_goss as ref_goss  # noqa: E402
from benchmark.lib.traffic import host_tree  # noqa: E402
from benchmark.objectives import binary  # noqa: E402
from lightgbm_tpu.boosting import goss  # noqa: E402
from lightgbm_tpu.obs.flight import global_flight  # noqa: E402


def _scores(seed, n, pad):
    """Non-negative f32 scores with runs of ties, zeros and ``pad`` padding
    rows (row_valid 0) at the end."""
    rng = np.random.RandomState(seed)
    s = np.abs(rng.standard_normal(n)).astype(np.float32)
    s[rng.rand(n) < 0.2] = 0.0
    s[rng.rand(n) < 0.3] = rng.choice(s[:50], size=None)   # ties
    s = np.round(s, 2).astype(np.float32)                  # more ties
    valid = np.r_[np.ones(n, np.float32), np.zeros(pad, np.float32)]
    return np.r_[s, rng.rand(pad).astype(np.float32) * 9], valid


@pytest.mark.parametrize("seed, n, pad, rate", [
    (0, 5000, 0, 0.2), (1, 4093, 3, 0.2), (2, 3000, 72, 0.05),
    (3, 1000, 24, 0.9), (4, 777, 0, 0.001)])
def test_threshold_is_top_k_to_the_bit(seed, n, pad, rate):
    s, valid = _scores(seed, n, pad)
    k = max(1, int(rate * n))
    bits = jnp.where(jnp.asarray(valid) > 0,
                     lax.bitcast_convert_type(jnp.asarray(s), jnp.int32), -1)
    got = jax.jit(goss.kth_largest_bits, static_argnums=1)(bits, k)
    want = lax.top_k(jnp.where(jnp.asarray(valid) > 0, jnp.asarray(s),
                               -1.0), k)[0][-1]
    assert int(got) == int(lax.bitcast_convert_type(want, jnp.int32))


def _select(seed, n, pad, K=1, top_rate=0.2, other_rate=0.1):
    rng = np.random.RandomState(seed)
    g = rng.standard_normal((K, n + pad)).astype(np.float32)
    h = rng.rand(K, n + pad).astype(np.float32)
    g[:, ::7] = np.round(g[:, ::7], 1)                     # ties
    valid = np.r_[np.ones(n, np.float32), np.zeros(pad, np.float32)]
    g[:, n:] = 0.0
    h[:, n:] = 0.0
    fn = jax.jit(goss.make_goss_weights(n, top_rate, other_rate))
    w, counts = fn(jnp.asarray(g), jnp.asarray(h),
                   jax.random.PRNGKey(seed), jnp.asarray(valid))
    score = np.sum(np.abs(g * h), axis=0)
    return np.asarray(w), np.asarray(counts), score, valid


@pytest.mark.parametrize("seed, n, pad, K", [
    (5, 6000, 0, 1), (6, 4090, 6, 1), (7, 3000, 40, 3), (8, 10007, 1, 1)])
def test_rest_is_exactly_other_k(seed, n, pad, K):
    w, counts, score, valid = _select(seed, n, pad, K)
    top_k, other_k, _ = ref_goss.sizes(n, 0.2, 0.1)
    amp = np.float32(n - top_k) / np.float32(other_k)
    assert set(np.unique(w)) <= {0.0, 1.0, amp}
    assert (w[valid == 0] == 0).all()
    thr = np.sort(score[:n])[n - top_k]
    assert np.array_equal(w[:n] == 1.0, score[:n] >= thr)   # ties all kept
    assert int((w == amp).sum()) == other_k
    assert list(counts) == [int((w != 0).sum()), int((w == 1).sum())]


def test_ties_at_the_cut_go_in_row_order():
    keys = jnp.asarray(np.array([5, 3, 3, 9, 3, 1, 3, 3], np.uint32))
    pool = jnp.asarray(np.array([1, 1, 1, 1, 0, 1, 1, 1], bool))
    got = np.asarray(goss.smallest_keys(keys, pool, 3))
    # key 1 (row 5), then the first two pool rows holding 3: rows 1, 2
    assert list(np.flatnonzero(got)) == [1, 2, 5]
    every = np.asarray(goss.smallest_keys(keys, pool, 50))
    assert np.array_equal(every, np.asarray(pool))


def test_rest_is_drawn_uniformly():
    w, _, score, _ = _select(9, 200000, 0)
    top = w == 1.0
    z = ref_goss.rest_bias_z(score, ~top, (w != 0) & ~top)
    assert z < 5.0


def _data(seed=3, n=6000):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 6).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2] + 0.4 * rng.randn(n)) > 0.8)
    return X, y.astype(np.float32)


BASE = {"objective": "binary", "boosting": "goss", "num_leaves": 7,
        "learning_rate": 0.25, "verbosity": -1, "min_data_in_leaf": 5}


def _records():
    return [e["args"] for e in global_flight.ring_events()
            if e["name"] == "grower.tree"]


def test_no_sampling_before_the_warm_up_ends():
    X, y = _data()
    global_flight._ring.clear()
    bst = lgb.Booster(BASE, lgb.Dataset(X, label=y, params=BASE))
    weights = []
    for _ in range(6):
        bst.update()
        weights.append(np.asarray(bst.boosting.last_row_weights)[:len(y)])
    recs = _records()
    assert [r["it"] for r in recs] == list(range(6))
    top_k, other_k, _ = ref_goss.sizes(len(y), 0.2, 0.1)
    for it, (r, w) in enumerate(zip(recs, weights)):
        if it < 4:                   # 1 / learning_rate = 4 rounds
            assert (r["goss_kept"], r["goss_top"]) == (0, 0)
            assert (w == 1.0).all()
        else:
            assert r["goss_top"] >= top_k
            assert r["goss_kept"] == r["goss_top"] + other_k
            assert int((w != 0).sum()) == r["goss_kept"]


def test_plain_trees_count_no_sample():
    X, y = _data()
    global_flight._ring.clear()
    lgb.train(dict(BASE, boosting="gbdt"), lgb.Dataset(X, label=y),
              num_boost_round=2)
    assert [(r["goss_kept"], r["goss_top"]) for r in _records()] \
        == [(0, 0), (0, 0)]


@pytest.mark.parametrize("quant", [False, True])
def test_chunk_program_equals_per_iteration(quant, monkeypatch):
    X, y = _data(4)
    params = dict(BASE, use_quantized_grad=quant)
    texts, weights = [], []
    for chunk in ("", "0"):
        monkeypatch.setenv("LGBM_TPU_CHUNK", chunk)
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=7)
        texts.append(bst.model_to_string())
        weights.append(np.asarray(bst.boosting.last_row_weights))
    assert texts[0] == texts[1]
    assert np.array_equal(weights[0], weights[1])
    assert (weights[0] > 1).any()


def test_round_program_holds_no_sort_over_the_rows():
    from lightgbm_tpu.boosting.macro import (build_chunk_program,
                                             chunk_host_inputs)
    X, y = _data(5, n=3000)
    params = dict(BASE, use_quantized_grad=True, num_grad_quant_bins=4,
                  tpu_tree_growth="rounds")
    b = lgb.Booster(params, lgb.Dataset(X, label=y, params=params)).boosting
    b.boost_from_average()
    xs, _ = chunk_host_inputs(b, 1)
    cu, cr = b._cegb_state
    gc, hc = b._macro_const_grads()
    text = build_chunk_program(b).lower(
        b.binned, b.train_score, cu, cr, np.int32(1), xs,
        b._macro_ctx["label"], b._macro_ctx["weight"], gc, hc,
        b._macro_ctx["obj_tables"]).as_text(debug_info=True)
    assert "lgbm.goss" in text
    sorting = re.compile(r"stablehlo\.sort|top_k")
    assert sorting.search(jax.jit(lambda s: lax.top_k(s, 3)).lower(
        jnp.ones(b._n_pad)).as_text())          # the search finds one
    rows = re.compile(rf"\b{b._n_pad}x")
    assert not [line for line in text.splitlines()
                if sorting.search(line) and rows.search(line)]


def _follow_sampled(params, rounds, seed=6, n=20000):
    """Train to ``rounds`` trees, the last one sampled; its reference
    readings: (program tree, the step on its weights, the step on the same
    sample with the rest left at weight 1, the program's quantization
    scales of the round)."""
    X, y = _data(seed, n)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    for _ in range(rounds - 1):
        bst.update()
    before = np.asarray(bst.boosting.train_score)[0, :n].astype(np.float64)
    bst.update()
    w = np.asarray(bst.boosting.last_row_weights)[:n].astype(np.float64)
    assert (w > 1).any()
    tree = host_tree(bst.boosting.models[-1])
    steps = [next(ref_goss.GossReference(weights=weights, check_at=()).follow(
        X, y, [tree], params["learning_rate"], starts={0: before},
        objective=binary)) for weights in ({0: w}, {0: np.minimum(w, 1.0)})]
    return tree, steps[0], steps[1], bst.boosting._quant_scales


def test_goss_tree_follows_the_weighted_reference_f32():
    params = dict(BASE, num_leaves=15)
    tree, step, flat, _ = _follow_sampled(params, rounds=6)

    def gap(step):
        v = step["value"]
        scale = np.maximum(np.abs(v), np.median(np.abs(v)))
        return np.max(np.abs(tree["leaf_value"] - v) / scale)
    assert gap(step) < 1e-4
    assert np.allclose(tree["leaf_weight"], step["H"], rtol=1e-4)
    assert gap(flat) > 1e-2           # the x8 is what it was grown on


def test_goss_tree_follows_the_weighted_reference_4_levels():
    """Under ``use_quantized_grad`` each row's weighted gradient and hessian
    are rounded up or down to a level at random, unbiased: a leaf's sums
    stray from the reference's by at most half a level per kept row, in
    standard deviation.  Held to six of them."""
    params = dict(BASE, num_leaves=4, use_quantized_grad=True,
                  num_grad_quant_bins=4)
    tree, step, flat, scales = _follow_sampled(params, 6)
    g_scale, h_scale = np.asarray(scales)[0]

    def outside(step):
        live = step["count"] > 0
        sd = 0.5 * np.sqrt(step["count"][live])
        H = tree["leaf_weight"]
        G = -(tree["leaf_value"] / params["learning_rate"]) * H
        return (np.abs(H - step["H"])[live] > 6 * h_scale * sd).sum() \
            + (np.abs(G - step["G"])[live] > 6 * g_scale * sd).sum()
    assert outside(step) == 0
    assert outside(flat) > 0


def _tied_data(seed=8, n=2000):
    """Few distinct rows: their |g*h| tie in runs, so the top set's
    threshold holds many rows and the round keeps more than top_k +
    other_k."""
    rng = np.random.RandomState(seed)
    X = rng.randint(0, 3, (n, 4)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] + 2 * rng.rand(n) > 3.0).astype(np.float32)
    return X, y


def _train(params, X, y, rounds, capture=None):
    """``rounds`` trees, the ``grower.tree`` records and, with ``capture``,
    what each call of the per-iteration program returned."""
    global_flight._ring.clear()
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    if capture is not None:
        step = bst.boosting._iter_fn

        def spy(*a):
            out = step(*a)
            capture.append(jax.device_get(out))
            return out
        bst.boosting._iter_fn = spy
    for _ in range(rounds):
        bst.update()
    return bst, _records()


@pytest.mark.parametrize("family, path, rows", [
    ("4_levels", "chunk", "tied"), ("4_levels", "per_iteration", "spread"),
    ("f32", "chunk", "spread")])
def test_kept_rows_grow_the_mask_tree(family, path, rows, monkeypatch):
    """On the rounds grower's fused arm a sampled tree grows on its kept
    rows gathered to the front of a row buffer (``kept_rows_first``), its
    passes and routes stopping at the last of them; against the mask form
    (every row under the weight mask), the trees are equal field for
    field, every row's leaf is the same, the train score is the same to
    the bit, and so are the weights the trees were grown on: in the chunk
    program and on the per-iteration path, and where ties at the top
    threshold keep more rows than top_k + other_k.  Under 4 levels the
    passes read only the kept rows' tiles; at f32 the sums would take
    another order, so the tree keeps the mask form and reads every row."""
    from lightgbm_tpu.boosting.goss import GOSS
    from lightgbm_tpu.ops import fused
    # row tiles of 512, so that the 2,048-row buffer holds four
    monkeypatch.setattr(fused, "_row_tile", lambda *_: 512)
    monkeypatch.setenv("LGBM_TPU_CHUNK", "" if path == "chunk" else "0")
    X, y = _tied_data() if rows == "tied" else _data(4, n=2000)
    params = dict(BASE, learning_rate=0.5, tpu_tree_growth="rounds",
                  tpu_hist_method="fused",
                  use_quantized_grad=family == "4_levels",
                  num_grad_quant_bins=4)
    runs = []
    for sampled in (True, False):
        monkeypatch.setattr(GOSS, "_rows_sampled", sampled)
        calls = [] if path == "per_iteration" else None
        bst, recs = _train(params, X, y, 4, calls)
        runs.append((bst, recs, calls))
    (gathered, recs, calls), (masked, recs_m, calls_m) = runs
    assert gathered.model_to_string() == masked.model_to_string()
    for name in ("train_score", "last_row_weights"):
        assert np.array_equal(np.asarray(getattr(gathered.boosting, name)),
                              np.asarray(getattr(masked.boosting, name)))
    if calls is not None:
        assert len(calls) == len(calls_m) == 4
        for got, want in zip(calls, calls_m):
            for a, b in zip(jax.tree_util.tree_leaves(got[:3]),
                            jax.tree_util.tree_leaves(want[:3])):
                assert np.array_equal(a, b)          # score, trees, leaves
    n_pad = gathered.boosting._n_pad
    top_k, other_k, _ = ref_goss.sizes(len(y), 0.2, 0.1)
    assert [r["goss_kept"] for r in recs] == [r["goss_kept"] for r in recs_m]
    for r, r_m in zip(recs, recs_m):
        passes = r["rounds"] + 1
        assert r_m["pass_rows"] == passes * n_pad      # the mask: every row
        if family == "f32":
            assert r["pass_rows"] == passes * n_pad
            continue
        # whole row tiles of the kept rows (a warm-up round: every row)
        live = r["goss_kept"] or len(y)
        assert passes * live <= r["pass_rows"] <= passes * n_pad
        if r["goss_kept"]:
            assert r["pass_rows"] < passes * n_pad
    assert all(r["goss_kept"] for r in recs[2:])
    if rows == "tied":
        assert all(r["goss_kept"] > top_k + other_k for r in recs[2:])


def _chunk_jaxpr(params):
    """The chunk program of a booster on ``params``, as a jaxpr."""
    from lightgbm_tpu.boosting.macro import chunk_host_inputs, make_chunk_fn
    X, y = _data(5, n=1000)
    b = lgb.Booster(params, lgb.Dataset(X, label=y, params=params)).boosting
    b.boost_from_average()
    xs, _ = chunk_host_inputs(b, 1)
    cu, cr = b._cegb_state
    gc, hc = b._macro_const_grads()
    return b._n_pad, jax.make_jaxpr(make_chunk_fn(b))(
        b.binned, b.train_score, cu, cr, np.int32(1), xs,
        b._macro_ctx["label"], b._macro_ctx["weight"], gc, hc,
        b._macro_ctx["obj_tables"])


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("boosting", ["gbdt", "goss"])
def test_plain_chunk_program_gathers_no_row(boosting):
    """The gather and the bound are the sampled booster's alone: a plain
    booster's chunk program holds no gather over the rows of the matrix
    and no accumulate kernel with a scalar-prefetch operand, which the
    GOSS program on the same arm holds both of."""
    params = dict(BASE, boosting=boosting, tpu_tree_growth="rounds",
                  tpu_hist_method="fused", use_quantized_grad=True,
                  num_grad_quant_bins=4)
    n_pad, closed = _chunk_jaxpr(params)
    eqns = list(_eqns(closed.jaxpr))
    row_gathers = [e for e in eqns if e.primitive.name == "gather"
                   and n_pad in e.invars[0].aval.shape
                   and e.invars[1].aval.shape[0] == n_pad]
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert kernels
    prefetch = [e for e in kernels
                if e.params["grid_mapping"].num_index_operands]
    if boosting == "gbdt":
        assert not row_gathers and not prefetch
    else:
        assert row_gathers and prefetch
