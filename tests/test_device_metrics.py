"""The validation metrics' device forms (``metrics.py``: ``device_partials``
reduced on the device, ``finish`` on the host) against their host forms, and
where ``GBDT`` takes them: AUC to the last bit on unweighted sets, logloss
within 1e-6 relative; weighted sets, multiclass and ranking metrics stay on
the host, and the registry's two counters say which form ran; the partials
``upd`` returns are used only for the array they came from; early stopping
decides as the host forms would; the labels are runtime arguments of
``upd``, never constants of it."""
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt
from lightgbm_tpu.boosting.macro import build_chunk_valid
from lightgbm_tpu.config import Config
from lightgbm_tpu.metrics import AUCMetric, BinaryLoglossMetric
from lightgbm_tpu.obs.metrics import global_registry
from lightgbm_tpu.objectives import BinaryLogloss


def _metric(cls, y, weight=None):
    m = cls(Config())
    m.init(SimpleNamespace(label=np.asarray(y, np.float64), weight=weight),
           len(y))
    return m


def _objective():
    obj = BinaryLogloss.__new__(BinaryLogloss)
    obj.config = Config()
    return obj


def _device_value(m, score, objective=None):
    partials = jax.jit(lambda s, l: m.device_partials(s, l, objective))(
        jnp.asarray(score), m.device_label())
    return m.finish(jax.device_get(partials))[0][1]


def _auc_case(name, rng):
    n = 5000
    y = (rng.rand(n) < 0.3).astype(np.float64)
    s = rng.randn(n).astype(np.float32)
    if name == "ties":                  # a few score values: large groups
        s = np.round(s, 1).astype(np.float32)
    elif name == "signed_zeros":        # -0.0 beside 0.0: one group
        zero = rng.rand(n) < 0.4
        s[zero] = np.where(rng.rand(zero.sum()) < 0.5, -0.0, 0.0)
        assert np.signbit(s[zero]).any() and not np.signbit(s[zero]).all()
    elif name == "all_positive":
        y[:] = 1.0
    elif name == "all_negative":
        y[:] = 0.0
    elif name == "blocks_not_whole":    # 12.2 blocks of 8,192 rows
        n = 100_003
        y = (rng.rand(n) < 0.25).astype(np.float64)
        s = np.round(rng.randn(n), 2).astype(np.float32)
    return s, y


@pytest.mark.parametrize("case", ["random", "ties", "signed_zeros",
                                  "all_positive", "all_negative",
                                  "blocks_not_whole"])
def test_device_auc_is_the_host_auc(case):
    rng = np.random.RandomState(39)
    s, y = _auc_case(case, rng)
    m = _metric(AUCMetric, y)
    assert m.device_ready()
    host = m.eval(s, None)[0][1]
    assert _device_value(m, s) == host
    if case.startswith("all_"):
        assert host == 1.0


def test_device_auc_at_the_int32_edge():
    """The block is the largest power of two whose counts an int32 holds:
    at 1,048,575 rows 1,024 rows, a full block of positives under every
    negative sums to 99.6% of 2^31 - 1, and the total (~8.5e9) only fits
    the host's int64."""
    n = 1_048_575
    rows = AUCMetric.block_rows(n)
    assert rows == 1024
    assert rows * 2 * n < 2 ** 31 <= 2 * rows * 2 * n
    rng = np.random.RandomState(0)
    s = rng.randn(n).astype(np.float32)
    y = np.zeros(n)
    y[np.argsort(s)[:4096]] = 1.0       # the lowest scores: each adds 2N
    y[rng.rand(n) < 0.01] = 1.0
    m = _metric(AUCMetric, y)
    partials = np.asarray(jax.jit(lambda s, l: m.device_partials(s, l))(
        jnp.asarray(s), m.device_label()))
    assert partials.dtype == np.int32 and partials.min() >= 0
    assert partials.max() > 0.98 * (2 ** 31 - 1)
    assert partials.astype(np.int64).sum() > 2 ** 31
    assert m.finish(partials)[0][1] == m.eval(s, None)[0][1]
    pos, neg = m._counts
    full = np.full(4, 2 ** 31 - 1, np.int32)        # no int32 wrap
    assert m.finish(full)[0][1] == 1.0 - (2 * (2 ** 31 - 1)) / (
        float(pos) * float(neg))


@pytest.mark.parametrize("case", ["random", "saturated"])
def test_device_logloss_is_the_host_logloss(case):
    """Within 1e-6 relative, also where a score past +-40 makes ``p`` 1.0
    or 0 in f32 (the host's float64 clip, not ``log(0)``)."""
    rng = np.random.RandomState(7)
    n = 20_011
    y = (rng.rand(n) < 0.3).astype(np.float64)
    s = (3 * rng.randn(n)).astype(np.float32)
    if case == "saturated":
        s[::5] = rng.choice([-100.0, -45.0, 45.0, 100.0], len(s[::5]))
    obj = _objective()
    m = _metric(BinaryLoglossMetric, y)
    assert m.device_ready()
    host = m.eval(s, obj)[0][1]
    assert abs(_device_value(m, s, obj) - host) <= 1e-6 * host


def test_the_log_is_within_two_ulp():
    """``metrics._log``, which the device logloss takes in place of the
    backend's f32 log, against float64 over the clipped range [1e-15, 1]."""
    from lightgbm_tpu.metrics import _log
    rng = np.random.RandomState(11)
    x = np.concatenate([rng.rand(100_000), 10 ** rng.uniform(-15, 0, 100_000),
                        1 - 10 ** rng.uniform(-7.2, -0.3, 100_000),
                        [1e-15, 0.5, 1.0]]).astype(np.float32)
    x = np.clip(x, np.float32(1e-15), np.float32(1))
    want = np.log(x.astype(np.float64))
    got = np.asarray(jax.jit(_log)(jnp.asarray(x)), np.float64)
    ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
    assert np.max(np.abs(got - want) / ulp) < 2.0
    assert got[-1] == 0.0


def _counters():
    c = global_registry.to_dict().get("counters", {})
    return (c.get("eval_metrics_device_total", 0),
            c.get("eval_metrics_host_total", 0))


def _binary(n=1200, seed=0, noise=1.0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + noise * rng.randn(n) > 0.3)
    return X, y.astype(np.float64)


def _ranking():
    rng = np.random.RandomState(3)
    X = rng.randn(600, 5)
    y = np.clip(np.round(X[:, 0] + rng.randn(600)), 0, 3)
    return X, y, [30] * 20


ROUNDS = 4
FORMS = {
    # params, (device, host) evaluations expected
    "unweighted": ({"objective": "binary",
                    "metric": ["auc", "binary_logloss"]}, (2, 0)),
    "weighted": ({"objective": "binary",
                  "metric": ["auc", "binary_logloss"]}, (0, 2)),
    "multiclass": ({"objective": "multiclass", "num_class": 3,
                    "metric": "multi_logloss"}, (0, 1)),
    "ndcg": ({"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [5]}, (0, 1)),
}


@pytest.mark.parametrize("case", sorted(FORMS))
def test_which_form_evaluates(case):
    params, (dev, host) = FORMS[case]
    kw, vkw = {}, {}
    if case == "ndcg":
        X, y, group = _ranking()
        kw, vkw = {"group": group[:15]}, {"group": group[15:]}
        Xt, yt, Xv, yv = X[:450], y[:450], X[450:], y[450:]
    else:
        X, y = _binary()
        if case == "multiclass":
            y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float64)
        Xt, yt, Xv, yv = X[:900], y[:900], X[900:], y[900:]
        if case == "weighted":
            rng = np.random.RandomState(1)
            kw = {"weight": rng.rand(900) + 0.5}
            vkw = {"weight": rng.rand(300) + 0.5}
    ds = lgb.Dataset(Xt, label=yt, **kw)
    dv = lgb.Dataset(Xv, label=yv, reference=ds, **vkw)
    d0, h0 = _counters()
    lgb.train(dict(params, num_leaves=7, verbosity=-1), ds, ROUNDS,
              valid_sets=[dv], verbose_eval=False)
    d1, h1 = _counters()
    assert (d1 - d0, h1 - h0) == (dev * ROUNDS, host * ROUNDS)


def _host_values(b, i=0):
    s = np.asarray(b.valid_scores[i])[0]
    return {m.name: m.eval(s, b.objective)[0][1] for m in b.valid_metrics[i]}


def _assert_host_forms(got, want):
    got = {name: v for (_, name, v, _) in got}
    assert got["auc"] == want["auc"]
    assert abs(got["binary_logloss"] - want["binary_logloss"]) <= (
        1e-6 * want["binary_logloss"])


def test_kept_partials_are_used_for_their_array_only():
    """After training the validation set's partials come from ``upd`` and
    no program of their own ran; after ``rollback_one_iter`` the score is
    another array, and ``eval_valid`` reduces it alone, to the host forms'
    values."""
    X, y = _binary()
    ds = lgb.Dataset(X[:900], label=y[:900])
    dv = lgb.Dataset(X[900:], label=y[900:], reference=ds)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                     "metric": ["auc", "binary_logloss"]}, ds, 6,
                    valid_sets=[dv], verbose_eval=False)
    b = bst.boosting
    kept, names, _ = b._valid_partials[0]
    assert kept is b.valid_scores[0] and names == ["auc", "binary_logloss"]
    _assert_host_forms(bst.eval_valid(), _host_values(b))
    assert b._partials_jit == {}
    b.rollback_one_iter()
    assert b._valid_partials[0][0] is not b.valid_scores[0]
    _assert_host_forms(bst.eval_valid(), _host_values(b))
    assert list(b._partials_jit) == [(("auc", "binary_logloss"), 300)]


def test_early_stopping_decides_as_the_host_forms(monkeypatch):
    """The same ``lgb.train`` with every metric forced onto its host form:
    the same ``best_iteration``, the same AUC history to the last bit and
    the logloss history within 1e-6 relative."""
    X, y = _binary(n=1500, seed=5, noise=2.0)
    params = {"objective": "binary", "metric": ["auc", "binary_logloss"],
              "num_leaves": 31, "learning_rate": 0.4, "min_data_in_leaf": 3,
              "verbosity": -1}

    def run():
        ds = lgb.Dataset(X[:1000], label=y[:1000])
        dv = lgb.Dataset(X[1000:], label=y[1000:], reference=ds)
        evals = {}
        bst = lgb.train(params, ds, 60, valid_sets=[dv], valid_names=["v"],
                        verbose_eval=False,
                        callbacks=[lgb.early_stopping(4, verbose=False),
                                   lgb.record_evaluation(evals)])
        return bst.best_iteration, evals["v"]

    d0, _ = _counters()
    device = run()
    assert _counters()[0] > d0
    monkeypatch.setattr(gbdt.GBDT, "_device_forms",
                        lambda self, metrics, objective: ())
    host = run()
    assert device[0] == host[0] and len(device[1]["auc"]) < 60
    assert device[1]["auc"] == host[1]["auc"]
    np.testing.assert_allclose(device[1]["binary_logloss"],
                               host[1]["binary_logloss"], rtol=1e-6, atol=0)


def test_upd_takes_the_labels_as_an_argument():
    """The lowered validation update with the reduction holds no constant
    of the validation set's length: its labels are a parameter."""
    X, y = _binary(n=2137)
    ds = lgb.Dataset(X[:900], label=y[:900])
    dv = lgb.Dataset(X[900:], label=y[900:], reference=ds)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                     "metric": ["auc", "binary_logloss"]}, ds, 2,
                    valid_sets=[dv], verbose_eval=False)
    b = bst.boosting
    forms = b._device_forms(b.valid_metrics[0], b.objective)
    seq = jax.tree_util.tree_map(lambda a: a[None], b.tree_history[-1])
    text = build_chunk_valid(b, forms).lower(
        b.valid_scores[0], seq, b.valid_binned[0],
        jnp.arange(1, dtype=jnp.int32), np.int32(1),
        forms[0].device_label()).as_text()
    rows = re.compile(r"tensor<(\d+x)*1237x")
    assert rows.search(text.split("@main(")[1].split("\n")[0])
    assert not [ln for ln in text.splitlines()
                if "constant" in ln and rows.search(ln)]
