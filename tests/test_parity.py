"""Numerical parity vs the locally built reference implementation.

SURVEY.md section 4 prescribes a parity harness the reference itself lacks:
train the same data through this package and through stock LightGBM
(built from /root/reference by tools/build_reference.sh, staged at
/tmp/refpkg) and compare metric trajectories and model-text cross-loading.

Skipped wholesale when the reference lib is absent (CI images build it
once; ~2 min).  The reference package is pure ctypes so importing it next to
the JAX stack is safe.

Measured facts these tests pin down (round 3, binary.train 7000x28):

==========  =========================  =========================
config      reference AUC              this repo AUC
==========  =========================  =========================
30r plain   0.8825759152573261         0.8809875801255787
30r bag .7  0.882125915650661          0.8816582569498983
20r weight  0.8575449931338933         0.8574...
iter-1 AUC  0.768800830329785          0.7688008303297851
==========  =========================  =========================

i.e. the round-1/2 "accuracy plateau" was the dataset at 30 rounds, not a
split-quality deficiency: the reference plateaus identically (and reaches
0.975 only at 100 rounds).  Bonus root cause: in this reference checkout
``boosting=goss`` never samples at all -- GOSS::Bagging delegates to
GBDT::Bagging (src/boosting/goss.hpp:129) whose guard requires
``bag_data_cnt_ < num_data_`` (src/boosting/gbdt.cpp:214), but with GOSS's
mandatory bagging_freq=0 ResetBaggingConfig leaves bag_data_cnt_ == num_data_
forever, so reference GOSS == reference GBDT bit-for-bit.  This repo
implements the *intended* GOSS (top-rate keep + other-rate sample after the
1/learning_rate warm-up), which is why its GOSS trajectory legitimately
differs from plain.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REFPKG = os.environ.get("LGBM_REF_PKG", "/tmp/refpkg")
EXAMPLES = "/root/reference/examples"
_REFLIB = os.path.join(REFPKG, "lightgbm", "lib_lightgbm.so")


def _ensure_reference_built() -> str:
    """Build the reference lib on demand (~2 min, cached in /tmp across
    runs) so the parity suite executes unskipped on any image with the
    toolchain; set LGBM_REF_SKIP_BUILD=1 to skip instead.  Called from the
    reflgb fixture (NOT at import time: collection must stay cheap) and
    serialized through a lock file for parallel pytest workers."""
    if os.path.exists(_REFLIB):
        return ""
    if os.environ.get("LGBM_REF_SKIP_BUILD") == "1":
        return "reference lib not built (LGBM_REF_SKIP_BUILD=1)"
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "build_reference.sh")
    import fcntl
    with open("/tmp/lgb_refbuild.lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)   # one builder at a time
        if os.path.exists(_REFLIB):         # another worker built it
            return ""
        proc = subprocess.Popen(["sh", script], stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            import signal
            os.killpg(proc.pid, signal.SIGKILL)   # sh AND make children
            proc.wait()
            return "reference build timed out"
        if proc.returncode != 0:
            return f"reference build failed rc={proc.returncode}: " \
                   f"{out.decode()[-300:]}"
    return "" if os.path.exists(_REFLIB) else "reference build produced no lib"


@pytest.fixture(scope="module")
def reflgb():
    reason = _ensure_reference_built()
    if reason:
        pytest.skip(reason)
    sys.path.insert(0, REFPKG)
    import lightgbm
    return lightgbm


@pytest.fixture(scope="module")
def binary_train():
    d = np.loadtxt(f"{EXAMPLES}/binary_classification/binary.train")
    return d[:, 1:], d[:, 0]


@pytest.fixture(scope="module")
def binary_test():
    d = np.loadtxt(f"{EXAMPLES}/binary_classification/binary.test")
    return d[:, 1:], d[:, 0]


def _train_auc_traj(pkg, X, y, params, nbr):
    ev = {}
    tr = pkg.Dataset(X, label=y)
    bst = pkg.train(params, tr, num_boost_round=nbr,
                    valid_sets=[pkg.Dataset(X, label=y, reference=tr)],
                    evals_result=ev, verbose_eval=False)
    return bst, ev["valid_0"]["auc"]


BASE = {"objective": "binary", "metric": "auc", "verbosity": -1}


def test_auc_trajectory_parity(reflgb, binary_train):
    import lightgbm_tpu as lgb
    X, y = binary_train
    _, ours = _train_auc_traj(lgb, X, y, dict(BASE), 30)
    _, ref = _train_auc_traj(reflgb, X, y, dict(BASE), 30)
    # iteration 1 must agree to float precision: same binning, same root
    # histogram, same first split set (reference value 0.768800830329785)
    assert abs(ours[0] - ref[0]) < 1e-9
    # accumulated tie-breaking/fp drift stays small across 30 rounds
    diffs = np.abs(np.asarray(ours) - np.asarray(ref))
    assert diffs.max() < 5e-3, f"trajectory diverged: max {diffs.max():.4g}"
    assert abs(ours[-1] - ref[-1]) < 3e-3


def test_model_cross_load_ours_to_ref(reflgb, binary_train, binary_test,
                                      tmp_path):
    """A model saved by this package parses in the reference C++ loader
    (gbdt_model_text.cpp:405) with identical predictions."""
    import lightgbm_tpu as lgb
    X, y = binary_train
    Xt, _ = binary_test
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 15},
                    lgb.Dataset(X, label=y), num_boost_round=10,
                    verbose_eval=False)
    path = str(tmp_path / "ours.txt")
    bst.save_model(path)
    ref_pred = reflgb.Booster(model_file=path).predict(Xt)
    np.testing.assert_allclose(bst.predict(Xt), ref_pred, atol=1e-12)


def test_model_cross_load_ref_to_ours(reflgb, binary_train, binary_test,
                                      tmp_path):
    import lightgbm_tpu as lgb
    X, y = binary_train
    Xt, _ = binary_test
    ref_bst = reflgb.train(
        {"objective": "binary", "verbosity": -1, "num_leaves": 15},
        reflgb.Dataset(X, label=y), num_boost_round=10)
    path = str(tmp_path / "ref.txt")
    ref_bst.save_model(path)
    ours = lgb.Booster(model_file=path)
    np.testing.assert_allclose(ours.predict(Xt), ref_bst.predict(Xt),
                               atol=1e-12)


def test_multiclass_parity(reflgb):
    import lightgbm_tpu as lgb
    d = np.loadtxt(f"{EXAMPLES}/multiclass_classification/multiclass.train")
    X, y = d[:, 1:], d[:, 0]
    params = {"objective": "multiclass", "num_class": 5,
              "metric": "multi_logloss", "verbosity": -1}

    def run(pkg):
        ev = {}
        tr = pkg.Dataset(X, label=y)
        pkg.train(params, tr, num_boost_round=20,
                  valid_sets=[pkg.Dataset(X, label=y, reference=tr)],
                  evals_result=ev, verbose_eval=False)
        return ev["valid_0"]["multi_logloss"]

    ours, ref = run(lgb), run(reflgb)
    assert abs(ours[0] - ref[0]) < 1e-6
    assert abs(ours[-1] - ref[-1]) < 2e-2


def test_regression_parity(reflgb):
    import lightgbm_tpu as lgb
    d = np.loadtxt(f"{EXAMPLES}/regression/regression.train")
    X, y = d[:, 1:], d[:, 0]
    params = {"objective": "regression", "metric": "l2", "verbosity": -1}

    def run(pkg):
        ev = {}
        tr = pkg.Dataset(X, label=y)
        pkg.train(params, tr, num_boost_round=20,
                  valid_sets=[pkg.Dataset(X, label=y, reference=tr)],
                  evals_result=ev, verbose_eval=False)
        return ev["valid_0"]["l2"]

    ours, ref = run(lgb), run(reflgb)
    assert abs(ours[0] - ref[0]) < 1e-7
    assert abs(ours[-1] - ref[-1]) < 2e-3


def _load_svm(path):
    """rank.train is LibSVM-format; densify via the reference loader-free
    parser (small files)."""
    labels, rows, maxf = [], [], 0
    with open(path) as f:
        for line in f:
            parts = line.split()
            labels.append(float(parts[0]))
            d = {}
            for tok in parts[1:]:
                k, v = tok.split(":")
                d[int(k)] = float(v)
                maxf = max(maxf, int(k))
            rows.append(d)
    X = np.zeros((len(rows), maxf + 1))
    for i, d in enumerate(rows):
        for k, v in d.items():
            X[i, k] = v
    return X, np.asarray(labels)


def test_lambdarank_trajectory_parity(reflgb):
    """NDCG trajectory parity on the stock lambdarank example (reference:
    rank_objective.hpp LambdarankNDCG; DCGCalculator label gains)."""
    import lightgbm_tpu as lgb
    X, y = _load_svm(f"{EXAMPLES}/lambdarank/rank.train")
    group = np.loadtxt(f"{EXAMPLES}/lambdarank/rank.train.query").astype(int)
    params = {"objective": "lambdarank", "metric": "ndcg",
              "ndcg_eval_at": [5], "verbosity": -1, "num_leaves": 31,
              "min_data_in_leaf": 20}

    def run(pkg):
        ev = {}
        tr = pkg.Dataset(X, label=y, group=group)
        bst = pkg.train(params, tr, num_boost_round=20,
                        valid_sets=[pkg.Dataset(X, label=y, group=group,
                                                reference=tr)],
                        evals_result=ev, verbose_eval=False)
        return bst, ev["valid_0"]["ndcg@5"]

    (bo, ours), (br, ref) = run(lgb), run(reflgb)
    # iteration 1 agrees to ~1e-3, not exactly: this package computes exact
    # sigmoids where the reference quantizes through a lookup table
    # (rank_objective.hpp:234-255; deviation documented in
    # objective_rank.py), so lambdas — and the first tree — differ in the
    # table's quantization error.  Measured round 4: |diff| = 3.2e-4.
    assert abs(ours[0] - ref[0]) < 1e-3, (ours[0], ref[0])
    assert abs(ours[-1] - ref[-1]) < 1e-2, (ours[-1], ref[-1])


def test_lambdarank_model_cross_load(reflgb, tmp_path):
    import lightgbm_tpu as lgb
    X, y = _load_svm(f"{EXAMPLES}/lambdarank/rank.train")
    group = np.loadtxt(f"{EXAMPLES}/lambdarank/rank.train.query").astype(int)
    Xt, _ = _load_svm(f"{EXAMPLES}/lambdarank/rank.test")
    Xt = Xt[:, :X.shape[1]] if Xt.shape[1] >= X.shape[1] else np.pad(
        Xt, ((0, 0), (0, X.shape[1] - Xt.shape[1])))
    bst = lgb.train({"objective": "lambdarank", "verbosity": -1,
                     "num_leaves": 15},
                    lgb.Dataset(X, label=y, group=group), num_boost_round=8)
    path = str(tmp_path / "rank.txt")
    bst.save_model(path)
    np.testing.assert_allclose(
        bst.predict(Xt), reflgb.Booster(model_file=path).predict(Xt),
        atol=1e-12)


def test_multiclass_model_cross_load(reflgb, tmp_path):
    import lightgbm_tpu as lgb
    d = np.loadtxt(f"{EXAMPLES}/multiclass_classification/multiclass.train")
    X, y = d[:, 1:], d[:, 0]
    bst = lgb.train({"objective": "multiclass", "num_class": 5,
                     "verbosity": -1, "num_leaves": 15},
                    lgb.Dataset(X, label=y), num_boost_round=6)
    path = str(tmp_path / "mc.txt")
    bst.save_model(path)
    np.testing.assert_allclose(
        bst.predict(X[:500]),
        reflgb.Booster(model_file=path).predict(X[:500]), atol=1e-12)


def _categorical_xy(n=5000, seed=5):
    rng = np.random.RandomState(seed)
    c1 = rng.randint(0, 12, n).astype(np.float64)
    c2 = rng.randint(0, 40, n).astype(np.float64)
    x3 = rng.rand(n)
    logit = (np.isin(c1, [2, 3, 7]) * 1.4 + (c2 % 5 == 0) * 0.9
             + 1.2 * x3 - 1.2 + 0.3 * rng.randn(n))
    y = (logit > 0).astype(np.float64)
    return np.column_stack([c1, c2, x3]), y


def test_categorical_trajectory_parity(reflgb):
    """Categorical split parity: count-sorted bins, one-hot and sorted
    many-vs-many categorical thresholds (reference:
    FindBestThresholdCategoricalInner, feature_histogram.hpp:259)."""
    import lightgbm_tpu as lgb
    X, y = _categorical_xy()
    params = {"objective": "binary", "metric": "auc", "verbosity": -1,
              "num_leaves": 15, "min_data_in_leaf": 20,
              "categorical_feature": [0, 1]}

    def run(pkg):
        ev = {}
        tr = pkg.Dataset(X, label=y, categorical_feature=[0, 1])
        pkg.train(params, tr, num_boost_round=20,
                  valid_sets=[pkg.Dataset(X, label=y, reference=tr,
                                          categorical_feature=[0, 1])],
                  evals_result=ev, verbose_eval=False)
        return ev["valid_0"]["auc"]

    ours, ref = run(lgb), run(reflgb)
    # iteration 1 agrees to ~5e-4, not exactly: categorical candidate
    # pruning here uses EXACT per-bin counts where the reference estimates
    # counts as RoundInt(hess * cnt_factor) (feature_histogram.hpp:813;
    # deviation documented in ops/split.py), shifting which categories
    # clear min_data_per_group.  Measured round 4: |diff| = 1.5e-4.
    assert abs(ours[0] - ref[0]) < 5e-4, (ours[0], ref[0])
    assert abs(ours[-1] - ref[-1]) < 5e-3, (ours[-1], ref[-1])


def test_categorical_model_cross_load(reflgb, tmp_path):
    import lightgbm_tpu as lgb
    X, y = _categorical_xy()
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 15, "categorical_feature": [0, 1]},
                    lgb.Dataset(X, label=y, categorical_feature=[0, 1]),
                    num_boost_round=8)
    path = str(tmp_path / "cat.txt")
    bst.save_model(path)
    np.testing.assert_allclose(
        bst.predict(X[:500]),
        reflgb.Booster(model_file=path).predict(X[:500]), atol=1e-12)


def test_large_scale_parity_150k(reflgb):
    """Trajectory parity at >=100k rows (round-3 review, item 9: previous
    parity evidence topped out at 7k rows)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    n = 150_000
    X = rng.rand(n, 12).astype(np.float64)
    w = rng.randn(12)
    logit = X @ w + 1.5 * X[:, 0] * X[:, 1] + 0.5 * rng.randn(n)
    y = (logit > np.median(logit)).astype(np.float64)
    params = {"objective": "binary", "metric": "auc", "verbosity": -1,
              "num_leaves": 63, "min_data_in_leaf": 20, "max_bin": 63}

    def run(pkg):
        ev = {}
        tr = pkg.Dataset(X, label=y)
        pkg.train(params, tr, num_boost_round=10,
                  valid_sets=[pkg.Dataset(X, label=y, reference=tr)],
                  evals_result=ev, verbose_eval=False)
        return ev["valid_0"]["auc"]

    ours, ref = run(lgb), run(reflgb)
    assert abs(ours[0] - ref[0]) < 1e-7, (ours[0], ref[0])
    diffs = np.abs(np.asarray(ours) - np.asarray(ref))
    assert diffs.max() < 3e-3, f"diverged: {diffs.max():.4g}"
