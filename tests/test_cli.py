"""CLI application tests.

Mirrors the reference CLI-vs-Python consistency strategy
(tests/c_api_test + tests/python_package_test/test_consistency.py:10-60):
train via examples/binary_classification/train.conf (written beside seeded
data of that example's shape by ``tests/example_data.py``) through the CLI,
predict through the CLI, and cross-check against the Python API on the same
data.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import example_data
import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(args, cwd):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "lightgbm_tpu"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


def test_cli_train_predict_consistency(tmp_path):
    conf = example_data.write_files(tmp_path, "binary", example_data.binary())
    r = _run_cli([f"config={conf}", "num_trees=15", "metric_freq=10",
                  "output_model=model.txt"], cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    assert (tmp_path / "model.txt").exists()

    r2 = _run_cli(["task=predict", "data=binary.test",
                   "input_model=model.txt",
                   "output_result=preds.txt"], cwd=str(tmp_path))
    assert r2.returncode == 0, r2.stderr[-2000:]
    cli_pred = np.loadtxt(tmp_path / "preds.txt")

    # Python API prediction from the same saved model must agree exactly
    bst = lgb.Booster(model_file=str(tmp_path / "model.txt"))
    data = np.loadtxt(tmp_path / "binary.test")
    py_pred = bst.predict(data[:, 1:])
    np.testing.assert_allclose(cli_pred, py_pred, rtol=1e-9, atol=1e-12)


def test_cli_convert_model_compiles_and_matches(tmp_path):
    import shutil
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    train = example_data.binary()[0]
    X, y = train.X[:200], train.y[:200]
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7},
                    lgb.Dataset(X, label=y), num_boost_round=4,
                    verbose_eval=False)
    bst.save_model(str(tmp_path / "m.txt"))
    r = _run_cli(["task=convert_model", "input_model=m.txt",
                  "convert_model=m.cpp"], cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]

    harness = r"""
#include <cstdio>
#include <cstdlib>
extern "C" void Predict(const double*, double*);
int main(int argc, char** argv) {
  int nf = atoi(argv[1]);
  double feat[256], out[8];
  while (true) {
    for (int i = 0; i < nf; ++i)
      if (scanf("%lf", &feat[i]) != 1) return 0;
    Predict(feat, out);
    printf("%.17g\n", out[0]);
  }
}
"""
    (tmp_path / "main.cpp").write_text(harness)
    c = subprocess.run(["g++", "-O1", "-o", "pred", "m.cpp", "main.cpp"],
                       cwd=str(tmp_path), capture_output=True, text=True)
    assert c.returncode == 0, c.stderr[-2000:]
    Xt = X[:32]
    inp = "\n".join(" ".join(f"{v:.17g}" for v in row) for row in Xt)
    run = subprocess.run(["./pred", str(X.shape[1])], input=inp,
                         cwd=str(tmp_path), capture_output=True, text=True)
    cpp_raw = np.array([float(v) for v in run.stdout.split()])
    py_raw = bst.predict(Xt, raw_score=True)
    np.testing.assert_allclose(cpp_raw, py_raw, rtol=1e-12, atol=1e-12)
