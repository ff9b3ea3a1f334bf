"""Seeded data in the shapes of upstream's ``examples/`` directories.

The public-API tests (``test_engine.py``, ``test_predict.py``,
``test_cli.py``) train on these in place of upstream's example files, which
no machine here has: the same row counts, widths, class and query structure,
so the tests' ``num_leaves`` / ``min_data_in_leaf`` / round counts keep
their meaning.  NumPy only; every law has a nonlinear signal plus noise, so
a metric lands well inside its range.  Each set is made once a process and
handed out read-only.
"""
from collections import namedtuple
from functools import lru_cache

import numpy as np

Split = namedtuple("Split", "X y weight group", defaults=(None, None))


def _split(X, y, weight=None, group=None):
    for a in (X, y, weight, group):
        if a is not None:
            a.setflags(write=False)
    return Split(X, y, weight, group)


def _signal(X):
    return 2.0 * (X[:, 0] > 0.3) - X[:, 4] + 0.7 * X[:, 1] * X[:, 2]


def _dense(seed, label, weighted=False):
    """(train 7000 x 28, test 500 x 28) with ``label(rng, X) -> y``."""
    rng = np.random.default_rng(seed)
    out = []
    for n in (7000, 500):
        X = rng.standard_normal((n, 28))
        w = rng.uniform(0.5, 1.5, n).round(3) if weighted else None
        out.append(_split(X, label(rng, X), w))
    return tuple(out)


@lru_cache(maxsize=None)
def binary():
    """``binary_classification``: 0/1 label, a per-row weight vector."""
    return _dense(1, lambda rng, X: (
        _signal(X) + 1.3 * rng.standard_normal(len(X)) > 0.74).astype(float),
        weighted=True)


@lru_cache(maxsize=None)
def regression():
    """``regression``: a real-valued label."""
    return _dense(2, lambda rng, X: _signal(X) + rng.standard_normal(len(X)))


@lru_cache(maxsize=None)
def multiclass():
    """``multiclass_classification``: 5 classes, cut from the noisy signal
    at the law's quintiles."""
    return _dense(3, lambda rng, X: np.digitize(
        _signal(X) + 1.2 * rng.standard_normal(len(X)),
        [-0.9, 0.23, 1.24, 2.43]).astype(float))


@lru_cache(maxsize=None)
def rank():
    """``lambdarank``: documents x 300 mostly-zero features in queries of
    unequal length, grades 0-4 -> (train ~3000 docs / 200 queries, test
    ~750 / 50)."""
    rng = np.random.default_rng(4)
    w = rng.standard_normal(300) * (rng.random(300) < 0.1)
    out = []
    for queries in (200, 50):
        group = rng.integers(5, 26, queries)
        n = int(group.sum())
        X = rng.random((n, 300)) * (rng.random((n, 300)) < 0.1)
        s = X @ w + np.sin(3.0 * X[:, 0]) + 0.4 * rng.standard_normal(n)
        y = np.digitize(s, np.quantile(s, [0.5, 0.75, 0.9, 0.97])).astype(float)
        out.append(_split(X, y, group=group))
    return tuple(out)


TRAIN_CONF = """\
task = train
objective = binary
metric = binary_logloss,auc
data = {name}.train
valid_data = {name}.test
num_trees = 100
learning_rate = 0.1
num_leaves = 63
feature_fraction = 0.8
bagging_freq = 5
bagging_fraction = 0.8
min_data_in_leaf = 50
min_sum_hessian_in_leaf = 5.0
"""


def write_files(dirpath, name, splits, libsvm=False):
    """Write ``splits`` (train, test) as upstream lays an example out:
    ``<name>.train`` / ``<name>.test`` (label first; TSV, or LibSVM's
    ``index:value`` of the non-zeros), ``.weight`` / ``.query`` beside a
    split that has them, and ``binary_classification``'s ``train.conf``
    with paths relative to ``dirpath``.  -> the conf's path."""
    for part, s in zip(("train", "test"), splits):
        path = dirpath / f"{name}.{part}"
        if libsvm:
            path.write_text("".join(
                f"{int(y)} " + " ".join(f"{j}:{row[j]:.6g}"
                                        for j in np.flatnonzero(row)) + "\n"
                for row, y in zip(s.X, s.y)))
        else:
            np.savetxt(path, np.column_stack([s.y, s.X]), delimiter="\t",
                       fmt="%.9g")
        if s.weight is not None:
            np.savetxt(f"{path}.weight", s.weight, fmt="%g")
        if s.group is not None:
            np.savetxt(f"{path}.query", s.group, fmt="%d")
    conf = dirpath / "train.conf"
    conf.write_text(TRAIN_CONF.format(name=name))
    return conf
