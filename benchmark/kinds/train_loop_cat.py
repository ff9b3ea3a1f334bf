"""``train_loop_cat``: ``train_loop`` over data with categorical columns.

The run is ``kinds/train_loop.py``'s own, round for round the same timed
window; this kind only listens at the hooks that run offers a planted fault
(``lib/faults.py``), keeps the ``Booster`` it is shown, and once the window
has closed takes each followed tree's ``decision_type``, ``cat_boundaries``
and ``cat_threshold`` from the host model beside ``lib/traffic.TREE_FIELDS``.
The comparison is ``correct.train_numbers`` with the categorical reference
(``lib/reference_gbdt_cat.py``) in the numeric one's place, so the numbers
carry the same names; it adds ``cat_splits_followed``, the count of
categorical nodes in the followed trees (printed), and
``trees_without_cat_split``, the followed trees that hold none (limit 0: a
run that took no categorical split has not shown what the cell is for).
Before anything else it asks the program whether it can hold the
configuration at all (``preflight``).
"""
import sys
from unittest import mock

import numpy as np

from benchmark.lib import correct, faults, lookup
from benchmark.lib import reference_gbdt_cat as ref_cat
from benchmark.lib.traffic import _generator, generate

CAT_FIELDS = ("decision_type", "cat_boundaries", "cat_threshold")
train_loop = lookup.load_module(lookup.REPO / "benchmark/kinds/train_loop.py")

PRIMARY = train_loop.PRIMARY
LIMITS = dict(train_loop.LIMITS, trees_without_cat_split=0)
primary = train_loop.primary


class CatAsNumeric(faults.Fault):
    """A tree handed out with its categorical nodes marked numeric: the
    node's rows then read as sent by ``code <= threshold``."""
    name = "cat_as_numeric"
    done = 0

    def after_pull(self, bst):
        models = bst.boosting._models
        for model in models[self.done:]:
            model.decision_type &= ~np.int8(ref_cat.CATEGORICAL_BIT)
        self.done = len(models)


FAULTS = dict(faults.TRAIN, cat_as_numeric=CatAsNumeric)


class _Listener(faults.Fault):
    """Hands every hook on to the planted fault, if any, and keeps the
    ``Booster`` the run builds."""

    def __init__(self, inner):
        self.inner = inner or faults.Fault()
        self.bst = None

    def after_build(self, bst):
        self.bst = bst
        self.inner.after_build(bst)

    def before_step(self, bst):
        return self.inner.before_step(bst)

    def after_step(self, bst, token):
        self.inner.after_step(bst, token)

    def after_pull(self, bst):
        self.inner.after_pull(bst)


PREFLIGHT_ROWS = 200_000       # the program's own sample for bin edges


def preflight(manifest, config, seed):
    """Ends the run, soon and with exit code 5, on a program that cannot
    hold the configuration: one whose binning rule gives a categorical
    column more bins than a node's category set has bits silently drops
    categories from every set past that width (and, read on the chip, does
    not finish set-up on the click log's id columns inside a run's time).
    The first rows' categorical columns go through the program's own rule
    (``binning.BinMapper.find_bin``, called as ``Dataset`` calls it): a
    second, and nothing on the ring."""
    from lightgbm_tpu.binning import BinMapper, BinType
    from lightgbm_tpu.ops.split import MAX_CAT_WORDS
    gen, gen_args = _generator(manifest, config)
    rows = min(int(config["rows"]), PREFLIGHT_ROWS)
    X, _, fields = generate(gen, seed, rows, int(config["features"]),
                            **gen_args)
    params = config["params"]
    wide = {}
    for f in fields.get("categorical_feature", ()):
        col = X[:, f].astype(np.float64)
        mapper = BinMapper()
        mapper.find_bin(
            col[np.isnan(col) | (np.abs(col) > 1e-35)], rows,
            int(params.get("max_bin", 255)),
            min_data_in_bin=int(params.get("min_data_in_bin", 3)),
            min_split_data=int(params.get("min_data_in_leaf", 20)),
            pre_filter=True, bin_type=BinType.CATEGORICAL, use_missing=True,
            zero_as_missing=False, forced_upper_bounds=())
        if mapper.num_bin > 32 * MAX_CAT_WORDS:
            wide[f] = int(mapper.num_bin)
    if wide:
        print(f"benchmark/kinds/train_loop_cat.py: this program cannot hold "
              f"the configuration: categorical columns {wide} (column: "
              f"bins) are binned wider than the {32 * MAX_CAT_WORDS} bins a "
              "node's category set holds", file=sys.stderr, flush=True)
        sys.exit(5)


def run(manifest, config, traffic, cell_file, seed, seconds,
        spans, compiles, devices, on_window=None, fault=None):
    with spans.span("preflight"):
        preflight(manifest, config, seed)
    ear = _Listener(fault)
    out = train_loop.run(manifest, config, traffic, cell_file, seed, seconds,
                         spans, compiles, devices, on_window, ear)
    models = ear.bst.models

    def with_sets(tree, index):
        return dict(tree, **{k: np.array(getattr(models[index], k))
                             for k in CAT_FIELDS})

    out.answers = [with_sets(t, i) for i, t in enumerate(out.answers)]
    if out.last is not None:
        out.last["tree"] = with_sets(out.last["tree"], out.last["index"])
    out.kind = "train_loop_cat"
    free = out.free

    def free_all():
        ear.bst = None
        free()
    out.free = free_all
    return out


def numbers(run, detail=None):
    reference = ref_cat.CatReference(run.params,
                                     run.aux["categorical_feature"])
    with mock.patch.object(correct, "ref", reference):
        out = correct.train_numbers(run, detail=detail)
    trees = list(run.answers)
    if run.last is not None and run.last["index"] >= len(trees):
        trees.append(run.last["tree"])
    cats = [int(np.sum(t["decision_type"] & ref_cat.CATEGORICAL_BIT))
            for t in trees]
    out["cat_splits_followed"] = float(sum(cats))
    out["trees_without_cat_split"] = float(sum(c == 0 for c in cats))
    return out
