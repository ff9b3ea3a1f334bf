"""Of the splits of the trees the host took, the share on a categorical
column, in percent: 100 x ``tree_splits_categorical_total`` /
``tree_splits_total``, the program's counters, bumped when a tree reaches
the host (warm rounds included).  A property of the data more than of the
program: it says how much of the cell's time stands on the categorical arm.
``None`` where the program made no such counter."""
from benchmark.metrics._program import counter


def read(ctx):
    splits = counter("tree_splits_total")
    if not splits:
        return None
    return 100.0 * (counter("tree_splits_categorical_total") or 0) / splits
