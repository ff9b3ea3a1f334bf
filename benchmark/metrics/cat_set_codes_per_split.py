"""Codes in the left set of a categorical split, on average over the
categorical splits of the trees the host took: ``tree_cat_set_codes_total``
/ ``tree_splits_categorical_total``, the program's counters (warm rounds
included).  ``None`` where the program made no such counter or the run took
no categorical split."""
from benchmark.metrics._program import counter


def read(ctx):
    splits = counter("tree_splits_categorical_total")
    codes = counter("tree_cat_set_codes_total")
    if not splits or codes is None:
        return None
    return codes / splits
