"""Bytes one tree's update of a validation score *needs*, from shapes
alone: every validation row's bins read once and its 4-byte score read and
written in place (counted once, as ``rooflines/traverse.py`` counts a
score).  The tree's node tables (a few KB) are left out.  HBM bound: a
walk does no arithmetic to speak of."""


def tree_bytes(rows, features, bin_itemsize=1):
    return rows * (features * bin_itemsize + 4)
