"""Bytes a forest traversal *needs*, from shapes alone: every row's
features read once (4-byte floats), one raw score per row and class
written, and the forest's node tables read once per request: per split a
feature id, a threshold and two children (4 x 4 bytes), per leaf a value
(8 bytes)."""


def forest_bytes(trees, leaves):
    return trees * ((leaves - 1) * 16 + leaves * 8)


def request_bytes(rows, features, trees, leaves, classes=1):
    return rows * (features * 4 + 4 * classes) + forest_bytes(trees, leaves)


def score_min_bytes(rows, features):
    """Host array in, host scores out: features in, an 8-byte score out."""
    return rows * (features * 4 + 8)
