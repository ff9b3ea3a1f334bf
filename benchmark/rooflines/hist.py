"""Bytes and operations a histogram pass *needs*, from shapes alone.

One pass builds the histograms of the leaves being split from every row
once: it has to read each row's bins, its gradient values and the id of
the leaf it sits in, and write at least one [channels, features, bins]
histogram of 4-byte cells.  It adds one value per row, feature and channel.
Whatever implements the pass (one-hot matmul on the MXU, scatter, sort) is
held to the same count, so a share of this roofline cannot pass 100% for a
kernel that moved only these bytes at the peak bandwidth.
"""


def pass_bytes(rows, features, bin_itemsize, value_bytes_per_row, bins,
               channels):
    per_row = features * bin_itemsize + value_bytes_per_row + 4
    return rows * per_row + channels * features * bins * 4


def pass_ops(rows, features, channels):
    return rows * features * channels


def tree_min_bytes(rows, features, bin_itemsize):
    """Traffic no tree can avoid: every row's bins, gradient, hessian and
    score read and the score written once (4 x 4 bytes)."""
    return rows * (features * bin_itemsize + 16)


def least_seconds(nbytes, ops, peaks, ops_peak_key):
    """(seconds, which bound) of the larger of bytes/bandwidth, ops/peak."""
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    by_ops = ops / peaks[ops_peak_key]
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "ops")
