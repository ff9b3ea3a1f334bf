"""Of the trees applied to validation scores, the share whose rows found
their leaves by the path form (whole feature rows and one matmul against the
tree's root paths) and not by the walk over tree levels, in percent:
100 x ``valid_update_trees_routed_total`` / (routed + walked), the program's
two counters, bumped on the host a validation update by trees x classes.
Which of the two a booster bumps is fixed when its programs are built, from
the data set's metadata (no categorical feature, on the accelerator), so the
share says how far the fast program engages in the cell.  ``None`` where the
program made neither counter (a program without the path form, or a run
without a validation set)."""
from benchmark.metrics._program import counter


def read(ctx):
    routed = counter("valid_update_trees_routed_total") or 0
    total = routed + (counter("valid_update_trees_walked_total") or 0)
    return 100.0 * routed / total if total else None
