"""Trips of the rounds grower's ``while_loop`` per tree, mean over the
window's trees: the ``rounds`` of the program's ``grower.tree`` records
(three int32 scalars carried through the loop and pulled beside the
tree).  A trip is one histogram kernel pass."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees:
        return None
    return sum(t["rounds"] for t in trees) / len(trees)
