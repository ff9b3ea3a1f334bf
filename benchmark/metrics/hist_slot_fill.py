"""Of the slots the histogram passes ran at, the share that held a live
candidate, in percent: 100 x sum ``offered`` / sum ``slots`` over the
window's ``grower.tree`` records.  ``slots`` sums the slot width of every
accumulate pass of a tree, the root's included; the one-hot kernel pays for
every slot of its width whatever the rows hold, so what is left was
multiplied as zeros.  ``None`` where the records carry no ``slots`` (a
program that runs every pass at the round cap and does not count)."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees or any("slots" not in t for t in trees):
        return None
    slots = sum(t["slots"] for t in trees)
    if not slots:
        return None
    return 100.0 * sum(t["offered"] for t in trees) / slots
