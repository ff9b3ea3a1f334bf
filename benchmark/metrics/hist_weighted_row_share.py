"""Of the rows the window's accumulate passes read, the share that carry a
nonzero weight, in percent: sum ``goss_kept`` x passes / sum ``n_pad`` x
passes over the window's ``grower.tree`` records, a tree's passes being its
``rounds`` and the root's.  A pass reads every row of the padded matrix
whatever its weight, so under GOSS this reads the kept share times rows /
``n_pad``; a pass over only the sampled rows would read close to 100.
``None`` where the records carry no ``goss_kept`` (a program that does not
count it)."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees or any("goss_kept" not in t for t in trees):
        return None
    passes = [t["rounds"] + 1 for t in trees]
    n_pad = int(ctx["run"].info["n_pad"])
    return 100.0 * sum(t["goss_kept"] * p for t, p in zip(trees, passes)) \
        / (n_pad * sum(passes))
