"""Of the rows a sampled tree could be grown on, the share GOSS kept (weight
not 0), in percent: 100 x sum ``goss_kept`` / (rows x trees) over the
window's ``grower.tree`` records.  ``goss_kept`` is the round's top set
(every row whose |g*h| is at least the k-th largest) and the sampled rest,
counted on the device beside the tree; 0.2 / 0.1 read about 30, and more
where ties at the threshold are all kept.  ``None`` where the records carry
no ``goss_kept`` (a program that does not count it)."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees or any("goss_kept" not in t for t in trees):
        return None
    rows = int(ctx["run"].rows)
    return 100.0 * sum(t["goss_kept"] for t in trees) / (rows * len(trees))
