"""Of the candidates the rounds grower offered (a trip builds that many
smaller-child histograms), the share it committed, in percent:
100 x sum ``applied`` / sum ``offered`` over the window's ``grower.tree``
records.  What is left was built and thrown away."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    offered = sum(t["offered"] for t in trees) if trees else 0
    if not offered:
        return None
    return 100.0 * sum(t["applied"] for t in trees) / offered
