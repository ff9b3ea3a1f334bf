"""Of the rows the window's accumulate passes could read, the share they
read, in percent: 100 x sum ``pass_rows`` / (``n_pad`` x sum passes) over
the window's ``grower.tree`` records, a tree's passes being its ``rounds``
and the root's.  A pass over the whole padded matrix reads 100; a sampled
tree grown on its kept rows gathered to the front of the row buffer reads
about the kept share, rounded up to whole row tiles of the kernel.
``None`` where the records carry no ``pass_rows`` (a program that does not
count it)."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees or any("pass_rows" not in t for t in trees):
        return None
    passes = sum(t["rounds"] + 1 for t in trees)
    n_pad = int(ctx["run"].info["n_pad"])
    return 100.0 * sum(t["pass_rows"] for t in trees) / (n_pad * passes)
