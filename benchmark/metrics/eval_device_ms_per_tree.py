"""Device milliseconds per tree in the programs that update the validation
scores (``metrics/_eval.py``): every validation row walked through the
round's new tree.  ``None`` where no such program ran (a cell that
trains without a validation set)."""
from benchmark.metrics._eval import eval_modules


def read(ctx):
    found = eval_modules(ctx)
    if not found or not ctx["run"].trees:
        return None
    return 1e3 * found[1] / ctx["run"].trees
