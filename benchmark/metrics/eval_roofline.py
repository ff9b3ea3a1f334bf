"""Least time one tree's walk over the binned validation rows needs
(``rooflines/valid_update.py``) times the walks made, over the device time
of the programs that made them, in percent."""
from benchmark.metrics._eval import eval_modules


def read(ctx):
    found = eval_modules(ctx)
    rows = getattr(ctx["run"], "valid_rows", 0)
    if not found or found[1] <= 0 or not rows:
        return None
    roof = ctx["roofline"]("valid_update")
    nbytes = found[0] * roof.tree_bytes(
        rows, int(ctx["config"]["features"]),
        ctx["config"]["hist"]["bin_itemsize"])
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / found[1]
