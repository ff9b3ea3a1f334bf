"""The whole step's share of the chip: least time the chip needs for one
tree's unavoidable traffic (``rooflines/hist.py`` ``tree_min_bytes``, HBM
bound) over the measured seconds per tree of this run, in percent."""


def read(ctx):
    cfg = ctx["config"]
    per_tree = ctx["e2e"].get("train_s_per_tree")
    if not per_tree or not ctx["run"].trees:
        return None
    roof = ctx["roofline"]("hist")
    nbytes = roof.tree_min_bytes(int(cfg["rows"]) // ctx["chips"],
                                 int(cfg["features"]),
                                 cfg["hist"]["bin_itemsize"])
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / per_tree
