"""Least time for the bytes the traversals need (``rooflines/traverse.py``)
over the traversal's device time, in percent."""


def read(ctx):
    run = ctx["run"]
    busy = ctx["trace"]["devices"][0]["busy_s"]
    if busy <= 0 or not run.requests:
        return None
    roof = ctx["roofline"]("traverse")
    nbytes = run.requests * roof.request_bytes(
        run.request_rows, run.features, int(ctx["traffic"]["forest_trees"]),
        int(ctx["config"]["params"]["num_leaves"]))
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / busy
