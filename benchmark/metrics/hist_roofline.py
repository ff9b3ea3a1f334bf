"""Least time for the passes the kernel made (``rooflines/hist.py``: each
custom call is one pass over every row of this chip) over the kernel's
device time, in percent.  HBM bound at every size here."""


def read(ctx):
    dev = ctx["trace"]["devices"][0]
    if not dev["kernel_calls"] or dev["kernel_s"] <= 0:
        return None
    cfg, hist = ctx["config"], ctx["config"]["hist"]
    roof = ctx["roofline"]("hist")
    rows = int(cfg["rows"]) // ctx["chips"]
    nbytes = roof.pass_bytes(rows, int(cfg["features"]),
                             hist["bin_itemsize"],
                             hist["value_bytes_per_row"],
                             int(cfg["params"]["max_bin"]) + 1,
                             hist["channels"])
    ops = roof.pass_ops(rows, int(cfg["features"]), hist["channels"])
    least, _bound = roof.least_seconds(nbytes, ops, ctx["peaks"],
                                       hist["ops_peak"])
    return 100.0 * dev["kernel_calls"] * least / dev["kernel_s"]
