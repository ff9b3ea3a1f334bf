"""Request wall time outside the device: the ``predict`` spans of the
window minus the device-busy union, per million rows (host-to-device copy,
device-to-host copy of what the traversal returns, the host leaf sum)."""


def read(ctx):
    run = ctx["run"]
    if not run.requests:
        return None
    wall = sum(ctx["samples"]["predict"][-run.requests:])
    return 1e3 * (wall - ctx["trace"]["busy_s"]) / (run.rows / 1e6)
