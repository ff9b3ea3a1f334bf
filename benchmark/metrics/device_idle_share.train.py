"""Share of the traced window in which no op ran on the device, percent."""
from benchmark.lib.trace_reduce import idle_share_percent


def read(ctx):
    return idle_share_percent(ctx["trace"])
