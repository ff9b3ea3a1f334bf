"""Device milliseconds per tree in all-reduce ops on the first chip."""


def read(ctx):
    dev = ctx["trace"]["devices"][0]
    if dev["collective_s"] <= 0 or not ctx["run"].trees:
        return None
    return 1e3 * dev["collective_s"] / ctx["run"].trees
