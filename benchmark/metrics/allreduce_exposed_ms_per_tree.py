"""The part of the all-reduce time during which no compute op ran on that
chip, in milliseconds per tree."""


def read(ctx):
    dev = ctx["trace"]["devices"][0]
    if dev["collective_s"] <= 0 or not ctx["run"].trees:
        return None
    return 1e3 * dev["collective_exposed_s"] / ctx["run"].trees
