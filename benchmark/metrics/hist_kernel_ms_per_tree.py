"""Device milliseconds per tree in the Mosaic (Pallas) custom calls: the
histogram accumulate kernel is the only one the training step runs."""


def read(ctx):
    dev = ctx["trace"]["devices"][0]
    if not dev["kernel_calls"] or not ctx["run"].trees:
        return None
    return 1e3 * dev["kernel_s"] / ctx["run"].trees
