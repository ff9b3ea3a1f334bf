"""Device milliseconds per tree in every other op of the round programs
(gradients, split scan, routing, commit, score update: one number until
the program names its scopes)."""


def read(ctx):
    dev = ctx["trace"]["devices"][0]
    if not ctx["run"].trees or not dev["ops"]:
        return None
    other = sum(dev["ops"].values()) - dev["kernel_s"] - dev["collective_s"]
    return 1e3 * other / ctx["run"].trees
