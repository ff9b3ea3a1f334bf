"""Device milliseconds per million rows scored: every device op of the
window belongs to the traversal program."""


def read(ctx):
    busy = ctx["trace"]["devices"][0]["busy_s"]
    if busy <= 0 or not ctx["run"].rows:
        return None
    return 1e3 * busy / (ctx["run"].rows / 1e6)
