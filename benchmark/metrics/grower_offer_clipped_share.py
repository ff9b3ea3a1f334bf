"""Of the rounds the rounds grower ran, the share its offer clipped, in
percent: 100 x sum ``clipped`` / sum ``rounds`` over the window's
``grower.tree`` records.  A round may offer no more candidates than the
loop's ``offer`` (the width of its histogram pass, following what the last
round committed); ``clipped`` counts the rounds in which the offer was what
bound the candidates *and* all of them committed, that is the rounds the
offer, not a child, ended the best-first prefix: each may have cost one
pass more.  ``None`` where the records carry no ``clipped`` (a program
whose rounds offer every candidate up to the round cap)."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees or any("clipped" not in t for t in trees):
        return None
    rounds = sum(t["rounds"] for t in trees)
    if not rounds:
        return None
    return 100.0 * sum(t["clipped"] for t in trees) / rounds
